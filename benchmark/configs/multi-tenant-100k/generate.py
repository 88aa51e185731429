"""BASELINE config 5 as a deployment, from a seed: namespaces-as-a-service
on one cluster. Tenants have a rank; every namespace, every user and every
team (``group``) belongs to one tenant drawn from a Zipfian over that rank
(p(rank k) ~ 1 / k ** zipf_constant), so the largest tenant holds about an
eighth of each and most tenants a handful. Hard multi-tenancy: a user is
in one tenant, and every grant stays inside it. A user is a member of
``teams_per_user`` teams of its tenant, a namespace has its tenant, one
creator (a user of its tenant) and ``team_grants_per_namespace`` viewer
grants to teams of its tenant, a tenant ``admins_per_tenant`` admins among
its users; every such draw is uniform and a repeat is one grant. A tenant
that drew no user or no team has none of the grants that need one: its
namespaces stay, and nobody sees them."""

import numpy as np


def _members(tenant_of: np.ndarray, n_tenants: int) -> tuple:
    """-> (objects ordered by tenant, each tenant's first position in
    that order, each tenant's count)."""
    order = np.argsort(tenant_of, kind="stable")
    count = np.bincount(tenant_of, minlength=n_tenants)
    return order, np.cumsum(count) - count, count


def _draw(rng, tenant: np.ndarray, members: tuple) -> tuple:
    """One uniform member of each entry's tenant. -> (kept entries as a
    mask, the member drawn for each kept entry)."""
    order, first, count = members
    keep = count[tenant] > 0
    t = tenant[keep]
    at = first[t] + (rng.random(len(t)) * count[t]).astype(np.int64)
    return keep, order[at]


def generate(sizes: dict, seed: int) -> dict:
    n_ns, n_users, n_tenants, n_teams = (
        sizes[k] for k in ("namespaces", "users", "tenants", "teams"))
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_tenants + 1) ** sizes["zipf_constant"]
    p /= p.sum()
    ns_tenant = rng.choice(n_tenants, size=n_ns, p=p)
    user_tenant = rng.choice(n_tenants, size=n_users, p=p)
    team_tenant = rng.choice(n_tenants, size=n_teams, p=p)
    users_of = _members(user_tenant, n_tenants)
    teams_of = _members(team_tenant, n_tenants)

    per_user = sizes["teams_per_user"]
    member_u = np.repeat(np.arange(n_users), per_user)
    keep, member_g = _draw(rng, user_tenant[member_u], teams_of)
    member_u = member_u[keep]
    per_ns = sizes["team_grants_per_namespace"]
    granted_ns = np.repeat(np.arange(n_ns), per_ns)
    keep, granted_g = _draw(rng, ns_tenant[granted_ns], teams_of)
    granted_ns = granted_ns[keep]
    keep, creator = _draw(rng, ns_tenant, users_of)
    created_ns = np.flatnonzero(keep)
    admin_t = np.repeat(np.arange(n_tenants), sizes["admins_per_tenant"])
    keep, admin_u = _draw(rng, admin_t, users_of)
    admin_t = admin_t[keep]
    return {
        "types": {"user": [("u", n_users)], "group": [("team", n_teams)],
                  "tenant": [("t", n_tenants)], "namespace": [("ns", n_ns)]},
        "edges": [
            ("group", "member", "user", "", member_g, member_u),
            ("tenant", "admin", "user", "", admin_t, admin_u),
            ("namespace", "tenant", "tenant", "", np.arange(n_ns),
             ns_tenant),
            ("namespace", "creator", "user", "", created_ns, creator),
            ("namespace", "viewer", "group", "member", granted_ns,
             granted_g),
        ],
        # not read by the harness: who belongs to which tenant
        "tenant_of": {"namespace": ns_tenant, "user": user_tenant,
                      "group": team_tenant},
    }

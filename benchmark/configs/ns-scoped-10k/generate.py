"""Namespace-scoped RBAC as a deployment, from a seed: groups of uniformly
drawn members; group and direct bindings and creators drawn uniformly over
all namespaces (a repeat is one grant); every service and every pod in a
uniformly drawn namespace. Services and pods are name segments only, one
segment a namespace (``ns<k>/s<j>``, ``ns<k>/p<j>``), so that the kube
namespace of an object is the namespace the rules check: NO edge names a
service or a pod, which is what a cluster that grants by namespace keeps."""

import numpy as np


def _spread(rng, n_objects: int, n_ns: int, letter: str) -> list:
    """``n_objects`` objects, each in a uniformly drawn namespace, as name
    segments in namespace order."""
    per_ns = np.bincount(rng.integers(n_ns, size=n_objects), minlength=n_ns)
    return [(f"ns{k}/{letter}", int(c))
            for k, c in enumerate(per_ns.tolist()) if c]


def generate(sizes: dict, seed: int) -> dict:
    n_ns, n_users, n_groups = (sizes[k] for k in ("namespaces", "users",
                                                  "groups"))
    rng = np.random.default_rng(seed)
    member_g = np.repeat(np.arange(n_groups), sizes["members_per_group"])
    member_u = rng.integers(n_users, size=len(member_g))
    bound_ns = np.repeat(np.arange(n_ns),
                         sizes["group_bindings_per_namespace"])
    bound_g = rng.integers(n_groups, size=len(bound_ns))
    direct_u = np.repeat(np.arange(n_users), sizes["bindings_per_user"])
    direct_ns = rng.integers(n_ns, size=len(direct_u))
    ns_creator = rng.integers(n_users, size=n_ns)
    services = _spread(rng, sizes["services"], n_ns, "s")
    pods = _spread(rng, sizes["pods"], n_ns, "p")
    return {
        "types": {"user": [("u", n_users)], "group": [("g", n_groups)],
                  "namespace": [("ns", n_ns)], "service": services,
                  "pod": pods},
        "edges": [
            ("group", "member", "user", "", member_g, member_u),
            ("namespace", "viewer", "group", "member", bound_ns, bound_g),
            ("namespace", "viewer", "user", "", direct_ns, direct_u),
            ("namespace", "creator", "user", "", np.arange(n_ns),
             ns_creator),
        ],
    }

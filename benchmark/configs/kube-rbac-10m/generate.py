"""The headline deployment's relationships from a seed: a copy of
``bench.py::build_columns`` (same draws in the same order) that keeps the
integer ids, so the plain reference never parses ten million strings."""

import numpy as np


def generate(sizes: dict, seed: int) -> dict:
    n_pods, n_users, n_ns, n_groups, n_rels = (
        sizes[k] for k in ("pods", "users", "namespaces", "groups",
                           "relationships"))
    rng = np.random.default_rng(seed)
    # group membership: ~20 users per group
    gm = min(20 * n_groups, n_rels // 20)
    member_g = rng.integers(n_groups, size=gm)
    member_u = rng.integers(n_users, size=gm)
    # namespace viewer grants via groups (2 per namespace)
    nv = 2 * n_ns
    view_ns = rng.integers(n_ns, size=nv)
    view_g = rng.integers(n_groups, size=nv)
    # every pod lives in a namespace
    pod_ns = rng.integers(n_ns, size=n_pods)
    # the rest: flat pod#viewer@user direct grants, deduplicated
    n_flat = n_rels - gm - nv - n_pods
    pair = rng.integers(0, n_pods * n_users, size=int(n_flat * 1.01),
                        dtype=np.int64)
    pair = np.unique(pair)[:n_flat]
    rng.shuffle(pair)
    return {
        "types": {"user": [("u", n_users)], "group": [("g", n_groups)],
                  "namespace": [("ns", n_ns)], "pod": [("ns/p", n_pods)]},
        "edges": [
            ("group", "member", "user", "", member_g, member_u),
            ("namespace", "viewer", "group", "member", view_ns, view_g),
            ("pod", "namespace", "namespace", "", np.arange(n_pods), pod_ns),
            ("pod", "viewer", "user", "", pair // n_users, pair % n_users),
        ],
    }

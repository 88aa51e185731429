"""BASELINE config 3 from a seed: the generator of ``bench.py::run_suite``
(leaf membership, leaf in mid, mid in top, one top group viewing each
namespace) with the seed taken from the run instead of fixed at 3, and
the integer ids kept for the plain reference. Group indices run leaf,
then mid, then top."""

import numpy as np


def generate(sizes: dict, seed: int) -> dict:
    n_users, n_g2, n_g1, n_g0, n_ns, per = (
        sizes[k] for k in ("users", "groups_leaf", "groups_mid",
                           "groups_top", "namespaces", "members_per_leaf"))
    rng = np.random.default_rng(seed)
    g2_0, g1_0, g0_0 = 0, n_g2, n_g2 + n_g1  # index bases inside "group"
    m = per * n_g2
    leaf_g = g2_0 + rng.integers(n_g2, size=m)
    leaf_u = rng.integers(n_users, size=m)
    mid_of_leaf = g1_0 + rng.integers(n_g1, size=n_g2)
    top_of_mid = g0_0 + rng.integers(n_g0, size=n_g1)
    top_of_ns = g0_0 + rng.integers(n_g0, size=n_ns)
    return {
        "types": {"user": [("u", n_users)],
                  "group": [("g2-", n_g2), ("g1-", n_g1), ("g0-", n_g0)],
                  "namespace": [("ns", n_ns)]},
        "edges": [
            ("group", "member", "user", "", leaf_g, leaf_u),
            ("group", "member", "group", "member", mid_of_leaf,
             g2_0 + np.arange(n_g2)),
            ("group", "member", "group", "member", top_of_mid,
             g1_0 + np.arange(n_g1)),
            ("namespace", "viewer", "group", "member", np.arange(n_ns),
             top_of_ns),
        ],
    }

"""BASELINE config 4 as a deployment, from a seed: namespaces in a tree
of ``len(levels)`` levels (``levels[k]`` namespaces at depth k, the roots
first in the index order), each namespace below a root with one parent
drawn uniformly from the level above; groups of uniformly drawn members;
group and direct bindings and creators drawn uniformly over all
namespaces; every pod in a uniformly drawn namespace. A pod of a leaf is
``len(levels)`` arrows from its tenant's root. Pods are indexed by
namespace, one name segment each (``ns<k>/p<j>``), so that the kube
namespace of a pod is the tree's."""

import numpy as np


def generate(sizes: dict, seed: int) -> dict:
    levels = sizes["levels"]
    n_users, n_groups, n_pods = (sizes[k] for k in ("users", "groups",
                                                    "pods"))
    n_ns = sum(levels)
    rng = np.random.default_rng(seed)
    base = np.concatenate([[0], np.cumsum(levels)])
    child = np.arange(levels[0], n_ns)
    parent = np.concatenate([
        base[k - 1] + rng.integers(levels[k - 1], size=levels[k])
        for k in range(1, len(levels))])
    m = n_groups * sizes["members_per_group"]
    member_g = np.repeat(np.arange(n_groups), sizes["members_per_group"])
    member_u = rng.integers(n_users, size=m)
    per_ns = sizes["group_bindings_per_namespace"]
    bound_ns = np.repeat(np.arange(n_ns), per_ns)
    bound_g = rng.integers(n_groups, size=n_ns * per_ns)
    per_user = sizes["bindings_per_user"]
    direct_u = np.repeat(np.arange(n_users), per_user)
    direct_ns = rng.integers(n_ns, size=n_users * per_user)
    ns_creator = rng.integers(n_users, size=n_ns)
    pods_in = np.bincount(rng.integers(n_ns, size=n_pods), minlength=n_ns)
    pod_ns = np.repeat(np.arange(n_ns), pods_in)
    pod_creator = rng.integers(n_users, size=n_pods)
    return {
        "types": {"user": [("u", n_users)], "group": [("g", n_groups)],
                  "namespace": [("ns", n_ns)],
                  "pod": [(f"ns{k}/p", int(c))
                          for k, c in enumerate(pods_in.tolist()) if c]},
        "edges": [
            ("group", "member", "user", "", member_g, member_u),
            ("namespace", "parent", "namespace", "", child, parent),
            ("namespace", "viewer", "group", "member", bound_ns, bound_g),
            ("namespace", "viewer", "user", "", direct_ns, direct_u),
            ("namespace", "creator", "user", "", np.arange(n_ns),
             ns_creator),
            ("pod", "namespace", "namespace", "", np.arange(n_pods),
             pod_ns),
            ("pod", "creator", "user", "", np.arange(n_pods), pod_creator),
        ],
    }

"""Operation kind ``list_scoped``: ``GET <path>`` of a whole namespaced
resource as one user, in a deployment whose rights are held on the
namespace and not on the object: the rule postfilters the list, one check
an object against the object's own namespace.

Cell parameters: ``path``, ``type`` (the listed objects, named
``<namespace>/<name>``), ``scope_type`` and ``permission`` (what each
object's check asks of its namespace) and ``users``: ``"permutation"``
draws every request's user from one seeded permutation of the users, so
none repeats.

``expect`` is this configuration's copy of the reference's last step: the
objects whose namespace, read from the object's own name, is among the
namespaces ``reference.py`` says the user holds the permission on. It asks
the program nothing.
"""

import functools

import numpy as np


@functools.lru_cache(maxsize=8)
def _scope_of(dep, typ: str, scope_type: str) -> np.ndarray:
    """The ``scope_type`` index of every object of ``typ``, by its name's
    part before the slash."""
    scopes = dep.names(scope_type)
    order = np.argsort(scopes)
    mine = np.char.partition(dep.names(typ), "/")[:, 0]
    at = np.searchsorted(scopes[order], mine)
    if not np.array_equal(scopes[order][at], mine):
        raise ValueError(f"list_scoped: a {typ} outside every {scope_type}")
    return order[at]


def plan(op: dict, count: int, rng, dep, ref) -> list:
    n_users = dep.count("user")
    if op.get("users", "permutation") != "permutation":
        raise ValueError(f"list_scoped: unknown user draw {op['users']!r}")
    if count > n_users:
        raise ValueError(
            f"list_scoped: {count} distinct users asked of {n_users}")
    users = rng.permutation(n_users)[:count]
    names = dep.names("user")
    return [{"kind": "list_scoped", "method": "GET", "path": op["path"],
             "user": str(names[u]), "user_idx": int(u),
             "key": f"{op['scope_type']}#{op['permission']}",
             "type": op["type"], "scope_type": op["scope_type"]}
            for u in users.tolist()]


def expect(req: dict, dep, ref) -> tuple:
    """-> (status, sorted ids the answer must hold)."""
    seen = ref.lookup(req["key"], req["user_idx"])
    kept = np.isin(_scope_of(dep, req["type"], req["scope_type"]), seen)
    return 200, sorted(dep.names(req["type"])[kept].tolist())

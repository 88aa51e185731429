"""Operation kind ``get``: ``GET`` of one object as one user.

Cell parameters: ``path`` (a template over ``{namespace}`` and ``{name}``
of the object's engine id), ``type`` and ``permission`` (what the rule
checks) and ``visible_share``: with that probability the object is drawn
uniformly from the user's visible set per the reference (expect 200),
otherwise uniformly from all objects of the type (expect 403 unless it
happens to be visible). Users are uniform; no (user, object) pair repeats.
"""

import numpy as np


def plan(op: dict, count: int, rng, dep, ref) -> list:
    n_users, n_obj = dep.count("user"), dep.count(op["type"])
    key = f"{op['type']}#{op['permission']}"
    draw = int(count * 1.1) + 8
    users = rng.integers(n_users, size=draw)
    objs = rng.integers(n_obj, size=draw)
    want_visible = rng.random(draw) < op["visible_share"]
    pick = rng.random(draw)
    for i in np.nonzero(want_visible)[0].tolist():
        seen = ref.lookup(key, int(users[i]))
        if len(seen):
            objs[i] = seen[int(pick[i] * len(seen))]
    _, first = np.unique(users.astype(np.int64) * n_obj + objs,
                         return_index=True)
    keep = np.sort(first)[:count]
    if len(keep) < count:
        raise ValueError(f"get: only {len(keep)} distinct pairs of {count}")
    unames, onames = dep.names("user"), dep.names(op["type"])
    out = []
    for u, o in zip(users[keep].tolist(), objs[keep].tolist()):
        ns, _, name = str(onames[o]).rpartition("/")
        out.append({"kind": "get", "method": "GET",
                    "path": op["path"].format(namespace=ns, name=name),
                    "user": str(unames[u]), "user_idx": u, "obj_idx": o,
                    "key": key, "type": op["type"]})
    return out


def expect(req: dict, dep, ref) -> tuple:
    """-> (status, engine ids the answer must hold): the object for a
    user who may see it, 403 and nothing otherwise."""
    if ref.check(req["key"], req["obj_idx"], req["user_idx"]):
        return 200, [str(dep.names(req["type"])[req["obj_idx"]])]
    return 403, []

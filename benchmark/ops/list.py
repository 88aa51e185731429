"""Operation kind ``list``: ``GET <path>`` of a whole resource as one user.

Cell parameters: ``path``, ``type`` and ``permission`` (what the rule's
prefilter looks up) and ``users``: ``"permutation"`` draws every request's
user from one seeded permutation of the type's users, so none repeats.
"""


def plan(op: dict, count: int, rng, dep, ref) -> list:
    n_users = dep.count("user")
    if op.get("users", "permutation") != "permutation":
        raise ValueError(f"list: unknown user draw {op['users']!r}")
    if count > n_users:
        raise ValueError(f"list: {count} distinct users asked of {n_users}")
    users = rng.permutation(n_users)[:count]
    names = dep.names("user")
    return [{"kind": "list", "method": "GET", "path": op["path"],
             "user": str(names[u]), "user_idx": int(u),
             "key": f"{op['type']}#{op['permission']}", "type": op["type"]}
            for u in users.tolist()]


def expect(req: dict, dep, ref) -> tuple:
    """-> (status, sorted engine ids the answer must hold)."""
    seen = ref.lookup(req["key"], req["user_idx"])
    return 200, sorted(dep.names(req["type"])[seen].tolist())

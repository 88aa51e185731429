"""Operation kind ``list_cycle``: ``GET <path>`` of a whole resource as
one user, for a cell that sends more lists than the deployment has users:
the users are one seeded permutation of all of them, repeated in the same
order when it runs out, so a user comes back one whole permutation later.

Cell parameters: ``path``, ``type`` and ``permission`` (what the rule's
prefilter looks up). The requests it plans are of kind ``list``: what
``ops/list.py`` expects of a list is expected of these, and the metrics
that read lists read them.
"""

import numpy as np


def plan(op: dict, count: int, rng, dep, ref) -> list:
    users = np.resize(rng.permutation(dep.count("user")), count)
    names = dep.names("user")
    return [{"kind": "list", "method": "GET", "path": op["path"],
             "user": str(names[u]), "user_idx": int(u),
             "key": f"{op['type']}#{op['permission']}", "type": op["type"]}
            for u in users.tolist()]

"""The plain reference: who may see what, straight from the seeded integer
tables of a configuration, by the textbook semantics of its schema. It
imports nothing of the program.

A configuration's ``reference`` block writes each permission as a union of
terms: a relation ``"type#relation"`` or an arrow ``["type#tupleset",
"type#permission"]``. A relation holds for user *u* on the resources it
relates directly to *u*, and on those it relates to a userset
(``subject_type#subject_relation``) that holds for *u*; recursive usersets
(``group#member`` of ``group#member``) are iterated to their fixpoint.
The schemas' other arms (``user:*``, expiration, caveats) have no rows in
these configurations.

``stale_share`` > 0 is the control: the same evaluator answering at a
revision before the last share of each relation's rows was written, which
breaks the consistency guarantee the configurations state.
"""

import numpy as np


class _BySubject:
    """One edge table indexed by subject."""

    def __init__(self, res, sub):
        order = np.argsort(sub, kind="stable")
        self.sub = np.asarray(sub)[order]
        self.res = np.asarray(res)[order]

    def of(self, members: np.ndarray) -> np.ndarray:
        lo = np.searchsorted(self.sub, members, "left")
        hi = np.searchsorted(self.sub, members, "right")
        parts = [self.res[a:b] for a, b in zip(lo.tolist(), hi.tolist())
                 if b > a]
        return np.concatenate(parts) if parts else self.res[:0]


class Reference:
    def __init__(self, deployment, stale_share: float = 0.0):
        self.dep = deployment
        self.permissions = deployment.config["reference"]
        # relation key -> [(subject key or None for a concrete user, table)]
        self.relations = {}
        for rt, rel, st, srel, res, sub in deployment.edges:
            keep = len(res) - int(len(res) * stale_share)
            subject = f"{st}#{srel}" if srel else (None if st == "user"
                                                   else f"{st}#")
            self.relations.setdefault(f"{rt}#{rel}", []).append(
                (subject, st, _BySubject(res[:keep], sub[:keep])))
        self._memo = {}
        self._orders = {}

    def _deps(self, key: str) -> list:
        """The keys whose sets ``_step(key)`` reads."""
        out = [t if isinstance(t, str) else t[1]
               for t in self.permissions.get(key, ())]
        out += [subject for subject, _, _ in self.relations.get(key, ())
                if subject and not subject.endswith("#")]
        return out

    def _order(self, key: str) -> tuple:
        """-> (the keys ``key`` rests on, dependencies first; whether every
        cycle among them is a key resting on itself, so that one pass in
        that order, each such key iterated alone, is the fixpoint)."""
        if key not in self._orders:
            order, seen = [], set()

            def visit(k):
                if k in seen:
                    return
                seen.add(k)
                for d in self._deps(k):
                    visit(d)
                order.append(k)
            visit(key)
            at = {k: i for i, k in enumerate(order)}
            simple = all(at[d] <= at[k] for k in order for d in self._deps(k))
            self._orders[key] = (order, simple)
        return self._orders[key]

    def _step(self, key: str, user: int, have: dict) -> np.ndarray:
        empty = np.zeros(0, dtype=np.int64)
        parts = []
        if key in self.permissions:
            for term in self.permissions[key]:
                if isinstance(term, str):
                    parts.append(have.get(term, empty))
                else:  # arrow: resources whose tupleset subject has the perm
                    for subject, _, table in self.relations.get(term[0], ()):
                        parts.append(table.of(have.get(term[1], empty)))
            return np.unique(np.concatenate(parts)) if parts else empty
        for subject, _, table in self.relations.get(key, ()):
            if subject is None:
                parts.append(table.of(np.asarray([user])))
            elif not subject.endswith("#"):
                parts.append(table.of(have.get(subject, empty)))
        return np.unique(np.concatenate(parts)) if parts else empty

    def lookup(self, key: str, user: int) -> np.ndarray:
        """Sorted indices of the resources on which ``key``
        (``type#permission``) holds for user index ``user``."""
        memo = self._memo.get((key, user))
        if memo is not None:
            return memo
        order, simple = self._order(key)
        have = {}
        while True:
            grew_any = False
            for k in order:
                while True:
                    now = self._step(k, user, have)
                    grew = len(now) != len(have.get(k, ()))
                    if grew:  # sets only grow, so a new length is a new set
                        have[k] = now
                        grew_any = True
                    if not grew or k not in self._deps(k):
                        break
            if simple or not grew_any:
                break
        if len(self._memo) > 1024:
            self._memo.clear()
        self._memo[(key, user)] = have.get(key, np.zeros(0, dtype=np.int64))
        return self._memo[(key, user)]

    def check(self, key: str, resource: int, user: int) -> bool:
        got = self.lookup(key, user)
        i = np.searchsorted(got, resource)
        return bool(i < len(got) and got[i] == resource)

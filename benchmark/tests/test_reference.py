"""reference.py against the program's own plain oracle
(engine/evaluator.py) at rehearsal size, for both configurations, and the
control (a stale revision) against the reference: it must differ."""

import pytest

from deployment import Deployment
from reference import Reference

CASES = [("kube-rbac-10m", "pod", "view"),
         ("kube-rbac-10m", "namespace", "view"),
         ("nested-org-1m", "namespace", "view")]


@pytest.mark.parametrize("config,typ,perm", CASES)
def test_reference_equals_the_oracle(config, typ, perm):
    from spicedb_kubeapi_proxy_tpu.engine import Engine

    dep = Deployment(config, 5, rehearse=True)
    ref = Reference(dep)
    e = Engine(dep.text("bootstrap.yaml"))
    e.bulk_load(dep.columns())
    oracle = e.oracle()
    names, users = dep.names(typ), dep.names("user")
    seen_any = False
    for u in range(0, dep.count("user"), 3):
        want = sorted(oracle.lookup_resources(typ, perm, "user",
                                              str(users[u])))
        got = sorted(names[ref.lookup(f"{typ}#{perm}", u)].tolist())
        assert got == want, (config, u)
        seen_any |= bool(want)
        for r in range(0, dep.count(typ), 17):
            assert ref.check(f"{typ}#{perm}", r, u) == (str(names[r]) in want)
    assert seen_any


@pytest.mark.parametrize("config,typ,perm", CASES[::2])
def test_the_control_answers_differently(config, typ, perm):
    dep = Deployment(config, 5, rehearse=True)
    ref = Reference(dep)
    stale = Reference(dep, dep.config["control"]["stale_share"])
    differ = sum(len(ref.lookup(f"{typ}#{perm}", u))
                 != len(stale.lookup(f"{typ}#{perm}", u))
                 for u in range(dep.count("user")))
    assert differ > 0

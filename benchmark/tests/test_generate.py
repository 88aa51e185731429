"""The generators: the same seed gives the same deployment and plan, byte
for byte; another seed another; the headline copy equals bench.py's."""

import hashlib
import json

import numpy as np

import run as bench_run
from deployment import Deployment
from reference import Reference


def plan_bytes(cell_name: str, seed: int) -> bytes:
    cell = bench_run.load_json("workloads", cell_name + ".json")
    cell.update(cell["rehearse"])
    dep = Deployment(cell["config"], seed, rehearse=True)
    plan = bench_run.make_plan(cell, seed, dep, Reference(dep))
    return "\n".join(json.dumps(r) for r in plan).encode()


def test_plan_is_byte_identical_for_a_seed_and_differs_for_another():
    for cell in ("kube-rbac-10m.list-distinct", "nested-org-1m.get-distinct"):
        a, b = plan_bytes(cell, 3000000019), plan_bytes(cell, 3000000019)
        assert hashlib.sha256(a).digest() == hashlib.sha256(b).digest()
        assert a != plan_bytes(cell, 3000000020)


def test_no_user_lists_twice_and_no_pair_is_got_twice():
    lists = [json.loads(x) for x in plan_bytes(
        "kube-rbac-10m.list-distinct", 11).splitlines()]
    assert len({r["user"] for r in lists}) == len(lists)
    gets = [json.loads(x) for x in plan_bytes(
        "nested-org-1m.get-distinct", 11).splitlines()]
    assert len({(r["user"], r["path"]) for r in gets}) == len(gets)


def test_headline_columns_equal_bench_build_columns():
    import bench

    dep = Deployment("kube-rbac-10m", 77, rehearse=True)
    s = dep.sizes
    want = bench.build_columns(s["pods"], s["users"], s["namespaces"],
                               s["groups"], s["relationships"], seed=77)
    got = dep.columns()
    assert set(got) == set(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), k

"""The ``ns-tree-10hop`` deployment and its cell: the generator's shapes
at both sizes, the two readers this cell brought (on a cut of its own
trace recorded on the chip, fixtures/list-ns-distinct.README.txt), and
the cell taken by the harness through its files alone."""

import asyncio
import json
import os

import numpy as np
import pytest

import run as bench_run
import trace_reduce
from conftest import BENCH
from deployment import Deployment
from reference import Reference
from run import by_name

CONFIG = "ns-tree-10hop"
CELL = "ns-tree-10hop.list-ns-distinct"
CUT = os.path.join(BENCH, "tests", "fixtures",
                   "list-ns-distinct.cut.xplane.pb")
NEW_METRICS = {"core_trips_per_dispatch", "core_edge_passes_per_dispatch",
               "tree_residual_hbm_pct", "core_device_pct"}


def edge(dep, rt, rel, st):
    (e,) = [e for e in dep.edges if (e[0], e[1], e[2]) == (rt, rel, st)]
    return e[4], e[5]


@pytest.mark.parametrize("rehearse,namespaces,relationships", [
    (True, 271, 4681), (False, 10_000, 739_900)])
def test_the_tree_has_ten_levels_and_every_parent_is_one_level_up(
        rehearse, namespaces, relationships):
    dep = Deployment(CONFIG, 2800000051, rehearse=rehearse)
    levels = dep.sizes["levels"]
    assert len(levels) == 10 and sum(levels) == namespaces
    assert dep.count("namespace") == namespaces
    assert dep.n_relationships() == relationships
    level = np.repeat(np.arange(10), levels)
    child, parent = edge(dep, "namespace", "parent", "namespace")
    # every namespace below a root has exactly one parent, one level up
    assert np.array_equal(child, np.arange(levels[0], namespaces))
    assert np.array_equal(level[parent], level[child] - 1)
    # so a pod of a leaf is ten arrows from its tenant's root
    up = np.arange(namespaces)
    up[child] = parent
    at = np.flatnonzero(level == 9)
    for _ in range(9):
        at = up[at]
    assert (level[at] == 0).all()
    s = dep.sizes
    member_g, _ = edge(dep, "group", "member", "user")
    assert np.array_equal(np.bincount(member_g), np.full(
        s["groups"], s["members_per_group"]))
    bound_ns, _ = edge(dep, "namespace", "viewer", "group")
    assert np.array_equal(np.bincount(bound_ns), np.full(namespaces, 2))
    _, direct_u = edge(dep, "namespace", "viewer", "user")
    assert np.array_equal(np.bincount(direct_u), np.full(s["users"], 2))
    # pods are named by the namespace of the tree they live in
    pod, pod_ns = edge(dep, "pod", "namespace", "namespace")
    assert len(pod) == s["pods"] == dep.count("pod")
    names, ns_names = dep.names("pod"), dep.names("namespace")
    for i in (0, len(pod) // 2, len(pod) - 1):
        assert names[i].startswith(ns_names[pod_ns[i]] + "/p")
    assert len(set(names.tolist())) == len(names)
    kube_ns, _ = dep.upstream_objects()["pods"][-1]
    assert kube_ns == ns_names[pod_ns[-1]]


def test_the_same_seed_gives_the_same_tree_and_another_another():
    a, b, c = (Deployment(CONFIG, seed, rehearse=True)
               for seed in (2800000057, 2800000057, 2800000059))
    for x, y in zip(a.edges, b.edges):
        assert np.array_equal(x[4], y[4]) and np.array_equal(x[5], y[5])
    assert not np.array_equal(edge(a, "namespace", "parent", "namespace")[1],
                              edge(c, "namespace", "parent", "namespace")[1])


def test_core_edge_passes_reads_gauges_and_the_histogram():
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    reader = by_name("readers", "core_edge_passes")
    args = bench_run.load_json(
        "metrics", "core_edge_passes_per_dispatch.json")["args"]
    deltas = {"engine_fixpoint_iterations_count": 40.0,
              "engine_fixpoint_iterations_sum": 220.0}
    metrics.gauge("engine_core_edges").set(524_288)
    metrics.gauge("engine_residual_edges").set(899_800)
    got = reader.read(args, {"delta": deltas.get})
    assert got == pytest.approx((524_288 * 5.5 + 375_512) / 1e6)
    # no dispatch observed; a program without the gauge (a parent commit)
    assert reader.read(args, {"delta": {}.get}) is None
    assert reader.read(dict(args, core="engine_no_such_gauge"),
                       {"delta": deltas.get}) is None


@pytest.fixture(scope="module")
def cut():
    return by_name("readers", "scope_device_share").load(CUT)


def test_the_cut_carries_the_scope_paths_and_the_span(cut):
    lo, hi = cut["span"]
    assert 0.2e9 < hi - lo < 0.4e9
    assert len(cut["ops"]) == 855
    paths = set(cut["scopes"].values())
    assert any(p.startswith("jit(sdbkp_fixpoint)/core/while/body/")
               for p in paths)
    assert any("/level1/" in p for p in paths)


def test_most_of_the_busy_time_is_under_the_core_scope(cut):
    reader = by_name("readers", "scope_device_share")
    core = reader.share_pct(cut, "core")
    rest = sum(reader.share_pct(cut, s)
               for s in ("level1", "level2", "readout"))
    assert core == pytest.approx(81.7155, abs=1e-3)
    assert core + rest == pytest.approx(100.0, abs=1.0)
    assert reader.share_pct(cut, "no_such_scope") == 0.0
    # the wire reader and jax's own agree on the operations' time
    from jax.profiler import ProfileData

    busy = trace_reduce.reduce_profile(ProfileData.from_file(CUT))["busy_s"]
    _, owned = trace_reduce.own_time(trace_reduce._clip(
        cut["ops"], *cut["span"]))
    assert sum(owned.values()) / 1e9 == pytest.approx(busy, rel=1e-6)
    # nothing without a scope path anywhere (a CPU trace), or no trace
    assert reader.share_pct(dict(cut, scopes={}), "core") is None
    assert reader.read({"scope": "core"}, {}) is None


def drive(trace, sabotage=None, seed=2800000061):
    args = bench_run.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearse"])
    return asyncio.run(bench_run.run_cell(args, sabotage=sabotage))


def test_the_cell_arrives_through_its_files_alone():
    """No file the benchmark had names the cell: the two end-to-end
    metrics say ``*``, the per-layer ones are the four files this cell
    brought, and BENCHMARK.json's entries are theirs."""
    assert {m["name"] for m in bench_run.metric_files(
        "end_to_end", CELL)} == {"requests_per_s", "setup_s"}
    mine = bench_run.metric_files("metrics", CELL)
    assert {m["name"] for m in mine} == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in mine)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["configs"][-1]["reduced"] == []
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "list-ns-distinct",
        "chips": 1, "why": bench_run.load_json(
            "workloads", CELL + ".json")["why"]}
    assert [m["name"] for m in manifest["per_layer"][-4:]] == [
        "core_trips_per_dispatch", "core_edge_passes_per_dispatch",
        "tree_residual_hbm_pct", "core_device_pct"]


def test_a_sound_run_is_correct_and_a_dropped_namespace_is_not():
    sound = drive(0)
    assert sound["exit"] == 0 and sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert {"setup_s", "requests_per_s"} <= set(sound["metrics"])

    def drop_a_listed_namespace(cfg):
        inner = cfg.engine.lookup_resources

        def lookup_resources(*a, **kw):
            return inner(*a, **kw)[1:]
        cfg.engine.lookup_resources = lookup_resources
    broken = drive(0, sabotage=drop_a_listed_namespace)
    assert broken["correct"] is False
    assert broken["checks"]["wrong_answers"]["value"] > 0


def test_a_traced_rehearsal_reports_the_counts_and_no_device_share():
    out = drive(1)
    assert out["correct"] is True
    got = out["metrics"]
    assert set(got) == {"core_trips_per_dispatch",
                        "core_edge_passes_per_dispatch"}
    assert 2.0 <= got["core_trips_per_dispatch"]["value"] <= 12.0
    assert got["core_edge_passes_per_dispatch"]["unit"] == "Medges"


def test_the_stale_reference_in_the_programs_place_is_not_correct():
    cell = bench_run.load_json("workloads", CELL + ".json")
    cell.update(cell["rehearse"])
    for seed in (5, 4000000007):
        dep = Deployment(CONFIG, seed, rehearse=True)
        ref = Reference(dep)
        stale = Reference(dep, dep.config["control"]["stale_share"])
        plan = bench_run.make_plan(cell, seed, dep, ref)
        assert len({r["user"] for r in plan}) == len(plan)

        def answers(of):
            expect = by_name("ops", "list").expect
            return [dict(zip(("status", "names"), expect(req, dep, of)), i=i)
                    for i, req in enumerate(plan)]
        assert bench_run.compare(answers(ref), plan, dep,
                                 ref)["wrong_answers"] == 0
        assert bench_run.compare(answers(stale), plan, dep,
                                 ref)["wrong_answers"] > len(plan) // 2

"""The ``ns-scoped-10k`` deployment and its cell: the generator's shapes
and counts at both sizes and two seeds (no relationship names a service
or a pod), the operation kind this cell brought (the reference's
by-namespace step) against a brute-force loop, the cell taken by the
harness through its files alone, its metric set compared by name and by
inclusion, and the control told from the reference."""

import asyncio
import json
import os

import numpy as np
import pytest

import run as bench_run
from conftest import BENCH
from deployment import Deployment
from reference import Reference
from run import by_name

CONFIG = "ns-scoped-10k"
CELL = "ns-scoped-10k.list-svc-postfilter"
OP = {"kind": "list_scoped", "path": "/api/v1/services", "type": "service",
      "scope_type": "namespace", "permission": "view",
      "users": "permutation"}
# what a run on the CPU can read of the cell's per-layer metrics
COUNTED = {
    "scoped_postfilter_ms", "scoped_postfilter_parse_ms",
    "scoped_postfilter_resolve_ms", "scoped_postfilter_write_ms",
    "scoped_bulk_cache_ms", "scoped_engine_check_ms",
    "scoped_engine_encode_ms", "scoped_engine_enqueue_ms",
    "scoped_device_wait_ms", "scoped_checks_per_list",
    "scoped_distinct_checks_pct", "scoped_kept_pct", "scoped_server_ms",
    "scoped_executor_wait_ms", "scoped_loop_wait_ms", "scoped_upstream_ms",
    "scoped_gc_pause_pct", "scoped_cache_hit_pct",
    "scoped_compiles_in_window", "scoped_list_p95_ms"}
# what only the chip's trace gives
DEVICE = {"scoped_device_ms_per_dispatch", "scoped_device_idle_pct",
          "scoped_peak_hbm_pct", "scoped_hop_roofline"}


def edge(dep, rt, rel, st):
    (e,) = [e for e in dep.edges if (e[0], e[1], e[2]) == (rt, rel, st)]
    return e[4], e[5]


@pytest.mark.parametrize("seed", [3500000051, 4100000009])
@pytest.mark.parametrize(
    "rehearse,namespaces,services,pods,users,groups,members", [
        (True, 100, 300, 3000, 200, 20, 10),
        (False, 10_000, 10_000, 150_000, 10_000, 1000, 50)])
def test_shapes_counts_and_no_edge_names_a_service_or_a_pod(
        rehearse, namespaces, services, pods, users, groups, members, seed):
    dep = Deployment(CONFIG, seed, rehearse=rehearse)
    assert [dep.count(t) for t in ("namespace", "service", "pod", "user",
                                   "group")
            ] == [namespaces, services, pods, users, groups]
    for e in dep.edges:
        assert not {e[0], e[2]} & {"service", "pod"}
    group, member = edge(dep, "group", "member", "user")
    assert np.array_equal(np.bincount(group), np.full(groups, members))
    assert member.min() >= 0 and member.max() < users
    bound, to = edge(dep, "namespace", "viewer", "group")
    assert np.array_equal(np.bincount(bound), np.full(namespaces, 2))
    assert to.max() < groups
    direct, user = edge(dep, "namespace", "viewer", "user")
    assert np.array_equal(np.bincount(user), np.full(users, 2))
    assert direct.max() < namespaces
    created, creator = edge(dep, "namespace", "creator", "user")
    assert np.array_equal(created, np.arange(namespaces))
    assert dep.n_relationships() == groups * members + 2 * namespaces \
        + 2 * users + namespaces
    # an object's kube namespace is a namespace of the deployment, and
    # its name is its own inside it
    ns_names = set(dep.names("namespace").tolist())
    for typ, letter in (("service", "s"), ("pod", "p")):
        names = dep.names(typ)
        assert len(set(names.tolist())) == len(names)
        ns, _, name = np.char.partition(names, "/").T
        assert set(ns.tolist()) <= ns_names
        assert all(n.startswith(letter) for n in name[:50].tolist())
    if not rehearse:
        assert dep.n_relationships() == 100_000
        # 10,000 uniform draws of 10,000 namespaces leave 1/e untouched
        held = len(set(np.char.partition(dep.names("service"),
                                         "/")[:, 0].tolist()))
        assert held == pytest.approx(6321, abs=150)


def test_the_same_seed_gives_the_same_deployment_and_another_others():
    a, b, c = (Deployment(CONFIG, seed, rehearse=True)
               for seed in (3500000057, 3500000057, 3500000059))
    for x, y in zip(a.edges, b.edges):
        assert np.array_equal(x[4], y[4]) and np.array_equal(x[5], y[5])
    assert a.types == b.types and a.types["service"] != c.types["service"]
    assert not np.array_equal(edge(a, "group", "member", "user")[1],
                              edge(c, "group", "member", "user")[1])


@pytest.mark.parametrize("seed", [3500000061, 4100000013])
@pytest.mark.parametrize("typ", ["service", "pod"])
def test_expect_is_the_namespaces_a_user_holds_object_by_object(seed, typ):
    """``expect`` against a loop that asks, object by object, whether the
    part of its name before the slash is a namespace the reference says
    the user may view."""
    dep = Deployment(CONFIG, seed, rehearse=True)
    ref = Reference(dep)
    kind = by_name("ops", "list_scoped")
    plan = kind.plan(dict(OP, type=typ), 40, np.random.default_rng(3),
                     dep, ref)
    users = [r["user_idx"] for r in plan]
    assert len(set(users)) == 40 and {r["kind"] for r in plan} \
        == {"list_scoped"}
    ns_names = dep.names("namespace")
    some = 0
    for req in plan:
        held = set(ns_names[ref.lookup("namespace#view",
                                       req["user_idx"])].tolist())
        brute = sorted(n for n in dep.names(typ).tolist()
                       if n.split("/", 1)[0] in held)
        assert kind.expect(req, dep, ref) == (200, brute)
        some += bool(brute)
    assert some  # not an empty comparison
    with pytest.raises(ValueError):
        kind.plan(OP, dep.count("user") + 1, np.random.default_rng(3), dep,
                  ref)


def test_the_cell_arrives_through_its_files_alone():
    """No file the benchmark had names the cell: the two end-to-end
    metrics say ``*``, the per-layer ones are files that list this cell
    alone, and BENCHMARK.json's entries are theirs, after the entries the
    benchmark had (a later PR appends after these: compared by name and
    by inclusion, never by last place)."""
    assert {m["name"] for m in bench_run.metric_files(
        "end_to_end", CELL)} == {"requests_per_s", "setup_s"}
    mine = bench_run.metric_files("metrics", CELL)
    assert COUNTED | DEVICE <= {m["name"] for m in mine}
    assert all(m["workloads"] == [CELL] and m["moves"] == "requests_per_s"
               and m["name"].startswith("scoped_") for m in mine)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def place(key, name):
        return [e["name"] for e in manifest[key]].index(name)

    assert place("configs", CONFIG) > place("configs", "multi-tenant-100k")
    assert manifest["configs"][place("configs", CONFIG)]["reduced"] == []
    cell = bench_run.load_json("workloads", CELL + ".json")
    # one client: two lists at once fall into step on the cache's locks
    # and a run reads anything from 2.0 to 3.1 requests/s; four chips for
    # steadiness alone: the work is one host thread's, and on a one-chip
    # machine, whose cores are shared, runs spread by 2.7-5.4%
    assert cell["chips"] == 4 and cell["loop"] == "closed"
    assert cell["clients"] == 1
    assert cell["operations"] == [dict(OP, weight=1)]
    assert place("workloads", CELL) > place(
        "workloads", "multi-tenant-100k.list-ns-256")
    assert manifest["workloads"][place("workloads", CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "list-svc-postfilter",
        "chips": 4, "why": cell["why"]}
    after = place("per_layer", "tree_batch_wait_ms")
    for m in mine:
        entry = manifest["per_layer"][place("per_layer", m["name"])]
        assert place("per_layer", m["name"]) > after
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == m["layer"]
    # every layer is one the benchmark already names
    layers = {e["layer"] for e in manifest["per_layer"][:after + 1]}
    assert {m["layer"] for m in mine} <= layers


def drive(trace, seed=3500000067):
    args = bench_run.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearse"])
    return asyncio.run(bench_run.run_cell(args))


def test_a_rehearsal_through_the_files_is_correct():
    sound = drive(0)
    assert sound["exit"] == 0 and sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert {"setup_s", "requests_per_s"} <= set(sound["metrics"])


def test_a_traced_rehearsal_reports_the_counts_and_no_device_share():
    got = drive(1)["metrics"]
    assert COUNTED <= set(got)
    assert not DEVICE & set(got)
    dep = Deployment(CONFIG, 3500000067, rehearse=True)
    # every request a user not seen before: every object one check, none
    # answered by the cache, a question a namespace that holds a service
    assert got["scoped_checks_per_list"]["value"] == dep.count("service")
    assert got["scoped_cache_hit_pct"]["value"] == 0.0
    held = len(set(np.char.partition(dep.names("service"),
                                     "/")[:, 0].tolist()))
    assert got["scoped_distinct_checks_pct"]["value"] == pytest.approx(
        100.0 * held / dep.count("service"))
    assert 0.0 < got["scoped_kept_pct"]["value"] < 50.0


def test_the_stale_reference_in_the_programs_place_is_not_correct():
    cell = bench_run.load_json("workloads", CELL + ".json")
    cell.update(cell["rehearse"])
    for seed in (5, 4000000007):
        dep = Deployment(CONFIG, seed, rehearse=True)
        ref = Reference(dep)
        stale = Reference(dep, dep.config["control"]["stale_share"])
        plan = bench_run.make_plan(cell, seed, dep, ref)

        def answers(of):
            expect = by_name("ops", "list_scoped").expect
            return [dict(zip(("status", "names"), expect(req, dep, of)), i=i)
                    for i, req in enumerate(plan)]
        assert bench_run.compare(answers(ref), plan, dep,
                                 ref)["wrong_answers"] == 0
        assert bench_run.compare(answers(stale), plan, dep,
                                 ref)["wrong_answers"] > len(plan) // 10

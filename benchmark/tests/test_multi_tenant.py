"""The ``multi-tenant-100k`` deployment and its cell: the generator's
shapes and counts at both sizes and two seeds, the Zipfian share of the
largest tenant, the operation kind this cell brought (one permutation of
the users, repeated in order), and the cell taken by the harness through
its files alone."""

import asyncio
import json
import os

import numpy as np
import pytest

import run as bench_run
from conftest import BENCH
from deployment import Deployment, load_module
from reference import Reference
from run import by_name

CONFIG = "multi-tenant-100k"
CELL = "multi-tenant-100k.list-ns-256"
NEW_METRICS = [
    "tenant_rows_per_dispatch", "tenant_device_ms_per_dispatch",
    "tenant_device_idle_pct", "tenant_peak_hbm_pct",
    "tenant_engine_lookup_ms", "tenant_executor_wait_ms",
    "tenant_body_filter_ms", "tenant_server_ms", "tenant_list_p95_ms",
    "tenant_cache_hit_pct", "tenant_compiles_in_window",
    "tenant_hop_roofline", "tenant_loop_wait_ms",
    "tenant_engine_enqueue_ms", "tenant_device_wait_ms",
    "tenant_device_queue_ms", "tenant_gc_pause_pct"]
# the batcher's own readings, in the cell whose lookups fuse
TREE_CELL = "ns-tree-10hop.list-ns-distinct"
TREE_METRICS = ["tree_rows_per_dispatch", "tree_batch_wait_ms"]


def edge(dep, rt, rel, st):
    (e,) = [e for e in dep.edges if (e[0], e[1], e[2]) == (rt, rel, st)]
    return e[4], e[5]


def tenant_of(dep, seed):
    gen = load_module(os.path.join(dep.dir, "generate.py"), "tenant_gen")
    return gen.generate(dep.sizes, seed)["tenant_of"]


@pytest.mark.parametrize("seed", [3200000051, 4100000007])
@pytest.mark.parametrize("rehearse,namespaces,users,tenants,teams", [
    (True, 1200, 300, 20, 40), (False, 100_000, 10_000, 1000, 2000)])
def test_shapes_counts_and_every_grant_inside_its_tenant(
        rehearse, namespaces, users, tenants, teams, seed):
    dep = Deployment(CONFIG, seed, rehearse=rehearse)
    assert [dep.count(t) for t in ("namespace", "user", "tenant", "group")
            ] == [namespaces, users, tenants, teams]
    of = tenant_of(dep, seed)
    has_user = np.bincount(of["user"], minlength=tenants) > 0
    has_team = np.bincount(of["group"], minlength=tenants) > 0
    # a namespace has one tenant, whatever that tenant drew
    ns, ns_t = edge(dep, "namespace", "tenant", "tenant")
    assert np.array_equal(ns, np.arange(namespaces))
    assert np.array_equal(ns_t, of["namespace"])
    # one creator where the tenant has a user, a user of that tenant
    created, creator = edge(dep, "namespace", "creator", "user")
    assert np.array_equal(created,
                          np.flatnonzero(has_user[of["namespace"]]))
    assert np.array_equal(of["user"][creator], of["namespace"][created])
    # two viewer grants where the tenant has a team, teams of that tenant
    granted, team = edge(dep, "namespace", "viewer", "group")
    assert np.array_equal(np.bincount(granted, minlength=namespaces),
                          2 * has_team[of["namespace"]])
    assert np.array_equal(of["group"][team], of["namespace"][granted])
    # a user is a member of two teams of its own tenant where it has any
    team, member = edge(dep, "group", "member", "user")
    assert np.array_equal(np.bincount(member, minlength=users),
                          2 * has_team[of["user"]])
    assert np.array_equal(of["group"][team], of["user"][member])
    # two admins a tenant that has a user, users of that tenant
    admin_t, admin = edge(dep, "tenant", "admin", "user")
    assert np.array_equal(np.bincount(admin_t, minlength=tenants),
                          2 * has_user)
    assert np.array_equal(of["user"][admin], admin_t)
    assert dep.n_relationships() == namespaces + len(created) \
        + len(granted) + len(member) + len(admin)
    if not rehearse:
        # what the sizes multiply to is 422,000; tenants that drew no
        # team or no user take about a thirteenth of it
        assert 380_000 < dep.n_relationships() < 400_000
        # YCSB's Zipfian constant over 1,000 ranks: 1 / H(1000, 0.99)
        p = 1.0 / np.arange(1, tenants + 1) ** 0.99
        for what in ("namespace", "user", "group"):
            share = np.bincount(of[what]).max() / len(of[what])
            assert share == pytest.approx(p[0] / p.sum(), abs=0.02), what
            assert np.argmax(np.bincount(of[what])) == 0


def test_the_same_seed_gives_the_same_tenants_and_another_others():
    a, b, c = (Deployment(CONFIG, seed, rehearse=True)
               for seed in (3200000057, 3200000057, 3200000059))
    for x, y in zip(a.edges, b.edges):
        assert np.array_equal(x[4], y[4]) and np.array_equal(x[5], y[5])
    assert not np.array_equal(edge(a, "namespace", "tenant", "tenant")[1],
                              edge(c, "namespace", "tenant", "tenant")[1])


def test_list_cycle_repeats_one_permutation_in_the_same_order():
    dep = Deployment(CONFIG, 3200000061, rehearse=True)
    n = dep.count("user")
    op = {"path": "/api/v1/namespaces", "type": "namespace",
          "permission": "view"}
    plan = by_name("ops", "list_cycle").plan(
        op, 2 * n + 7, np.random.default_rng(9), dep, None)
    users = [r["user_idx"] for r in plan]
    assert sorted(users[:n]) == list(range(n))
    assert users[n:2 * n] == users[:n] and users[2 * n:] == users[:7]
    assert users[:n] != list(range(n))
    # the requests are lists: ops/list.py expects their answers, and the
    # metrics that read lists read them
    assert {r["kind"] for r in plan} == {"list"}
    assert plan[0]["path"] == op["path"] \
        and plan[0]["user"] == str(dep.names("user")[users[0]])
    # and the old kind still refuses more requests than users
    with pytest.raises(ValueError):
        by_name("ops", "list").plan(dict(op, users="permutation"), n + 1,
                                    np.random.default_rng(9), dep, None)


def test_the_cell_arrives_through_its_files_alone():
    """No file the benchmark had names the cell: the two end-to-end
    metrics say ``*``, the per-layer ones are the files this cell
    brought, and BENCHMARK.json's entries are theirs, appended."""
    assert {m["name"] for m in bench_run.metric_files(
        "end_to_end", CELL)} == {"requests_per_s", "setup_s"}
    mine = bench_run.metric_files("metrics", CELL)
    assert {m["name"] for m in mine} == set(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "requests_per_s"
               for m in mine)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def place(key, name):
        return [e["name"] for e in manifest[key]].index(name)

    # appended: after what the benchmark had (a later PR appends after)
    assert place("configs", CONFIG) > place("configs", "ns-tree-10hop")
    assert manifest["configs"][place("configs", CONFIG)]["reduced"] == []
    cell = bench_run.load_json("workloads", CELL + ".json")
    assert cell["clients"] == 256 and cell["chips"] == 1
    assert place("workloads", CELL) > place(
        "workloads", "ns-tree-10hop.list-ns-distinct")
    assert manifest["workloads"][place("workloads", CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "list-ns-256",
        "chips": 1, "why": cell["why"]}
    first = place("per_layer", NEW_METRICS[0])
    assert first > place("per_layer", "core_device_pct")
    assert [m["name"] for m in manifest["per_layer"][first:]] \
        == NEW_METRICS + TREE_METRICS
    for name in TREE_METRICS:
        m = bench_run.load_json("metrics", name + ".json")
        assert m["workloads"] == [TREE_CELL] and m["layer"] == "batcher"
        assert manifest["per_layer"][place("per_layer", name)][
            "workloads"] == [TREE_CELL]


def drive(trace, seed=3200000067):
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
    assert {"tenant_rows_per_dispatch", "tenant_cache_hit_pct",
            "tenant_compiles_in_window", "tenant_server_ms",
            "tenant_list_p95_ms", "tenant_loop_wait_ms",
            "tenant_engine_enqueue_ms", "tenant_device_wait_ms",
            "tenant_gc_pause_pct"} <= set(got)
    assert got["tenant_rows_per_dispatch"]["value"] >= 1.0
    assert not {"tenant_device_ms_per_dispatch", "tenant_device_idle_pct",
                "tenant_hop_roofline", "tenant_peak_hbm_pct"} & set(got)


def test_the_stale_reference_in_the_programs_place_is_not_correct():
    cell = bench_run.load_json("workloads", CELL + ".json")
    cell.update(cell["rehearse"])
    for seed in (5, 4000000007):
        dep = Deployment(CONFIG, seed, rehearse=True)
        ref = Reference(dep)
        stale = Reference(dep, dep.config["control"]["stale_share"])
        plan = bench_run.make_plan(cell, seed, dep, ref)

        def answers(of):
            expect = by_name("ops", "list").expect
            return [dict(zip(("status", "names"), expect(req, dep, of)), i=i)
                    for i, req in enumerate(plan)]
        assert bench_run.compare(answers(ref), plan, dep,
                                 ref)["wrong_answers"] == 0
        assert bench_run.compare(answers(stale), plan, dep,
                                 ref)["wrong_answers"] > len(plan) // 2

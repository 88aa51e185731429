"""run.py driven past its look for a chip, at rehearsal size on the CPU:
a sound run comes out correct; the same run with the timed path broken
underneath (an answer altered where the engine produces it) comes out
not correct; a cell, a metric and a reader dropped in as new files run
without an edit anywhere else; and without a chip the command prints no
result and exits non-zero."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
from conftest import BENCH, ROOT

LIST = "kube-rbac-10m.list-distinct"
GET = "nested-org-1m.get-distinct"


def drive(workload, sabotage=None, trace=0, seed=7):
    args = bench_run.parse_args([
        "--workload", workload, "--seed", str(seed), "--seconds", "1",
        "--trace", str(trace), "--rehearse"])
    return asyncio.run(bench_run.run_cell(args, sabotage=sabotage))


def drop_a_listed_object(cfg):
    inner = cfg.engine.lookup_resources

    def lookup_resources(*a, **kw):
        return inner(*a, **kw)[1:]
    cfg.engine.lookup_resources = lookup_resources


def flip_every_third_verdict(cfg):
    inner = cfg.engine.check_bulk
    calls = [0]

    def check_bulk(items, *a, **kw):
        calls[0] += 1
        out = inner(items, *a, **kw)
        return [not v for v in out] if calls[0] % 3 == 0 else out
    cfg.engine.check_bulk = check_bulk


@pytest.mark.parametrize("workload,sabotage", [
    (LIST, drop_a_listed_object), (GET, flip_every_third_verdict)])
def test_sound_run_is_correct_and_broken_path_is_not(workload, sabotage):
    sound = drive(workload)
    assert sound["exit"] == 0 and sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-2:] == ["checks", "exit"]  # the checks come last
    assert all(c["value"] == 0 for c in sound["checks"].values())
    names = set(sound["metrics"])
    assert "setup_s" in names and "requests_per_s" in names
    broken = drive(workload, sabotage=sabotage)
    assert broken["correct"] is False
    assert broken["checks"]["wrong_answers"]["value"] > 0


def test_traced_run_reports_the_per_layer_metrics_it_can_read():
    out = drive(GET, trace=1)
    assert out["correct"] is True
    got = set(out["metrics"])
    assert {"server_ms", "upstream_ms", "engine_check_ms", "cache_hit_pct",
            "authorize_host_ms", "compiles_in_window",
            "client_cpu_pct", "client_p50_ms"} <= got
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    # no device plane on the CPU: the trace's readers return nothing, and
    # nothing is what the line holds — never a 0
    assert not {"hop_roofline", "device_idle_pct",
                "device_ms_per_dispatch"} & got
    assert "breakdown" in out


def test_a_cell_a_metric_and_a_reader_arrive_as_new_files():
    cell = "zz-throwaway.get-few"
    added = {
        os.path.join(BENCH, "workloads", cell + ".json"): json.dumps({
            "config": "nested-org-1m", "traffic": "get-few", "chips": 1,
            "loop": "closed", "clients": 2, "warm_requests": 4,
            "plan_requests": 400, "operations": [
                {"kind": "get", "weight": 1,
                 "path": "/api/v1/namespaces/{name}", "type": "namespace",
                 "permission": "view", "visible_share": 0.9}],
            "why": "throw-away"}),
        os.path.join(BENCH, "metrics", "zz_dispatch_span_ms.json"):
            json.dumps({"name": "zz_dispatch_span_ms", "layer": "engine "
                        "dispatch", "unit": "ms", "better": "lower",
                        "source": "program_span", "moves": "requests_per_s",
                        "workloads": [cell], "reader": "span_mean",
                        "args": {"span": "engine_dispatch", "scale": 1000}}),
        os.path.join(BENCH, "metrics", "zz_sent.json"):
            json.dumps({"name": "zz_sent", "layer": "load generator",
                        "unit": "count", "better": "higher",
                        "source": "host_clock", "moves": "requests_per_s",
                        "workloads": [cell], "reader": "zz_count"}),
        os.path.join(BENCH, "readers", "zz_count.py"):
            "def read(args, ctx):\n    return float(len(ctx['records']))\n",
    }
    try:
        for path, text in added.items():
            with open(path, "w") as f:
                f.write(text)
        out = drive(cell, trace=1)
    finally:
        for path in added:
            os.unlink(path)
    assert out["correct"] is True
    assert out["metrics"]["zz_sent"]["value"] == out["attempted"]
    assert set(out["metrics"]) <= {"zz_sent", "zz_dispatch_span_ms"}


def test_without_a_chip_no_result_and_no_zero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", LIST,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_client_imports_neither_jax_nor_the_package():
    code = ("import sys; sys.path.insert(0, %r); import client; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'numpy', 'spicedb_kubeapi_proxy_tpu')]; "
            "assert not bad, bad" % BENCH)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


@pytest.mark.parametrize("workload", [LIST, GET])
def test_the_control_in_the_programs_place_is_not_correct(workload):
    """The configuration's control (the reference at a stale revision)
    answers the cell's own plan; run.py's comparison fails it, and passes
    the reference's own answers."""
    from deployment import Deployment
    from reference import Reference

    cell = bench_run.load_json("workloads", workload + ".json")
    cell.update(cell["rehearse"])
    for seed in (3, 4000000007, 11):
        dep = Deployment(cell["config"], seed, rehearse=True)
        ref = Reference(dep)
        stale = Reference(dep, dep.config["control"]["stale_share"])
        plan = bench_run.make_plan(cell, seed, dep, ref)

        def answers(of):
            out = []
            for i, req in enumerate(plan):
                status, names = bench_run.by_name(
                    "ops", req["kind"]).expect(req, dep, of)
                out.append({"i": i, "status": status, "names": names})
            return out
        assert bench_run.compare(answers(ref), plan, dep,
                                 ref)["wrong_answers"] == 0
        assert bench_run.compare(answers(stale), plan, dep,
                                 ref)["wrong_answers"] > 0

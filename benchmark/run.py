#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the served path, from a client's
side.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip and serves; the load generator
(``client.py``) is a process of its own. Set-up (``setup_s``: process
start to the first timed request) loads the cell's configuration from
the seed, builds the served configuration with default flags, and warms
the cell's own shapes through the listener. The window is closed loop.
After it the timed answers are compared with the plain reference, and the
last stdout line is the result. A platform other than ``tpu`` is a
failure before any work; ``--rehearse`` walks everything at the
configuration's rehearsal size on whatever JAX finds, prints no result
and exits non-zero. ``--control 1`` (a builder's reading, never the
driver's) also puts the configuration's control — the reference at a
stale revision — in the program's place and prints how it fares.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import collections  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from deployment import Deployment, load_module  # noqa: E402
from trace_reduce import SPAN_MARK  # noqa: E402

TRACE_S = 3.0  # traced span, in the middle of the window
NO_RESULT = 3  # exit code of a run that may print no result


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def metric_files(directory: str, cell: str) -> list:
    """The metric files of ``directory`` that name this cell, or every
    cell (``"workloads": "*"``)."""
    out = []
    for fn in sorted(os.listdir(os.path.join(HERE, directory))):
        if fn.endswith(".json"):
            m = load_json(directory, fn)
            if m["workloads"] == "*" or cell in m["workloads"]:
                out.append(m)
    return out


@functools.lru_cache(maxsize=None)
def by_name(directory: str, name: str):
    """An operation kind or a reader, loaded once by its file's name."""
    return load_module(os.path.join(HERE, directory, name + ".py"),
                       f"bench_{directory}_{name}")


class Lazy:
    """Builds its object at first use: a cell whose plan needs no
    reference pays for it only after the window."""

    def __init__(self, make):
        self._make, self._obj = make, None

    def __getattr__(self, name):
        if self._obj is None:
            self._obj = self._make()
        return getattr(self._obj, name)


class CompileLog:
    """XLA backend compiles and persistent-cache hits, from
    jax.monitoring (as chip_smoke.py counts them)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.compile_s += float(duration)

    def _event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


class GcLog:
    """The serving process's own garbage collections during the window,
    by generation: a diagnosis of the tail, printed and not reported."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0

    def stop(self) -> None:
        gc.callbacks.remove(self._on)

    def line(self) -> str:
        return "server gc in window by generation: " + ", ".join(
            f"gen{g} {n}x {s * 1e3:.0f}ms"
            for g, (n, s) in enumerate(zip(self.count, self.seconds)))


def snapshot(compiles: CompileLog) -> dict:
    """Every series of the program's registry, summed over its labels,
    plus the harness's own compile count."""
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    out = {"bench_backend_compiles": float(compiles.compiles)}
    for line in metrics.render().splitlines():
        if line.startswith("#") or "_bucket" in line:
            continue
        series, _, value = line.rpartition(" ")
        name = series.split("{", 1)[0]
        out[name] = out.get(name, 0.0) + float(value)
    return out


def delta_of(before: dict, after: dict):
    def delta(name: str):
        if name not in after:
            return None
        return after[name] - before.get(name, 0.0)
    return delta


def percentile(values: list, q: float) -> float:
    """Nearest rank: the smallest value with q% of the sample at or
    below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def make_plan(cell: dict, seed: int, dep, ref) -> list:
    """The cell's requests in order, warm-up first: each operation kind
    plans its share, and a seeded draw by weight interleaves them."""
    import numpy as np

    rng = np.random.default_rng([seed, 1])
    total = cell["warm_requests"] + cell["plan_requests"]
    ops = cell["operations"]
    w = np.asarray([op["weight"] for op in ops], dtype=float)
    which = rng.choice(len(ops), size=total, p=w / w.sum())
    plan = [None] * total
    for k, op in enumerate(ops):
        slots = np.nonzero(which == k)[0]
        reqs = by_name("ops", op["kind"]).plan(op, len(slots), rng, dep, ref)
        for i, req in zip(slots.tolist(), reqs):
            plan[i] = req
    return plan


def write_plan(path: str, plan: list) -> None:
    with open(path, "w") as f:
        for req in plan:
            f.write(json.dumps(req) + "\n")


async def run_client(port: int, plan_path: str, out_path: str, clients: int,
                     seconds: float) -> dict:
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "client.py"),
        "--port", str(port), "--plan", plan_path, "--out", out_path,
        "--clients", str(clients), "--seconds", str(seconds),
        stdout=asyncio.subprocess.PIPE)
    try:
        out, _ = await proc.communicate()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    summary = json.loads(out.decode().strip().splitlines()[-1])
    with open(out_path) as f:
        summary["records"] = [json.loads(line) for line in f]
    return summary


def compare(records: list, plan: list, dep, ref) -> dict:
    """Every timed answer against the reference: its status and the
    objects it names."""
    wrong = unanswered = 0
    first = None
    for rec in records:
        req = plan[rec["i"]]
        if rec["status"] == 0:
            unanswered += 1
            continue
        status, names = by_name("ops", req["kind"]).expect(req, dep, ref)
        if rec["status"] != status or sorted(rec["names"]) != names:
            wrong += 1
            if first is None:
                first = (f"{req['method']} {req['path']} as {req['user']}: "
                         f"got {rec['status']} with {len(rec['names'])} "
                         f"objects, reference {status} with {len(names)}")
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "first_wrong": first}


async def trace_middle(work: str, seconds: float, compiles: CompileLog,
                       into: dict) -> None:
    """Trace TRACE_S seconds in the middle of the window; the counters
    are read at both ends of the traced span."""
    import jax

    span = min(TRACE_S, seconds / 2)
    await asyncio.sleep(max(0.0, (seconds - span) / 2) + 0.3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = os.path.join(work, "trace")
    await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                            profiler_options=opts)
    # the trace runs on while it is being stopped: the span that counts is
    # this mark's, and the reduction clips the device plane to it
    with jax.profiler.TraceAnnotation(SPAN_MARK):
        into["before"] = snapshot(compiles)
        await asyncio.sleep(span)
        into["after"] = snapshot(compiles)
    await asyncio.to_thread(jax.profiler.stop_trace)
    into["dir"] = trace_dir


async def run_cell(args, sabotage=None) -> dict:
    """Set-up, window, comparison. -> the result (with ``exit``: the
    process's exit code). ``sabotage(cfg)`` is the tests' way to break
    the timed path underneath."""
    import jax

    from spicedb_kubeapi_proxy_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    cell = load_json("workloads", args.workload + ".json")
    if args.rehearse:
        cell.update(cell.get("rehearse", {}))
    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cache_dir = place_compile_cache()
    # every program goes to the cache, however quick its compile: a later
    # run in this checkout then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileLog()

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']} "
        f"compile cache: {cache_dir}")
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"run.py: platform is {device['platform']!r}, not 'tpu': "
              "no accelerator, no result", file=sys.stderr)
        return {"exit": NO_RESULT}
    if len(devs) < cell["chips"]:
        print(f"run.py: the cell asks {cell['chips']} chip(s), JAX found "
              f"{len(devs)}", file=sys.stderr)
        return {"exit": NO_RESULT}

    from reference import Reference
    from upstream import ReadOnlyKube

    from spicedb_kubeapi_proxy_tpu import native
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    phases = {}

    def phase(name: str, t0: float) -> None:
        phases[name] = time.perf_counter() - t0

    phases["imports_and_device"] = time.time() - T_START
    t0 = time.perf_counter()
    if not native.available():
        raise RuntimeError("native/graphcore.cpp did not build or load")
    phase("native", t0)

    t0 = time.perf_counter()
    dep = Deployment(cell["config"], args.seed, rehearse=args.rehearse)
    cols = dep.columns()
    phase("columns", t0)
    t0 = time.perf_counter()
    kube = ReadOnlyKube(dep.upstream_objects())
    phase("upstream", t0)

    t0 = time.perf_counter()
    cfg = Options(
        rule_content=dep.text("rules.yaml"),
        bootstrap_content=dep.text("bootstrap.yaml"), upstream=kube,
        bind_host="127.0.0.1", bind_port=0,
        workflow_database_path=os.path.join(work, "dtx.sqlite"),
    ).complete()
    phase("complete", t0)
    engine = cfg.engine
    t0 = time.perf_counter()
    engine.bulk_load(cols)
    del cols
    phase("bulk_load", t0)
    t0 = time.perf_counter()
    cg = engine.compiled()
    phase("compile_graph", t0)
    t0 = time.perf_counter()
    d = cg._dev()
    jax.block_until_ready((d["blocks"], d["blocks_bits"]))
    phase("placement", t0)
    blocks = [(int(b.n_dst), int(b.n_src)) for b in cg.blocks]
    say(f"deployment {cell['config']}: {dep.n_relationships()} "
        f"relationships, dense blocks [n_dst x n_src] {blocks}, "
        f"levels={cg.n_levels}")
    if sabotage is not None:
        sabotage(cfg)

    t0 = time.perf_counter()
    ref = Lazy(lambda: Reference(dep))
    plan = make_plan(cell, args.seed, dep, ref)
    n_warm = cell["warm_requests"]
    write_plan(os.path.join(work, "warm.jsonl"), plan[:n_warm])
    write_plan(os.path.join(work, "plan.jsonl"), plan[n_warm:])
    timed_plan = plan[n_warm:]
    phase("plan", t0)

    await cfg.run()
    port = cfg.server.port
    tracing = {}
    try:
        t0 = time.perf_counter()
        warm = await run_client(port, os.path.join(work, "warm.jsonl"),
                                os.path.join(work, "warm_records.jsonl"),
                                cell["clients"], 600.0)
        bad = [r for r in warm["records"] if r["status"] not in (200, 403)]
        if bad:
            raise RuntimeError(f"warm-up: {len(bad)} of {n_warm} requests "
                               f"failed, first {bad[0]}")
        phase("warm", t0)
        say(f"set-up phases (s): {json.dumps(phases)}; xla compiles="
            f"{compiles.compiles} in {compiles.compile_s:.1f}s, "
            f"persistent-cache hits={compiles.cache_hits}")

        # the window starts from a collected heap, not from wherever the
        # load and the warm-up left the collector's counters
        gc.collect()
        before = snapshot(compiles)
        gc_log = GcLog()
        tasks = [asyncio.ensure_future(run_client(
            port, os.path.join(work, "plan.jsonl"),
            os.path.join(work, "records.jsonl"), cell["clients"],
            float(args.seconds)))]
        if args.trace:
            tasks.append(asyncio.ensure_future(trace_middle(
                work, float(args.seconds), compiles, tracing)))
        window = (await asyncio.gather(*tasks))[0]
        after = snapshot(compiles)
        gc_log.stop()
    finally:
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        engine.close_compaction()

    stats = devs[0].memory_stats() or {}
    peak = max(int((dv.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for dv in devs[:cell["chips"]])
    device["memory_peak_bytes"] = peak
    records = window["records"]
    seconds = float(args.seconds)
    if window["plan_exhausted"] and not args.rehearse:
        raise RuntimeError(
            f"the plan's {len(timed_plan)} requests ran out before the "
            "window closed: raise the cell's plan_requests")

    gauges = {"window_s": seconds, "setup_s":
              window["window_start_epoch"] - T_START,
              "client_cpu_s": window["client_cpu_s"],
              "memory_peak_bytes": float(peak)}
    for rec in records:
        rec["kind"] = timed_plan[rec["i"]]["kind"]
    ctx = {"records": records, "gauges": gauges,
           "delta": delta_of(before, after)}
    if args.trace:
        import roofline
        from trace_reduce import reduce_path

        from spicedb_kubeapi_proxy_tpu.obs.trace import tracer

        peaks = None
        if not args.rehearse:  # no table of peaks for a rehearsal's CPU
            peaks = roofline.peaks(device["kind"])
            gauges["hbm_bytes"] = peaks["hbm_bytes"]
        t_lo = window["window_start_epoch"]
        ctx["traces"] = lambda: [t for t in tracer.recent(10 ** 6)
                                 if t_lo <= t["start"] <= t_lo + seconds]
        tr = reduce_path(tracing["dir"])
        tdelta = delta_of(tracing["before"], tracing["after"])
        n_lookup = tdelta("engine_lookup_seconds_count") or 0.0
        n_check = tdelta("engine_check_seconds_count") or 0.0
        rows = max(1.0, (tdelta("engine_lookups_total") or 0.0)
                   / n_lookup) if n_lookup else 1.0
        least = 0.0
        if peaks and blocks:
            least = n_lookup * roofline.dispatch_least_s(blocks, rows, peaks) \
                + n_check * roofline.dispatch_least_s(blocks, 1.0, peaks)
        tr.update(window_s=tr["span_s"], dispatches=n_lookup + n_check,
                  least_s=least)
        ctx["trace"] = tr
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        say(f"traced span: {tr['window_s']:.3f}s, device busy "
            f"{tr['busy_s']:.4f}s on {tr['devices']} device plane(s), "
            f"{tr['dispatches']:.0f} engine dispatches, least "
            f"{least:.4f}s by the roofline count")

    metrics_out = {}
    for m in metric_files("metrics" if args.trace else "end_to_end",
                          args.workload):
        value = by_name("readers", m["reader"]).read(m.get("args", {}), ctx)
        if value is not None:
            metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}

    answered = [r for r in records if r["status"] != 0]
    lat = sorted((r["end"] - r["start"]) * 1e3 for r in answered)
    statuses = collections.Counter(r["status"] for r in records)
    say(f"window: {len(records)} requests sent in {seconds:.0f}s by "
        f"{cell['clients']} clients, {len(answered)} answered, closed "
        f"{window['closed_after_s'] - seconds:.3f}s after the window; "
        f"latency ms p50={percentile(lat, 50):.3f} "
        f"p95={percentile(lat, 95):.3f} max={lat[-1]:.3f} (n={len(lat)}); "
        f"statuses {dict(sorted(statuses.items()))}; "
        f"compiles in window={ctx['delta']('bench_backend_compiles'):.0f}; "
        f"device bytes in use={stats.get('bytes_in_use')} peak={peak}")

    slowest = sorted(answered, key=lambda r: r["start"] - r["end"])[:12]
    say("slowest requests (sent at s: ms): " + ", ".join(
        f"{r['start']:.1f}: {(r['end'] - r['start']) * 1e3:.0f}"
        for r in sorted(slowest, key=lambda r: r["start"]))
        + f"; {gc_log.line()}")

    # the comparison: after the window, the peak read, the server stopped
    t0 = time.perf_counter()
    verdict = compare(records, timed_plan, dep, ref)
    checks = {
        "wrong_answers": {"value": verdict["wrong_answers"], "limit": 0},
        "unanswered": {"value": verdict["unanswered"], "limit": 0},
        "nothing_compared": {"value": int(not answered), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    say(f"comparison with the reference: {time.perf_counter() - t0:.1f}s "
        f"for {len(answered)} answers")
    if verdict["first_wrong"]:
        print(f"first wrong answer: {verdict['first_wrong']}",
              file=sys.stderr)
    if args.control:
        stale = Reference(dep, dep.config["control"]["stale_share"])
        control = 0
        for rec in answered:
            req = timed_plan[rec["i"]]
            expect = by_name("ops", req["kind"]).expect
            control += expect(req, dep, stale) != expect(req, dep, ref)
        print(f"control (reference at a stale revision, share "
              f"{dep.config['control']['stale_share']}): wrong_answers="
              f"{control} of {len(answered)} limit 0 -> "
              f"{'not correct' if control > 0 else 'CORRECT: it must not be'}",
              file=sys.stderr)

    failed = sum(1 for r in records if r["status"] not in (200, 403))
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics_out, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    result["exit"] = 0
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    result = asyncio.run(run_cell(args))
    code = result.pop("exit")
    if code != 0:
        return code
    if args.rehearse:
        print("run.py: the rehearsal walked every phase "
              f"(correct={result['correct']}, metrics="
              f"{sorted(result['metrics'])}), but it is no run on the "
              "chip: no result", file=sys.stderr)
        return NO_RESULT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as ex:
        code = ex.code if isinstance(ex.code, int) else 2
    except BaseException:  # noqa: BLE001 - any failure is a failed run
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # leave at once: serving threads of a failed run must not hold the chip
    os._exit(code)

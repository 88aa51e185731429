"""The program's own stages beside the device plane, on the profiler's
one clock: the run's ``.xplane.pb`` read again for what
``trace_reduce.py`` does not keep. The program writes each stage of the
served path as a host annotation ``sdbkp:<stage>`` (``obs/trace.py``)
and its fixpoint runs as the XLA module ``jit_sdbkp_fixpoint``; all is
clipped to the harness's ``bench:traced_span``. args ``what``:

``device_queue_ms`` — per dispatch, the start of its module event on the
``XLA Modules`` line minus the end of its ``sdbkp:engine_enqueue``
annotation, mean: the queue on the device. The device runs programs in
the order they were enqueued, so the k-th enqueue of the trace pairs
with the (k+d)-th module, d being the dispatches that were in the queue
already when the trace began (their enqueues are not in it): the largest
shift under which no ``sdbkp:device_wait`` ends before the module it
waited for has run, and at least the smallest under which no module
starts before its enqueue began (no such shift: no reading). A module
that started inside its enqueue's call waited in no queue: 0, and the
count of those goes to stderr. args ``enqueue``, ``wait``, ``module``
name the three.

``idle_unexplained_pct`` — of the device's idle time in the span, the
share during which no ``sdbkp:`` leaf stage was open on the host (args
``parents``: the stages that only bracket others). The table of idle
seconds by stage goes to stderr once: an idle instant is split evenly
among the working stages open then, and among the waiting ones (args
``waiting``) only where no working stage is open.

Nothing is returned without a traced run, without a trace file, or
where the program writes no such annotation (a parent commit).
"""

import glob
import os
import sys

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MODULES_LINE = "XLA Modules"
STAGE_PREFIX = "sdbkp:"
SLACK_NS = 50_000  # two clocks' disagreement and a thread's wake-up


def newest_xplane() -> str:
    """The trace this run wrote: the harness hands readers no path, and
    ``.bench_work/*/trace`` may hold another cell's older one."""
    hits = []
    for d in glob.glob(os.path.join(ROOT, ".bench_work", "*", "trace")):
        try:
            hits.append(trace_reduce.find_xplane(d))
        except FileNotFoundError:
            pass
    return max(hits, key=os.path.getmtime) if hits else ""


def load(profile) -> dict:
    """-> {"span": (lo, hi) ns, "stages": {name: [(start, end, line)]}
    without the prefix, "modules": [(start, end, name)], "busy": merged
    [(start, end)] of device operations}, all clipped to the span where
    the harness's mark is there."""
    stages, modules, ops, marks = {}, [], [], []
    for plane in profile.planes:
        if plane.name.startswith(trace_reduce.HOST_PLANE):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns, i)
                    if e.name.startswith(STAGE_PREFIX):
                        stages.setdefault(
                            e.name[len(STAGE_PREFIX):], []).append(ev)
                    elif e.name == trace_reduce.SPAN_MARK:
                        marks.append(ev)
        elif plane.name.startswith(trace_reduce.DEVICE_PLANE) \
                and not modules:  # one chip's plane: the first that ran
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                if line.name == MODULES_LINE:
                    modules = sorted(evs)
                elif line.name == trace_reduce.OPS_LINE:
                    ops = evs
    if marks:
        lo, hi = marks[0][0], marks[0][1]
    elif ops:
        lo, hi = min(e[0] for e in ops), max(e[1] for e in ops)
    else:
        lo = hi = 0
    busy, _ = trace_reduce.own_time(trace_reduce._clip(ops, lo, hi))
    for evs in stages.values():
        evs.sort()
    return {"span": (lo, hi), "stages": stages, "modules": modules,
            "busy": [tuple(b) for b in busy]}


def device_queue_ms(tl: dict, args: dict):
    module = args.get("module", "jit_sdbkp_fixpoint")
    enq = tl["stages"].get(args.get("enqueue", "engine_enqueue"), [])
    waits = tl["stages"].get(args.get("wait", "device_wait"), [])
    mods = [m for m in tl["modules"] if m[2].startswith(module)]
    if not enq or not mods:
        return None
    # a dispatch's wait: the next one opened on its enqueue's own thread
    wait_end = []
    for s, e, line in enq:
        after = [w for w in waits if w[2] == line and w[0] >= e]
        wait_end.append(min(after)[1] if after else None)

    def holds(d, bound):
        return all(bound(k, mods[k + d]) for k in range(len(enq))
                   if k + d < len(mods))

    shifts = range(len(mods))
    least = next((d for d in shifts if holds(
        d, lambda k, m: m[0] >= enq[k][0] - SLACK_NS)), None)
    if least is None:
        return None
    most = least
    if any(w is not None for w in wait_end):
        ok = [d for d in shifts if d >= least and holds(
            d, lambda k, m: wait_end[k] is None
            or m[1] <= wait_end[k] + SLACK_NS)]
        most = max(ok, default=least)
    lo, hi = tl["span"]
    waited = [mods[k + most][0] - e for k, (s, e, _)
              in enumerate(enq) if lo <= e <= hi and k + most < len(mods)]
    if not waited:
        return None
    # before its enqueue BEGAN no module starts (``least``); one that
    # started before the call returned (the thread was waiting for the
    # interpreter lock) met no queue, and is said aloud, not hidden
    inside = sum(w < -SLACK_NS for w in waited)
    if inside:
        print(f"device_queue_ms: {inside} of {len(waited)} modules started "
              "before their enqueue's call returned: counted as no queue",
              file=sys.stderr)
    return sum(max(0, w) for w in waited) / len(waited) / 1e6


def idle_by_stage(tl: dict, args: dict) -> dict:
    """-> {stage: idle ns charged to it, "(no stage)": the rest}."""
    lo, hi = tl["span"]
    parents = set(args.get("parents", ()))
    waiting = set(args.get("waiting", ()))
    edges = []  # (time, +1 | -1, key): key None is the device's idleness
    t = lo
    for a, b in tl["busy"] + [(hi, hi)]:
        if a > t:
            edges += [(t, 1, None), (a, -1, None)]
        t = max(t, b)
    for name, evs in tl["stages"].items():
        if name in parents:
            continue
        for s, e, _ in trace_reduce._clip(evs, lo, hi):
            edges += [(s, 1, name), (e, -1, name)]
    edges.sort(key=lambda x: (x[0], x[1]))
    open_now, out = {}, {}
    idle, t = 0, lo
    for at, step, key in edges:
        if idle and at > t:
            live = {n: c for n, c in open_now.items() if c > 0}
            work = {n: c for n, c in live.items() if n not in waiting}
            share = work or live or {"(no stage)": 1}
            total = sum(share.values())
            for n, c in share.items():
                out[n] = out.get(n, 0.0) + (at - t) * c / total
        t = at
        if key is None:
            idle += step
        else:
            open_now[key] = open_now.get(key, 0) + step
    return out


def idle_unexplained_pct(tl: dict, args: dict):
    if not tl["stages"] or not tl["busy"]:
        return None
    table = idle_by_stage(tl, args)
    total = sum(table.values())
    if total <= 0:
        return None
    print("device idle seconds by program stage (traced span "
          f"{(tl['span'][1] - tl['span'][0]) / 1e9:.3f}s, idle "
          f"{total / 1e9:.3f}s): " + ", ".join(
              f"{n} {ns / 1e9:.4f}" for n, ns in
              sorted(table.items(), key=lambda kv: -kv[1])),
          file=sys.stderr)
    return table.get("(no stage)", 0.0) / total * 100.0


READINGS = {"device_queue_ms": device_queue_ms,
            "idle_unexplained_pct": idle_unexplained_pct}


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    if "program_timeline" not in ctx:  # read the file once for both
        from jax.profiler import ProfileData

        path = newest_xplane()
        ctx["program_timeline"] = load(
            ProfileData.from_file(path)) if path else None
    tl = ctx["program_timeline"]
    if tl is None:
        return None
    return READINGS[args["what"]](tl, args)

"""From a profiler trace (``.xplane.pb``) to device busy time, the idle
gaps and a table of device operations.

Busy is the union of the intervals in which an operation ran on a device
(the ``XLA Ops`` line of each ``/device:TPU:N`` plane). Nested events
(a ``while`` and the ops of its body) are charged to the innermost one,
so the operation table sums to the busy time. A gap is labelled by the
benchmark's own host annotation (``bench:*``) that covers half of it or
more, else ``host``. Names are the ones the trace gives (``fusion.N``,
``_unknown_.N``): telling kernels apart needs names inside the program.
"""

import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OWN_PREFIX = "bench:"
SPAN_MARK = "bench:traced_span"  # the harness's bracket of the traced span


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def own_time(events: list) -> tuple:
    """``events``: [(start, end, name)] on one line. -> (busy intervals
    merged, {name: seconds owned}) with every instant charged to the
    innermost running event, in the events' own time unit."""
    events = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    owned = {}
    merged = []
    stack = []
    t = 0.0

    def give(ev, upto):
        nonlocal t
        if upto > t:
            owned[ev[2]] = owned.get(ev[2], 0.0) + (upto - t)
            t = upto

    for ev in events:
        start = ev[0]
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            give(top, top[1])
        if stack:
            give(stack[-1], start)
        else:
            t = max(t, start)
        stack.append(ev)
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], ev[1])
        else:
            merged.append([start, ev[1]])
    while stack:
        top = stack.pop()
        give(top, top[1])
    return merged, owned


def _clip(events: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce_profile(profile, top: int = 10) -> dict:
    """-> {"devices", "busy_s" (mean over device planes that ran an
    operation), "span_s", "device_ops": [[name, s]], "idle_gaps":
    [[label, s]]}. Where the host plane holds the harness's
    ``bench:traced_span`` mark, everything is clipped to it and ``span_s``
    is its length (a trace runs on while it is being stopped); else the
    span is the first start to the last end of any device operation."""
    host_marks = []
    planes = []
    for plane in profile.planes:
        if plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                host_marks += [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events
                               if e.name.startswith(OWN_PREFIX)]
        elif plane.name.startswith(DEVICE_PLANE):
            evs = [(e.start_ns, e.start_ns + e.duration_ns,
                    short_name(e.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                planes.append(evs)
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "span_s": 0.0,
                "device_ops": [], "idle_gaps": []}
    host_marks.sort()
    busy, ops, gaps = [], {}, []
    bracket = [m for m in host_marks if m[2] == SPAN_MARK]
    host_marks = [m for m in host_marks if m[2] != SPAN_MARK]
    if bracket:
        lo, hi = bracket[0][0], bracket[0][1]
        planes = [_clip(evs, lo, hi) for evs in planes]
    else:
        lo = min(e[0] for evs in planes for e in evs)
        hi = max(e[1] for evs in planes for e in evs)
    for evs in planes:
        merged, owned = own_time(evs)
        if bracket and merged:  # the bracket's own ends are idle too
            gaps += [(merged[0][0] - lo, lo, merged[0][0]),
                     (hi - merged[-1][1], merged[-1][1], hi)]
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, ns in owned.items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / len(planes)
        gaps += [(b0 - a1, a1, b0) for (_, a1), (b0, _)
                 in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    labelled = []
    for dur, a, b in gaps[:top]:
        covered = {}
        for s, e, name in host_marks:
            if s < b and e > a:
                covered[name] = covered.get(name, 0.0) + min(e, b) - max(s, a)
        label = max(covered, key=covered.get, default="host")
        if covered.get(label, 0.0) < dur / 2:
            label = "host"  # ours covers under half of it: the program's
        labelled.append([label, dur / 1e9])
    table = sorted(ops.items(), key=lambda kv: -kv[1])
    return {"devices": len(planes),
            "busy_s": sum(busy) / len(busy),
            "span_s": (hi - lo) / 1e9,
            "device_ops": [[n, s] for n, s in table[:top]],
            "idle_gaps": labelled}


def reduce_path(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(path)), top)

"""The share of the device's busy time spent in operations under one
named scope of the program (``jax.named_scope``), in %: the run's
``.xplane.pb`` read for what ``jax.profiler.ProfileData`` does not show.
The chip's trace gives every operation of the ``XLA Ops`` line a
``tf_op`` stat on its metadata, the scope path it was traced under
(``jit(sdbkp_fixpoint)/core/while/body/residual/gather:``); args
``scope`` names one segment of that path. Every instant of the traced
span (``bench:traced_span``) is charged to the innermost operation
running then, as ``trace_reduce.py`` does, so a ``while`` owns only what
its body's operations leave. The file is read as protobuf wire format
(XSpace 1: planes; XPlane 2: name, 3: lines, 4: event metadata, 5: stat
metadata; XLine 2: name, 3: timestamp_ns, 4: events; XEvent 1: metadata
id, 2: offset_ps, 3: duration_ps; XEventMetadata 1: id, 2: name, 5:
stats; XStat 1: metadata id, 5: string, 7: reference to a stat
metadata's name): the only generated classes for it here are
tensorflow's, too heavy to import beside a serving process.

Nothing is returned without a traced run, a trace file, device
operations in the span, or any operation that carries a scope path (a
CPU rehearsal; a trace without such metadata)."""

import os

import trace_reduce
from deployment import load_module

HERE = os.path.dirname(os.path.abspath(__file__))


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an integer for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _message(buf) -> dict:
    """Field number -> values, for a message read whole."""
    out = {}
    for num, value in fields(buf):
        out.setdefault(num, []).append(value)
    return out


def _text(values) -> str:
    return bytes(values[0]).decode("utf-8", "replace") if values else ""


def _map_entries(entries) -> dict:
    """A ``map<int64, Message>`` field -> {key: parsed message}."""
    out = {}
    for entry in entries:
        m = _message(entry)
        out[m[1][0]] = _message(m[2][0])
    return out


def load(path: str) -> dict:
    """-> {"span": (lo, hi) ns or None, "ops": [(start, end, metadata
    id)] of the first device plane that ran an operation, "scopes":
    {metadata id: scope path}}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    span, ops, scopes = None, [], {}
    for num, plane in fields(space):
        if num != 1:
            continue
        p = _message(plane)
        name = _text(p.get(2))
        device = name.startswith(trace_reduce.DEVICE_PLANE) and not ops
        if not device and not name.startswith(trace_reduce.HOST_PLANE):
            continue
        meta = _map_entries(p.get(4, ()))
        if device:
            stat_names = {k: _text(m.get(2)) for k, m
                          in _map_entries(p.get(5, ())).items()}
        for line in p.get(3, ()):
            ln = _message(line)
            if device and _text(ln.get(2)) != trace_reduce.OPS_LINE:
                continue
            t0 = ln.get(3, [0])[0]
            for ev in ln.get(4, ()):
                e = _message(ev)
                mid = e[1][0]
                start = t0 + e.get(2, [0])[0] / 1e3
                end = start + e.get(3, [0])[0] / 1e3
                if device:
                    ops.append((start, end, mid))
                elif span is None and _text(
                        meta.get(mid, {}).get(2)) == trace_reduce.SPAN_MARK:
                    span = (start, end)
        if device and ops:
            for mid, m in meta.items():
                for stat in m.get(5, ()):
                    s = _message(stat)
                    if stat_names.get(s[1][0]) == "tf_op":
                        scopes[mid] = _text(s.get(5)) or stat_names.get(
                            s.get(7, [None])[0], "")
    return {"span": span, "ops": ops, "scopes": scopes}


def share_pct(tl: dict, scope: str):
    ops = tl["ops"]
    if tl["span"] is not None:
        ops = trace_reduce._clip(ops, *tl["span"])
    if not ops or not any(tl["scopes"].values()):
        return None
    _, owned = trace_reduce.own_time(ops)
    inside = sum(ns for mid, ns in owned.items()
                 if scope in tl["scopes"].get(mid, "").split("/"))
    return inside / sum(owned.values()) * 100.0


def read(args: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    if "scope_device_share" not in ctx:  # read the file once
        path = load_module(os.path.join(HERE, "program_timeline.py"),
                           "bench_readers_program_timeline").newest_xplane()
        ctx["scope_device_share"] = load(path) if path else None
    tl = ctx["scope_device_share"]
    return None if tl is None else share_pct(tl, args["scope"])

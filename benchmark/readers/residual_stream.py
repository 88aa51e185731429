"""The sparse hop's share of the HBM peak: the least seconds of the
traced span's dispatches by ``roofline_residual.py`` over the device's
busy seconds, in %. The compiled graph's size is read from the program's
gauges in this process (``engine_residual_edges``,
``engine_graph_slots``: a gauge that stands still has no movement to
read), the subject rows per dispatch from ``engine_dispatch_rows_total``
over the window's dispatches. Nothing is returned without a trace, on a
device with no table of peaks (a rehearsal), or where the program has
no such gauge or counter (a parent commit) or no residual edge."""

import roofline
import roofline_residual


def program_gauge(name: str):
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    for line in metrics.render().splitlines():
        if line.startswith(name + " "):
            return float(line.rpartition(" ")[2])
    return None


def read(args: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0 or not tr["dispatches"] \
            or "hbm_bytes" not in ctx["gauges"]:
        return None
    edges = program_gauge("engine_residual_edges")
    slots = program_gauge("engine_graph_slots")
    rows = ctx["delta"]("engine_dispatch_rows_total")
    calls = sum(ctx["delta"](s + "_count") or 0.0
                for s in ("engine_lookup_seconds", "engine_check_seconds"))
    if not edges or not slots or not rows or not calls:
        return None
    import jax

    peak = roofline.peaks(jax.devices()[0].device_kind)
    least = tr["dispatches"] * roofline_residual.dispatch_least_s(
        edges, slots, rows / calls, peak)
    return least / tr["busy_s"] * 100.0

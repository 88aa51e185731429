"""Numbers of the traced span: the device plane reduced by
``trace_reduce.py`` beside the program's dispatch counts over the same
span. args ``what``: ``idle_pct`` (1 - busy / traced seconds),
``ms_per_dispatch`` (busy / engine dispatches) or ``hop_roofline`` (least
seconds of the span's dispatches by ``roofline.py`` / busy). Nothing is
returned without a trace, without device time, or, for the roofline,
where the compiled graph has no dense block to count."""


def read(args: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0 or tr["window_s"] <= 0:
        return None
    what = args["what"]
    if what == "idle_pct":
        return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
    if not tr["dispatches"]:
        return None
    if what == "ms_per_dispatch":
        return tr["busy_s"] / tr["dispatches"] * 1e3
    if what == "hop_roofline":
        return tr["least_s"] / tr["busy_s"] * 100.0 if tr["least_s"] > 0 \
            else None
    raise ValueError(f"device_trace: unknown reading {what!r}")

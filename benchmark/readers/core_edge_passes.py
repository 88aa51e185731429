"""Edge passes of one dispatch under the stratified schedule, in
millions: the iterated core is walked once per trip of the fixpoint's
loop, the acyclic levels after it once. args ``core`` and ``whole`` name
two gauges of the program (``engine_core_edges``: padded residual edges of
level 0 plus the cells of level-0 dense blocks; ``engine_residual_edges``:
every residual edge, unpadded) and ``trips`` its histogram of iterations
per dispatch (``engine_fixpoint_iterations``, mean over the window):

    core x trips + (whole - core)

The last term sets an unpadded count against a padded one, so it errs low
by the core's padding; ``roofline_residual.py`` counts ``whole`` once, and
the ratio of the two is what iteration costs. Nothing is returned where
the program has no such gauge (a parent commit) or no dispatch was
observed."""

import os

from deployment import load_module

HERE = os.path.dirname(os.path.abspath(__file__))


def read(args: dict, ctx: dict):
    gauge = load_module(os.path.join(HERE, "residual_stream.py"),
                        "bench_readers_residual_stream").program_gauge
    core, whole = gauge(args["core"]), gauge(args["whole"])
    n = ctx["delta"](args["trips"] + "_count")
    total = ctx["delta"](args["trips"] + "_sum")
    if core is None or whole is None or not n or total is None:
        return None
    return (core * total / n + max(0.0, whole - core)) / 1e6

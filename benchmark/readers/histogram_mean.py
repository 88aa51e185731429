"""Mean of a program histogram over the window: sum / count of the
observations that landed between the two snapshots. args: ``histogram``,
``scale`` (1000 for seconds -> ms)."""


def read(args: dict, ctx: dict):
    n = ctx["delta"](args["histogram"] + "_count")
    total = ctx["delta"](args["histogram"] + "_sum")
    if not n or total is None:
        return None
    return total / n * args.get("scale", 1.0)

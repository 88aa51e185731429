"""A linear combination over a count: (sum(plus) - sum(minus)) / per *
scale. Each name is one of the harness's gauges (``window_s``,
``client_cpu_s``, ``memory_peak_bytes``, ``hbm_bytes``) or else a program
series, read as its movement over the window. Nothing is returned where
``per`` or every ``plus`` term is missing."""


def read(args: dict, ctx: dict):
    def value(name):
        if name in ctx["gauges"]:
            return ctx["gauges"][name]
        return ctx["delta"](name)

    plus = [value(n) for n in args["plus"]]
    per = value(args["per"])
    if not per or all(v is None for v in plus):
        return None
    minus = [value(n) or 0.0 for n in args.get("minus", ())]
    return (sum(v or 0.0 for v in plus) - sum(minus)) / per \
        * args.get("scale", 1.0)

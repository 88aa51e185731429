"""Ratio of counter movements over the window (series summed over their
labels): sum(numerator) / sum(denominator) * scale; with no denominator,
the numerator's movement itself. Nothing is returned where no named
series exists or the denominator did not move."""


def read(args: dict, ctx: dict):
    num = [ctx["delta"](s) for s in args["numerator"]]
    den = [ctx["delta"](s) for s in args.get("denominator", ())]
    if all(v is None for v in num + den):
        return None
    top = sum(v or 0.0 for v in num)
    if "denominator" not in args:
        return top * args.get("scale", 1.0)
    bottom = sum(v or 0.0 for v in den)
    if bottom <= 0:
        return None
    return top / bottom * args.get("scale", 1.0)

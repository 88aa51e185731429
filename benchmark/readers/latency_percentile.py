"""A percentile (nearest rank) of the client-side latency, send to last
byte, over ALL requests sent in the window, of one operation kind or of
every kind: those answered after it closed count with their wait, one that
never got an answer with the generator's 30 s limit. args: ``q``, and
``kind`` to keep to one."""

import math


def read(args: dict, ctx: dict):
    lat = sorted((r["end"] - r["start"]) * 1e3 for r in ctx["records"]
                 if args.get("kind") in (None, r["kind"]))
    if not lat:
        return None
    return lat[max(0, math.ceil(args["q"] / 100.0 * len(lat)) - 1)]

"""Requests answered inside the window over the window's seconds: all
the work over all the time, not a mean of chunks. A request still in
flight when the window closes is not counted."""


def read(args: dict, ctx: dict):
    window = ctx["gauges"]["window_s"]
    done = sum(1 for r in ctx["records"]
               if r["status"] != 0 and r["end"] <= window)
    return done / window if done else None

"""Mean duration of one of the program's spans (``obs/trace.py``) over the
traces that its ring kept of the window (tail-sampled by the program's
own default settings, which the harness leaves alone). args: ``span``,
``scale`` (1000 for seconds -> ms)."""


def read(args: dict, ctx: dict):
    durations = [s["duration_us"] for t in ctx["traces"]()
                 for s in t.get("spans", ()) if s.get("name") == args["span"]]
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e6 * args.get("scale", 1.0)

"""One of the harness's own readings as it stands. args: ``gauge``
(``setup_s``, ``window_s``, ``client_cpu_s``, ``memory_peak_bytes``,
``hbm_bytes``)."""


def read(args: dict, ctx: dict):
    return ctx["gauges"].get(args["gauge"])

"""Process-level profiling hooks: what neither the request path nor the
engine can time itself, mirrored into the metrics registry.

XLA compilation happens inside jax, invisibly to the dispatch path, yet
a recompile is the single largest latency cliff the engine has (tens of
seconds at the 10M-relationship scale). jax's monitoring module
broadcasts it: every backend compile lands in
``jax_backend_compiles_total`` / ``jax_compile_seconds``, every program
read back from the persistent cache in ``jax_compile_cache_hits_total``,
so a scrape can attribute a p99 spike to compilation instead of
guessing, and tell a cold checkout from a warm one.

The interpreter's garbage collector stops every thread of the serving
process while it runs: ``install_gc_hook`` times each collection as
stage ``gc`` (``process_gc_seconds{generation=...}`` and the profiler
annotation ``sdbkp:gc``, obs/trace.py).

The other profiling hooks live where the numbers are produced:
CSR nnz / slot-space gauges at graph compile (engine/engine.py
``compiled()``), dispatch batch-size and frontier-occupancy histograms on
the query paths, queue-wait on the admission controller, replication ack
wait on the mirrored engine.
"""

from __future__ import annotations

import gc
import threading

from ..utils.metrics import metrics
from .trace import Stage

_install_lock = threading.Lock()
_installed = False
_gc_installed = False


def _on_event_duration(event: str, duration: float, **kw) -> None:
    # jax event names are path-ish; tracing and lowering durations come
    # by the same channel and are not compiles
    if not event.endswith("backend_compile_duration"):
        return
    metrics.counter("jax_backend_compiles_total").inc()
    metrics.histogram(
        "jax_compile_seconds",
        buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                 60.0, 120.0)).observe(float(duration))


def _on_event(event: str, **kw) -> None:
    if event.endswith("compilation_cache/cache_hits"):
        metrics.counter("jax_compile_cache_hits_total").inc()


def install_jax_compile_hook() -> bool:
    """Register the compile-event listeners once per process; True when
    they are (now or already) installed. Safe without jax or against a
    jax whose monitoring surface moved — profiling is best-effort, the
    engine must not fail to boot over it."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            monitoring.register_event_listener(_on_event)
        except Exception:  # noqa: BLE001 - any jax/API-drift failure
            return False
        _installed = True
        return True


def install_gc_hook() -> None:
    """Time every garbage collection of this process as stage ``gc``,
    once per process (the serving process: proxy/server.py ``start``).
    The interpreter calls back on the collecting thread, start and stop
    in turn, so one slot holds the open stage. A collection can begin
    at any allocation, also one made under the registry's lock (a
    scrape being rendered): the callback therefore takes no lock the
    interrupted frame may hold — its three histograms are looked up
    here, once, and a histogram's own lock is re-entrant."""
    global _gc_installed
    with _install_lock:
        if _gc_installed:
            return
        _gc_installed = True
    by_generation = [metrics.histogram("process_gc_seconds", generation=g)
                     for g in range(3)]
    open_stage = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            # histogram and annotation only: a span would hang the
            # pause on whichever request the collecting thread served
            open_stage.append(
                Stage(None, "gc", by_generation[info["generation"]]))
        elif open_stage:
            open_stage.pop().finish()

    gc.callbacks.append(on_gc)

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

The interpreter's garbage collector runs under the interpreter lock,
which it gives up only where it frees an object that does (a device
array): ``install_gc_hook`` times each collection as stage ``gc``
(``process_gc_seconds{generation=...}`` and the profiler annotation
``sdbkp:gc``, obs/trace.py), on the collecting thread's wall clock, its
waits to get the lock back included. ``settle_collector`` keeps it off
the heap that start-up built, makes it come rarely, and gives it a
thread of its own, so that no request's thread sits in a collection.

The other profiling hooks live where the numbers are produced:
CSR nnz / slot-space gauges at graph compile (engine/engine.py
``compiled()``), dispatch batch-size and frontier-occupancy histograms on
the query paths, queue-wait on the admission controller, replication ack
wait on the mirrored engine.
"""

from __future__ import annotations

import gc
import threading
import time

from ..utils.metrics import metrics
from .trace import Stage

_install_lock = threading.Lock()
_installed = False
_gc_installed = False
_collector_settled = False

# The collector's thread takes the young generation once it holds this
# many net container allocations (the interpreter's own threshold is
# 700, which under load means dozens of collections a second, each over
# the requests in flight), every tenth time the middle one with it,
# every hundredth everything that is not frozen. What bounds it from
# above is what a request's garbage holds until then: device arrays, so
# peak device memory, and the length of that one collection. Constants
# from one sweep on the chip (PERF.md section 6, PR 34), not options.
GC_YOUNG_AFTER = 10_000
GC_POLL_S = 0.05
# The interpreter's own thresholds while that thread collects: a
# backstop, reached only if the thread falls ten collections behind.
GC_BACKSTOP = (10 * GC_YOUNG_AFTER, 10, 10)


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


def settle_collector() -> None:
    """Where the process begins to serve (proxy/server.py ``start``),
    once per process: collect, freeze what is left — the store, the
    compiled graph, the rules, jax and every imported module — into the
    permanent generation, which no later collection walks, and hand the
    collecting to a thread of its own (``_collect``). An embedding
    application's live objects are frozen with the proxy's; the
    collection before the freeze is what finds the garbage among them.
    Whatever is made later (the programs the first requests compile, a
    graph recompiled while serving) stays ordinary, and a second
    ``start`` in the process does nothing: what is alive then are
    requests in flight."""
    global _collector_settled
    with _install_lock:
        if _collector_settled:
            return
        _collector_settled = True
    gc.collect()
    gc.freeze()
    metrics.gauge("process_gc_frozen_objects").set(gc.get_freeze_count())
    gc.set_threshold(*GC_BACKSTOP)
    threading.Thread(target=_collect, daemon=True,
                     name="sdbkp-collector").start()


def _collect() -> None:
    """The collector's thread. A collection lasts several times its own
    CPU time under load: each device array it frees gives the
    interpreter lock up, and the collecting thread queues for it again
    behind every busy worker. On the thread whose allocation happened
    to cross the threshold that is a request (or, on the event loop,
    every request) standing still for up to seconds; here it is nobody.
    It collects for as long as the thresholds are the ones it was
    started under: whoever sets others takes the collector back."""
    young = 0
    while gc.get_threshold() == GC_BACKSTOP:
        time.sleep(GC_POLL_S)
        if gc.get_count()[0] < GC_YOUNG_AFTER:
            continue
        young += 1
        gc.collect(2 if young % 100 == 0 else 1 if young % 10 == 0 else 0)

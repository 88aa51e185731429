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
waits to get the lock back included, with the thread's own CPU seconds
beside it (``process_gc_cpu_seconds_total``). ``settle_collector`` keeps
it off the heap that start-up built, makes it come rarely, and gives it
a thread of its own, so that no request's thread sits in a collection.

The interpreter lock itself is read three ways, none on a request's
path. Who holds it: the CPU counters of the synchronous stages
(obs/trace.py) and :class:`CpuLedger`, CPU seconds by thread role from
the kernel's own clocks, read when the registry is rendered. Who waits
for it: wall minus CPU of those stages. How long a thread that wants it
waits: the collector's thread sleeps 20 times a second anyway, and how
late each sleep returns is ``process_lock_wait_seconds``.

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
import weakref

from ..utils.metrics import Registry, metrics
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
    interrupted frame may hold — its three histograms and its counter
    are looked up here, once; a histogram's own lock is re-entrant and
    nothing else touches the counter. Both callbacks run on the
    collecting thread, so the stage reads that thread's CPU clock too:
    what a collection computes, beside how long it lasted."""
    global _gc_installed
    with _install_lock:
        if _gc_installed:
            return
        _gc_installed = True
    by_generation = [metrics.histogram("process_gc_seconds", generation=g)
                     for g in range(3)]
    cpu = metrics.counter("process_gc_cpu_seconds_total")
    open_stage = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            # histogram and annotation only: a span would hang the
            # pause on whichever request the collecting thread served
            open_stage.append(
                Stage(None, "gc", by_generation[info["generation"]], cpu))
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
        _sleep_a_poll()
        if gc.get_count()[0] < GC_YOUNG_AFTER:
            continue
        young += 1
        gc.collect(2 if young % 100 == 0 else 1 if young % 10 == 0 else 0)


def _sleep_a_poll() -> None:
    """The collector's sleep, and the one probe of the interpreter lock:
    ``time.sleep`` gives the lock up and has to take it again before its
    next line runs, so how late the sleep returns is what a thread that
    becomes runnable waits for the lock (plus the kernel's wake-up, tens
    of microseconds). With one thread computing it reads the
    interpreter's switch interval, 5 ms: the holder has to be asked.
    Observed directly, not as a stage: it is no work of the served path,
    and an ``sdbkp:`` annotation would enter the device's idle table as
    one."""
    t0 = time.perf_counter()
    time.sleep(GC_POLL_S)
    late = time.perf_counter() - t0 - GC_POLL_S
    metrics.histogram(
        "process_lock_wait_seconds",
        buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)).observe(
        max(0.0, late))


class _RoleClock:
    """CPU seconds of the threads of one role, summed. A thread's clock
    is asked by its kernel id, ``(~tid << 3) | 6`` being what
    ``pthread_getcpuclockid`` computes from a ``pthread_t``: the kernel
    refuses an id that has gone (EINVAL) where a stale ``pthread_t`` is
    undefined behaviour. A thread that has ended keeps its last reading
    in the sum."""

    def __init__(self):
        self._last: dict = {}  # live thread -> its last reading
        self._ended = 0.0

    def total(self, threads=()) -> float:
        """The role's CPU seconds now; ``threads`` join the role (and
        stay in it for as long as they live)."""
        for t in threads:
            self._last.setdefault(t, 0.0)
        for t in list(self._last):
            if not t.is_alive():
                self._ended += self._last.pop(t)
                continue
            try:
                self._last[t] = time.clock_gettime((~t.native_id << 3) | 6)
            except OSError:  # it ended between the two lines
                pass
        return self._ended + sum(self._last.values())


class CpuLedger:
    """CPU seconds by thread role, from clocks the kernel keeps anyway:
    the whole process (``process_cpu_seconds_total``), the event loop's
    thread (``process_loop_cpu_seconds_total``: whichever ran
    ``Server.start``) and the threads of that loop's default executor,
    the pool ``tracer.to_thread`` hands work to
    (``process_worker_cpu_seconds_total``). The rest — the collector's
    thread, the batcher's compile thread, the device runtime's own
    threads, a profiler session — is the remainder. Read only when the
    registry is rendered (a scrape, the benchmark's snapshots)."""

    def __init__(self, registry: Registry):
        self._registry = registry
        self._lock = threading.Lock()
        self._loops = weakref.WeakSet()
        self._loop_clock = _RoleClock()
        self._worker_clock = _RoleClock()

    def serve_from(self, loop) -> None:
        """The calling thread runs ``loop``, and ``loop`` serves."""
        with self._lock:
            self._loops.add(loop)
            self._loop_clock.total([threading.current_thread()])
        self._registry.add_refresher(self.refresh)

    def refresh(self) -> None:
        with self._lock:
            pools = [getattr(loop, "_default_executor", None)
                     for loop in self._loops]
            workers = [t for pool in pools
                       for t in tuple(getattr(pool, "_threads", ()))]
            loop_s = self._loop_clock.total()
            worker_s = self._worker_clock.total(workers)
            # last, so that the parts never exceed the whole
            process_s = time.process_time()
        reg = self._registry
        reg.counter("process_loop_cpu_seconds_total").advance_to(loop_s)
        reg.counter("process_worker_cpu_seconds_total").advance_to(worker_s)
        reg.counter("process_cpu_seconds_total").advance_to(process_s)


cpu_ledger = CpuLedger(metrics)

"""Request tracing: W3C traceparent context + cheap in-process spans.

One trace follows one proxy request end-to-end. The proxy ingress adopts
an incoming ``traceparent`` header (or mints one), the authz middleware
opens named child spans for every stage it runs (authn, rule match,
admission wait, cache probe, engine dispatch, post-filter, upstream RTT),
and the remote-engine wire carries the context as a frame field so
engine-host spans (queue wait, device dispatch, replication ack wait)
stitch into the proxy's trace — in-process when proxy and engine host
share an interpreter (the test/bench shape), by shared trace_id across
processes otherwise.

Recording is TAIL-sampled: spans are buffered on the live trace and the
keep/drop decision happens when the root finishes — error, shed, and
slow-threshold traces are always kept, the rest kept with probability
``sample``. Kept traces land in a lock-sharded ring buffer served by
``/debug/traces``. ``sample == 0`` records no span at all: every hook
degrades to the profiler annotation (while a session runs) and, for a
stage, its histogram observation — under two microseconds (the bench
acceptance pin).

Spans cross threads explicitly: ``contextvars`` carry the active span
through ``asyncio`` tasks and ``asyncio.to_thread``, and executor-pool
hops (which do NOT copy context) re-enter via ``capture()`` /
``activate()``.

A STAGE (``tracer.stage(name, metrics.histogram(...))``) is how a step
of the served path is measured, three ways at once: a span as above;
an observation in a histogram of its own name, for EVERY request and
not only the tail-sampled ones (what ``/metrics`` and the benchmark
read); and a
``jax.profiler.TraceAnnotation("sdbkp:<name>")``, so that while a
profiler session runs the stage lies on the device trace's clock.
Every span carries the annotation; ``stage`` adds the histogram.

A wall clock cannot tell work from waiting for the interpreter lock. A
stage that starts and ends on one thread with no ``await`` between
takes ``cpu=metrics.counter("<stage>_cpu_seconds_total")`` too: the
thread's own CPU clock is read beside the wall clock at both ends and
the difference added to the counter (the span carries it as
``cpu_us``). Wall minus CPU of such a stage is the time its thread did
not run: it waited for the lock or, in native code that holds none,
for a core. A stage that crosses an ``await`` on the event loop gets
no ``cpu``: the loop's thread runs other requests meanwhile. The CPU
clock is a system call where the wall clock is not, so one such stage
in ``CPU_EVERY``, drawn at random, reads it and counts for them all.
"""

from __future__ import annotations

import asyncio
import contextvars
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

from ..utils.metrics import metrics

ANNOTATION_PREFIX = "sdbkp:"

# One ``cpu=`` stage in this many reads its thread's CPU clock, and adds
# this many times what it read. ``time.thread_time()`` is a system call
# held under the interpreter lock: 0.3 us on a plain kernel, 6 us and
# more under a sandboxed one (gVisor, where the benchmark's chips are),
# and ten of them a list, every list, cost the two cells that the lock
# binds 1-5% of their requests/s (PERF.md section 6, PR 37). Read one
# stage in sixteen they cost a sixteenth, and a window's thousands of
# stages still sum to their CPU. Measured, not an option.
CPU_EVERY = 16

_FLAG_SAMPLED = 0x01

# (trace, parent_span_id) of the code currently executing, or None
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "sdbkp_trace", default=None)


def parse_traceparent(header) -> Optional[tuple[str, str, int]]:
    """``(trace_id, parent_span_id, flags)`` from a W3C ``traceparent``
    (version 00), or ``None`` for anything malformed — a bad header from
    an arbitrary client must start a fresh trace, never raise."""
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().lower().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    try:
        int(version, 16), int(trace_id, 16), int(span_id, 16)
        f = int(flags, 16)
    except ValueError:
        return None
    return trace_id, span_id, f


def format_traceparent(trace_id: str, span_id: str,
                       sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def _flag_exception(trace: "Trace", e: BaseException) -> None:
    """Trace-level flag for an exception crossing a span boundary: load
    sheds are the admission design WORKING and must stay distinguishable
    from real failures — they flag "shed", everything else "error" (both
    always survive tail sampling). Lazy import: only the exception path
    pays it, and obs/ stays import-light."""
    from ..admission import AdmissionRejected

    if isinstance(e, AdmissionRejected):
        trace.flag("shed")
    else:
        trace.flag("error")


def _annotation(name: str):
    """An entered ``TraceAnnotation("sdbkp:<name>")``, or None. Best
    effort, and only in a process that has imported jax already: without
    it no profiler session can be running, and a proxy in front of a
    remote engine must not pay jax's import for a name nobody records.
    Nor is one built while no session runs: it would record nothing.
    The event is written whole when the annotation exits, on whatever
    thread that is, so a stage may cross an ``await`` or finish on a
    worker thread."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        cls = jax.profiler.TraceAnnotation
        if not cls.is_enabled():
            return None
        ann = cls(ANNOTATION_PREFIX + name)
        ann.__enter__()
    except Exception:  # noqa: BLE001 - API drift must not break serving
        return None
    return ann


def _end_annotation(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


def _new_trace_id() -> str:
    return f"{random.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{random.getrandbits(64):016x}"


class Span:
    """One named, timed segment of a trace. ``set()`` attaches attributes
    (JSON-safe values only); ``finish()`` records it onto its trace —
    callable from any thread, exactly once (later calls are ignored)."""

    __slots__ = ("trace", "span_id", "parent_id", "name", "start_epoch",
                 "_t0", "duration", "attrs", "_done")

    def __init__(self, trace: "Trace", parent_id: Optional[str], name: str,
                 attrs: Optional[dict] = None):
        self.trace = trace
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.start_epoch = time.time()
        self._t0 = time.perf_counter()
        self.duration = 0.0
        self.attrs = dict(attrs) if attrs else {}
        self._done = False

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def traceparent(self) -> str:
        return format_traceparent(self.trace.trace_id, self.span_id)

    @property
    def trace_id(self) -> str:
        return self.trace.trace_id

    def finish(self) -> None:
        if self._done:
            return
        self._done = True
        self.duration = time.perf_counter() - self._t0
        self.trace.record(self)

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start_epoch,
            "duration_us": int(self.duration * 1e6),
            "attrs": self.attrs,
        }


class _NullSpan:
    """The disabled-path stand-in: every hook stays unconditional at the
    call site while costing nothing."""

    __slots__ = ()
    trace_id = None
    span_id = None

    def set(self, key, value) -> None:
        pass

    def traceparent(self):
        return None

    def finish(self) -> None:
        pass


NULL_SPAN = _NullSpan()


class Stage:
    """One measured stage, running from construction to ``finish()``:
    span (when a trace is active and tracing is on), profiler annotation
    and, at the finish, one observation in ``histogram`` (the call site
    registers it by its literal name: the metrics contract). As a
    context manager the span nests what runs inside it; a bare
    ``finish()`` — from any thread, once — leaves it a leaf, for a stage
    that ends elsewhere than it began. With a ``cpu`` counter (only for
    a stage that ends on the thread it began on, with no ``await``
    between) the thread's CPU seconds between the two ends, read inside
    the wall clock's two readings and so never more than the wall time,
    are added to it ``cpu_weight`` times over: the stage stands for that
    many that went unread (:meth:`Tracer.stage` draws which).
    Built by :meth:`Tracer.stage`."""

    __slots__ = ("_span", "_ann", "_hist", "_t0", "_token", "_cpu", "_c0",
                 "_cpu_weight")

    def __init__(self, span: Optional[Span], name: str, histogram=None,
                 cpu=None, cpu_weight: int = 1):
        self._span = span
        self._hist = histogram
        self._cpu = cpu
        self._cpu_weight = cpu_weight
        self._token = None
        self._ann = _annotation(name)
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time() if cpu is not None else 0.0

    # the span's surface, so a call site reads the same with tracing off
    def set(self, key: str, value) -> None:
        if self._span is not None:
            self._span.set(key, value)

    def traceparent(self):
        return None if self._span is None else self._span.traceparent()

    def finish(self) -> None:
        if self._t0 is None:
            return
        if self._cpu is not None:
            used = time.thread_time() - self._c0
            self._cpu.inc(used * self._cpu_weight)
            if self._span is not None:
                self._span.set("cpu_us", int(used * 1e6))
        dt, self._t0 = time.perf_counter() - self._t0, None
        _end_annotation(self._ann)
        if self._hist is not None:
            self._hist.observe(dt)
        if self._span is not None:
            self._span.finish()

    def __enter__(self) -> "Stage":
        if self._span is not None:
            self._token = _CURRENT.set((self._span.trace,
                                        self._span.span_id))
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._span is not None:
            if exc is not None:
                self._span.set("error", repr(exc))
                _flag_exception(self._span.trace, exc)
            _CURRENT.reset(self._token)
        self.finish()


class _LoopWait:
    """Stage ``loop_wait`` (``proxy_loop_wait_seconds``) over one trip
    through the event loop's queue: ``start()`` where the work ends, on
    whatever thread; ``finish()`` where the coroutine that waited for it
    runs again. Either may come alone, or ``start()`` late (the waiter
    was cancelled): what started is finished, and after ``finish()``
    nothing starts."""

    __slots__ = ("_tracer", "_stage", "_closed")

    def __init__(self, tracer: "Tracer"):
        self._tracer = tracer
        self._stage = None
        self._closed = False

    def start(self) -> None:
        if self._closed:
            return
        self._stage = self._tracer.stage(
            "loop_wait", metrics.histogram("proxy_loop_wait_seconds"))
        if self._closed:  # finish() ran meanwhile, on the loop's thread
            self._stage.finish()

    def finish(self) -> None:
        self._closed = True
        if self._stage is not None:
            self._stage.finish()


class SpawnedTask:
    """A task started beside the coroutine that will ``wait()`` for it.
    It crosses the event loop's queue twice more than the ``to_thread``
    inside it does, and both are stage ``loop_wait`` too: from the spawn
    until its first step runs, and from its last line until the waiting
    coroutine runs again — the latter only if that coroutine was in
    ``wait()`` by then (before, it waits for something else, and the
    time is that thing's). On a loop busy with other requests' work
    these are most of a request. Built by :meth:`Tracer.spawn`."""

    __slots__ = ("task", "_tracer", "_first", "_last")

    def __init__(self, tracer: "Tracer", fn):
        self._tracer = tracer
        self._first = _LoopWait(tracer)
        self._first.start()
        self._last = None
        # ensure_future copies the contextvar context: spans opened in
        # the task land on the spawning request's trace
        self.task = asyncio.ensure_future(self._run(fn))

    async def _run(self, fn):
        self._first.finish()
        try:
            return await fn()
        finally:
            if self._last is not None:
                self._last.start()

    def cancel(self) -> None:
        self.task.cancel()
        self._first.finish()  # its first step may never run

    async def wait(self, timeout: float):
        """The task's result, as ``asyncio.wait_for`` gives it."""
        self._last = _LoopWait(self._tracer)
        try:
            return await asyncio.wait_for(self.task, timeout)
        finally:
            self._last.finish()


class Trace:
    """A live trace: the span accumulator plus trace-level flags. Spans
    append under a lock (proxy event loop, to_thread workers, and engine
    host executor threads all record concurrently)."""

    __slots__ = ("trace_id", "external", "flags", "spans", "start_epoch",
                 "_t0", "_lock")

    def __init__(self, trace_id: Optional[str] = None,
                 external: bool = False):
        self.trace_id = trace_id or _new_trace_id()
        # external: the root lives in ANOTHER process (an engine host
        # serving a remote proxy's op) — this trace holds a satellite
        # fragment, finished per-op instead of per-request
        self.external = external
        self.flags: dict = {}
        self.spans: list[Span] = []
        self.start_epoch = time.time()
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def record(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def flag(self, key: str, value=True) -> None:
        self.flags[key] = value

    def stage_micros(self) -> dict:
        """Total finished-span duration per span name, in integer
        microseconds — the audit line's per-stage attribution."""
        out: dict[str, int] = {}
        with self._lock:
            for s in self.spans:
                out[s.name] = out.get(s.name, 0) + int(s.duration * 1e6)
        return out

    def to_dict(self) -> dict:
        with self._lock:
            spans = [s.to_dict() for s in self.spans]
        return {
            "trace_id": self.trace_id,
            "start": self.start_epoch,
            "duration_us": int((time.perf_counter() - self._t0) * 1e6),
            "flags": dict(self.flags),
            "external": self.external,
            "spans": spans,
        }


class Tracer:
    """The process-global span recorder (module-level ``tracer``).
    ``configure()`` is how flags reach it; every hook below is safe to
    call with tracing disabled or no active trace."""

    RING_SHARDS = 8

    def __init__(self, sample: float = 0.1, slow_ms: float = 250.0,
                 ring: int = 256):
        self._rand = random.random
        self._live_lock = threading.Lock()
        self._live: dict[str, Trace] = {}
        self.configure(sample=sample, slow_ms=slow_ms, ring=ring)

    def configure(self, sample: Optional[float] = None,
                  slow_ms: Optional[float] = None,
                  ring: Optional[int] = None, _rand=None) -> None:
        if sample is not None:
            self.sample = max(0.0, min(1.0, float(sample)))
        if slow_ms is not None:
            self.slow_s = max(0.0, float(slow_ms)) / 1e3
        if ring is not None:
            per = max(1, int(ring) // self.RING_SHARDS)
            self._shards = [(threading.Lock(), deque(maxlen=per))
                            for _ in range(self.RING_SHARDS)]
        if _rand is not None:
            self._rand = _rand

    @property
    def enabled(self) -> bool:
        return self.sample > 0.0

    # -- context ------------------------------------------------------------

    def capture(self):
        """The active (trace, parent_span_id), for crossing an executor
        hop that does not copy contextvars; re-enter with
        :meth:`activate`."""
        return _CURRENT.get()

    @contextmanager
    def activate(self, captured):
        """Make a captured context the active one in THIS thread (worker
        pools; ``asyncio.to_thread`` copies context by itself)."""
        if captured is None:
            yield
            return
        token = _CURRENT.set(captured)
        try:
            yield
        finally:
            _CURRENT.reset(token)

    def current_trace(self) -> Optional[Trace]:
        cur = _CURRENT.get()
        return cur[0] if cur is not None else None

    def current_trace_id(self) -> Optional[str]:
        cur = _CURRENT.get()
        return cur[0].trace_id if cur is not None else None

    def current_traceparent(self) -> Optional[str]:
        cur = _CURRENT.get()
        if cur is None:
            return None
        return format_traceparent(cur[0].trace_id, cur[1])

    def flag(self, key: str, value=True) -> None:
        """Set a trace-level flag (error/shed/...) on the active trace;
        flagged traces survive tail sampling unconditionally."""
        cur = _CURRENT.get()
        if cur is not None:
            cur[0].flag(key, value)

    def flagged(self, key: str) -> bool:
        cur = _CURRENT.get()
        return bool(cur is not None and cur[0].flags.get(key))

    def stage_micros(self) -> dict:
        cur = _CURRENT.get()
        return cur[0].stage_micros() if cur is not None else {}

    # -- span lifecycle -----------------------------------------------------

    @contextmanager
    def start(self, name: str, traceparent: Optional[str] = None, **attrs):
        """Open a ROOT span (proxy ingress): adopts the trace_id from a
        valid incoming ``traceparent``, mints one otherwise. Exiting the
        context finishes the trace and runs the tail-sampling decision."""
        ann = _annotation(name)
        if not self.enabled:
            try:
                yield NULL_SPAN
            finally:
                _end_annotation(ann)
            return
        parsed = parse_traceparent(traceparent)
        trace = Trace(parsed[0] if parsed else None)
        root = Span(trace, parsed[1] if parsed else None, name, attrs)
        with self._live_lock:
            if trace.trace_id in self._live:
                # a second in-flight request reusing the same incoming
                # traceparent (client retry racing its original): sharing
                # the live entry would cross-stitch engine-host spans and
                # stage timings between unrelated requests — mint a fresh
                # trace and keep the client's id as an attribute
                requested = trace.trace_id
                trace = Trace()
                root = Span(trace, None, name, attrs)
                root.set("requested_trace_id", requested)
            self._live[trace.trace_id] = trace
        token = _CURRENT.set((trace, root.span_id))
        try:
            yield root
        except BaseException as e:
            root.set("error", repr(e))
            _flag_exception(trace, e)
            raise
        finally:
            _CURRENT.reset(token)
            _end_annotation(ann)
            root.finish()
            with self._live_lock:
                if self._live.get(trace.trace_id) is trace:
                    del self._live[trace.trace_id]
            self._tail_decide(trace, root)

    def stage(self, name: str, histogram=None, cpu=None, **attrs) -> Stage:
        """Start a :class:`Stage`: a child span of whatever is active
        (none when nothing is, or tracing is off), the profiler
        annotation ``sdbkp:<name>`` and, given a ``histogram``
        (``metrics.histogram("<stage>_seconds")`` at the call site), an
        observation of the stage's seconds at its finish; given a
        ``cpu`` counter (``metrics.counter("<stage>_cpu_seconds_total")``,
        where the stage stays on one thread and crosses no ``await``),
        the thread's CPU seconds too, for one such stage in
        ``CPU_EVERY`` (:meth:`cpu_weight`). ``with``
        it where it nests other spans; ``finish()`` it by hand where it
        ends on another thread. Exceptions mark the span AND flag the
        trace as error before propagating."""
        cur = _CURRENT.get()
        span = None
        if cur is not None and self.enabled:
            span = Span(cur[0], cur[1], name, attrs)
        weight = self.cpu_weight() if cpu is not None else 0
        return Stage(span, name, histogram, cpu if weight else None, weight)

    def cpu_weight(self) -> int:
        """Whether this stage reads its thread's CPU clock: 0 for the
        ones that go unread, ``CPU_EVERY`` for the one in ``CPU_EVERY``
        that is read, which adds that many times its reading to its
        counter. Drawn, not counted off: the stages of a request come in
        one order, and a fixed stride would always read the same ones.
        For a stage timed by hand (``bulk_cache``); :meth:`stage` asks
        by itself."""
        return CPU_EVERY if self._rand() * CPU_EVERY < 1.0 else 0

    def span(self, name: str, **attrs) -> Stage:
        """A stage with no histogram: span + annotation. ``with`` it to
        nest children under it; or, for async dispatch paths whose
        completion callback runs elsewhere, keep it a LEAF: never
        entered, the caller owns ``finish()``."""
        return self.stage(name, None, **attrs)

    async def to_thread(self, fn, *args, **kwargs):
        """``asyncio.to_thread`` with both hand-overs measured: stage
        ``executor_wait`` runs from the submit to the first line in the
        worker (``proxy_executor_wait_seconds``), stage ``loop_wait``
        from the worker's last line until the event loop runs the
        awaiting coroutine again (``proxy_loop_wait_seconds``)."""
        wait = self.stage("executor_wait",
                          metrics.histogram("proxy_executor_wait_seconds"))
        back = _LoopWait(self)

        def in_worker():
            wait.finish()
            try:
                return fn(*args, **kwargs)
            finally:
                back.start()

        try:
            return await asyncio.to_thread(in_worker)
        finally:
            wait.finish()  # cancelled before a worker took it
            back.finish()

    def spawn(self, fn) -> "SpawnedTask":
        """``asyncio.ensure_future(fn())`` with the task's own trips
        through the event loop's queue measured: :class:`SpawnedTask`."""
        return SpawnedTask(self, fn)

    @contextmanager
    def adopt(self, wire: Optional[str], name: str, **attrs):
        """Engine-host entry: attach to the trace named by a wire-carried
        ``traceparent``. When the trace is LIVE in this process (proxy and
        engine host sharing an interpreter), spans stitch straight into
        it; otherwise a satellite trace fragment is recorded under the
        same trace_id and tail-sampled on its own when the op ends."""
        parsed = parse_traceparent(wire) if wire else None
        if parsed is None or not self.enabled:
            yield NULL_SPAN
            return
        trace_id, parent_id, _flags = parsed
        with self._live_lock:
            live = self._live.get(trace_id)
        if live is not None:
            sp = Span(live, parent_id, name, attrs)
            token = _CURRENT.set((live, sp.span_id))
            try:
                yield sp
            except BaseException as e:
                sp.set("error", repr(e))
                _flag_exception(live, e)
                raise
            finally:
                _CURRENT.reset(token)
                sp.finish()
            return
        trace = Trace(trace_id, external=True)
        root = Span(trace, parent_id, name, attrs)
        token = _CURRENT.set((trace, root.span_id))
        try:
            yield root
        except BaseException as e:
            root.set("error", repr(e))
            _flag_exception(trace, e)
            raise
        finally:
            _CURRENT.reset(token)
            root.finish()
            self._tail_decide(trace, root)

    # -- tail sampling + ring -----------------------------------------------

    def _tail_decide(self, trace: Trace, root: Span) -> None:
        keep = (bool(trace.flags)
                or root.duration >= self.slow_s
                or self._rand() < self.sample)
        if not keep:
            return
        lock, ring = self._shards[hash(trace.trace_id) % self.RING_SHARDS]
        with lock:
            ring.append(trace.to_dict())

    def recent(self, limit: int = 64) -> list[dict]:
        """Most recent kept traces, newest first."""
        out: list[dict] = []
        for lock, ring in self._shards:
            with lock:
                out.extend(ring)
        out.sort(key=lambda t: t["start"], reverse=True)
        return out[:max(0, int(limit))]

    def reset(self) -> None:
        """Drop every kept trace (tests)."""
        for lock, ring in self._shards:
            with lock:
                ring.clear()


tracer = Tracer()

"""Cross-request lookup fusing: concurrent LookupResources queries of
different subjects become one device dispatch of several subject rows.

The reference overlaps concurrent prefilters with goroutines, but each
still costs SpiceDB a full LookupResources dispatch
(/root/reference/pkg/authz/responsefilterer.go:165-183). Here a lookup
that misses the decision cache passes this batcher, with default flags,
and what it does is decided from what the engine can see, not from a
window an operator sets:

- a lookup that finds nothing waiting, no lookup dispatch being enqueued
  and fewer than ``FUSED_DEPTH`` lookup dispatches unfinished on the
  device goes at once and alone, through ``Engine._lookup_direct`` as
  without a batcher;
- what arrives meanwhile waits, and rides the next flush. Fewer than
  ``MIN_ROWS`` waiting cannot fuse and leave as soon as the device has
  fewer than ``FUSED_DEPTH`` dispatches, one each, pipelined as without
  a batcher; ``MIN_ROWS`` or more wait on, gathering company, until the
  device has nothing left or a whole dispatch of ``FUSED_ROWS`` waits,
  and leave fused. The waiters themselves watch the oldest unfinished
  dispatch, and the first to see it finish takes everything that waits
  (itself included) and enqueues it. No timer, no thread of its own.

Which graphs fuse is read from the compiled graph (``fused_rows``), by
what the chip said (PERF.md §6, PR 32): the fused program is not the
one-row program with more rows, because every pass over the state is B
rows wide. On a graph with no dense block a dispatch of 8 rows costs
what 2.5 of one row cost, and no more from 2 rows to 8, so it is used
from ``MIN_ROWS`` waiting lookups; with dense blocks it costs what 11
to 13 cost (the kernels change with B too), so such a graph's lookups
never pass the queue and are served exactly as without a batcher, as a
tiered graph's are (it streams blocks by the rows it is asked for).

A fused dispatch has ONE shape a window: ``FUSED_ROWS`` subject rows
(those nobody asked for are seeded with the trash slot), read back as
the grid of that many rows over the type's window; so a compiled graph
that fuses has two lookup programs a (type, permission), and both are
compiled by the window's first lookup: the fused one by a dispatch of
trash seeds on a thread of its own (``_FusedProgram``), beside the
lookup's own one-row dispatch, and that lookup alone waits for it (one
after the other the two compiles, 5 to 6 s each on the chip for the
tree deployment on an empty compile cache, would take that lookup past
the 10 s a prefilter is given: authz/middleware.py). Every
other lookup that finds the program not compiled yet goes alone, as
without a batcher, and none waits: a burst that has been answered has
both programs, and no later one meets a compile. A fused dispatch
answers each row exactly as a dispatch of that row alone; rows of
different subjects never share a mask.

Thread-safe and synchronous-friendly: callers run in worker threads
(asyncio.to_thread); ``submit`` never blocks on the device, ``result()``
does (a window's first lookup blocks in ``submit`` on its two compiles).
Errors propagate to every caller of the affected dispatch.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import numpy as np

from ..obs.trace import tracer
from ..utils.metrics import metrics

log = logging.getLogger("sdbkp.batcher")

# lookup dispatches left unfinished on the device before a lookup waits
# for company: one running and one queued behind it keep the device busy
# across the host's enqueue of the next
FUSED_DEPTH = 2
# subject rows of the fused program, and the waiting lookups from which
# it is used: on the chip 8 rows cost what 2.5 one-row dispatches cost on
# the graphs with no dense block (25.1 against 9.99 ms, 21.3 against 8.2),
# and 32 rows 13 on one of them (PERF.md §6, PR 32)
FUSED_ROWS = 8
MIN_ROWS = 3


def fused_rows(cg) -> int:
    """Rows of a compiled graph's fused lookup program; 0: its lookups do
    not fuse (dense blocks, or tiered: see the module's docstring)."""
    return 0 if cg.blocks or cg.tier is not None else FUSED_ROWS


def _unfinished(out) -> bool:
    ready = getattr(out, "is_ready", None)
    return ready is not None and not ready()


class _FusedProgram:
    """The fused lookup program of one window of a compiled graph: kept
    beside the graph's compiled programs (``CompiledGraph.memo``), so it
    lives as long as the jitted entry and rides along incremental
    updates. ``ready``: compiled, by one dispatch of trash seeds."""

    def __init__(self, off: int, n: int):
        self.off, self.n = off, n
        self.ready = False
        self._begun = threading.Lock()

    def beside(self, cg, lookup):
        """-> ``lookup()``. The one caller that finds the compile not
        begun (the window's first lookup) runs it on a thread of its
        own meanwhile, and returns when both have ended."""
        if not self._begun.acquire(blocking=False):
            return lookup()
        compile_ = threading.Thread(target=self._compile, args=(cg,),
                                    name="sdbkp-fused-program", daemon=True)
        compile_.start()
        try:
            return lookup()
        finally:
            compile_.join()

    def _compile(self, cg) -> None:
        try:
            cg.query_async(np.full((FUSED_ROWS, 2), cg.M, dtype=np.int32),
                           None, None,
                           q_contig_grid=(self.off, self.n, FUSED_ROWS)
                           ).result()
            self.ready = True
        except BaseException:  # noqa: BLE001 - lookups just keep going alone
            log.exception("fused lookup program did not compile")


class BatchedLookup:
    """One caller's lookup. ``result()`` blocks until its dispatch is
    enqueued (driving the batcher meanwhile), then materializes from the
    shared device future — so submitting never blocks on device
    execution (the non-blocking contract of
    lookup_resources_mask_async holds through the batcher)."""

    __slots__ = ("_batcher", "_event", "_thunk", "_value", "_error",
                 "_done")

    def __init__(self, batcher: "LookupBatcher"):
        self._batcher = batcher
        self._event = threading.Event()
        self._thunk = None
        self._value = None
        self._error: Optional[BaseException] = None
        self._done = False

    def _resolve(self, thunk) -> None:
        self._thunk = thunk
        self._event.set()

    def _reject(self, err: BaseException) -> None:
        self._error = err
        self._event.set()

    def result(self):
        if not self._event.is_set():
            self._batcher._drive(self)
        if not self._done:
            if self._error is None:
                try:
                    self._value = self._thunk()
                except BaseException as e:  # noqa: BLE001
                    self._error = e
            self._done = True
        if self._error is not None:
            raise self._error
        return self._value


class _Waiting:
    """A submitted lookup that no dispatch carries yet."""

    __slots__ = ("args", "fut", "wait")

    def __init__(self, args: tuple, fut: BatchedLookup):
        self.args = args
        self.fut = fut
        # submit -> the flush that carries it, finished by the flusher
        self.wait = tracer.stage(
            "batch_wait", metrics.histogram("engine_batch_wait_seconds"))


class LookupBatcher:
    """Fuses ``lookup_resources_mask`` calls across threads."""

    def __init__(self, engine):
        self.engine = engine
        self._cond = threading.Condition()
        self._pending: list[_Waiting] = []
        self._enqueuing = False
        self._inflight: list = []  # device outputs of lookup dispatches
        self._closed = False

    def submit(self, resource_type: str, permission: str, subject_type: str,
               subject_id: str,
               subject_relation: Optional[str]):
        """Only now-less lookups pass here (callers pinning an explicit
        evaluation time are dispatched directly by the engine), so one
        dispatch-time clock is correct for the whole fused batch, exactly
        like the unbatched path.

        -> a future with ``result()``. A lookup whose window has no fused
        program yet, and a late submit racing ``close()``, take the direct
        engine path on the caller's thread; the window's first begins the
        program's compile beside its own and returns when both have
        ended."""
        args = (resource_type, permission, subject_type, subject_id,
                subject_relation)
        e = self.engine
        cg = e.compiled()
        prog = None if self._closed else self._program(
            cg, resource_type, permission)
        if prog is None:
            return e._lookup_direct(*args, None)
        if not prog.ready:
            return prog.beside(cg, lambda: e._lookup_direct(*args, None))
        item = _Waiting(args, BatchedLookup(self))
        with self._cond:
            closed = self._closed
            if not closed:
                self._pending.append(item)
            batch = self._take_locked()
        if closed:
            item.wait.finish()
            return e._lookup_direct(*args, None)
        self._flush(batch)
        return item.fut

    @staticmethod
    def _program(cg, resource_type: str,
                 permission: str) -> Optional[_FusedProgram]:
        """The fused program of this lookup's window on this graph,
        compiled or not; None where the window's lookups do not fuse."""
        off = cg.offset_of(resource_type, permission)
        n = cg.type_sizes.get(resource_type)
        if not fused_rows(cg) or off is None or n is None:
            return None
        return cg.memo(("fused", off, n), lambda: _FusedProgram(off, n))

    def _take_locked(self) -> Optional[list]:
        """Everything that waits, if it may go now (the module's
        docstring says when)."""
        if self._enqueuing or not self._pending:
            return None
        self._inflight = [o for o in self._inflight if _unfinished(o)]
        waiting = len(self._pending)
        if self._inflight and not self._closed and not (
                len(self._inflight) < FUSED_DEPTH and (
                    waiting < MIN_ROWS or waiting >= FUSED_ROWS)):
            return None
        batch, self._pending = self._pending, []
        self._enqueuing = True
        return batch

    def _drive(self, fut: BatchedLookup) -> None:
        """A caller whose lookup still waits: sleep until the dispatch
        being enqueued is, or the oldest on the device has finished, and
        flush what waits then, unless another waiter was first."""
        while not fut._event.is_set():
            oldest = None
            with self._cond:
                if fut._event.is_set():
                    return
                batch = self._take_locked()
                if batch is None:
                    if self._enqueuing or not self._inflight:
                        self._cond.wait(0.05)
                        continue
                    oldest = self._inflight[0]
            if batch is not None:
                self._flush(batch)
            else:
                try:
                    oldest.block_until_ready()
                except Exception:  # noqa: BLE001 - its own callers see it
                    pass

    def _flush(self, batch: Optional[list]) -> None:
        while batch:
            enqueued: list = []
            for item in batch:
                item.wait.finish()
            try:
                self._dispatch(batch, enqueued)
            except BaseException as e:  # noqa: BLE001 - fan the error out
                for item in batch:
                    if not item.fut._event.is_set():
                        item.fut._reject(e)
            with self._cond:
                self._enqueuing = False
                self._inflight.extend(q._out for q in enqueued)
                self._cond.notify_all()
                # a closed batcher has no waiter left to count on
                batch = self._take_locked() if self._closed else None

    def _dispatch(self, batch: list, enqueued: list) -> None:
        """Enqueue a batch: the lookups of one (type, permission) in
        fused dispatches where ``MIN_ROWS`` of them or more wait, the
        others alone."""
        e = self.engine
        cg = e.compiled()
        groups: dict[tuple, list] = {}
        for item in batch:
            groups.setdefault(item.args[:2], []).append(item)
        for (rt, perm), items in groups.items():
            prog = self._program(cg, rt, perm)
            while prog is not None and prog.ready \
                    and len(items) >= MIN_ROWS:
                self._fused(cg, items[:FUSED_ROWS], enqueued)
                items = items[FUSED_ROWS:]
            for item in items:
                alone = e._lookup_direct(*item.args, None,
                                         enqueued=enqueued)
                item.fut._resolve(alone.result)

    def _fused(self, cg, chunk: list, enqueued: list) -> None:
        from .engine import _count_dispatch_rows, mask_pseudo_objects

        e = self.engine
        objs = e._objects_by_name()
        rt, perm = chunk[0].args[:2]
        off, n = cg.offset_of(rt, perm), cg.type_sizes[rt]
        interner = objs[rt]
        with tracer.stage("engine_encode",
                          metrics.histogram("engine_encode_seconds"),
                          metrics.counter("engine_encode_cpu_seconds_total")):
            seeds = np.full((FUSED_ROWS, 2), cg.M, dtype=np.int32)
            for i, item in enumerate(chunk):
                _rt, _perm, st, sid, srl = item.args
                seeds[i] = cg.encode_subject(st, sid, srl, objs)
        t0 = time.perf_counter()
        qfut = cg.query_async(seeds, None, None,
                              q_contig_grid=(off, n, FUSED_ROWS))
        enqueued.append(qfut)
        metrics.counter("engine_lookup_batches_total").inc()
        metrics.counter("engine_lookups_total").inc(len(chunk))
        _count_dispatch_rows(len(chunk))
        once = threading.Lock()  # taken by the first caller to read

        def materialize(i: int):
            with tracer.stage("device_wait", metrics.histogram(
                    "engine_device_wait_seconds"),
                    rows=len(chunk)) as wait:
                out = qfut.result()  # QueryFuture memoizes; thread-safe
            if once.acquire(blocking=False):
                # once a dispatch, as the one-row path counts its own
                metrics.histogram("engine_lookup_seconds").observe(
                    time.perf_counter() - t0)
                it = qfut.iterations()
                wait.set("fixpoint_iters", it)
                wait.set("core_edges", cg.core_edges())
                metrics.histogram("engine_fixpoint_iterations").observe(it)
                missing = qfut.caveats_missing()
                if missing:
                    metrics.counter(
                        "engine_caveat_denied_missing_context_total"
                    ).inc(missing)
            return mask_pseudo_objects(
                np.array(out[i * n:(i + 1) * n])), interner

        for i, item in enumerate(chunk):
            item.fut._resolve(lambda i=i: materialize(i))

    def close(self) -> None:
        """Flush what waits and mark the batcher dead: submits from here
        on bypass it entirely (direct engine path)."""
        with self._cond:
            self._closed = True
            batch = self._take_locked()
        self._flush(batch)

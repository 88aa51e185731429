"""Cross-request lookup batching: coalesce concurrent LookupResources
queries into one device dispatch.

The reference overlaps concurrent prefilters with goroutines, but each
still costs SpiceDB a full LookupResources dispatch
(/root/reference/pkg/authz/responsefilterer.go:165-183). On TPU the batch
axis is nearly free below the bit-kernel ceiling (ops/bitprop.py
BIT_B_MAX): this batcher holds a lookup for at most ``window`` seconds,
fusing up to ``max_rows`` concurrent subjects into ONE fixpoint whose
q_slots concatenate every caller's slot range (q_batch maps slots to
batch rows). 256 concurrent list requests (BASELINE config 5) become ~32
dispatches instead of 256.

Thread-safe and synchronous-friendly: callers run in worker threads
(asyncio.to_thread); futures block on an event. Errors propagate to every
caller of the affected flush.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np


class BatchedLookup:
    """One caller's pending lookup. ``result()`` blocks until the batch is
    DISPATCHED, then materializes from the shared device future — so the
    submitting threads never block on device execution (the non-blocking
    contract of lookup_resources_mask_async holds through the batcher)."""

    __slots__ = ("_event", "_thunk", "_value", "_error", "_done")

    def __init__(self):
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
        self._event.wait()
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


class LookupBatcher:
    """Coalesces ``lookup_resources_mask`` calls across threads."""

    def __init__(self, engine, window: float = 0.002, max_rows: int = 8):
        self.engine = engine
        self.window = window
        self.max_rows = max_rows
        self._lock = threading.Lock()
        self._pending: list[tuple] = []  # (args tuple, BatchedLookup)
        self._timer: Optional[threading.Timer] = None
        self._closed = False

    def submit(self, resource_type: str, permission: str, subject_type: str,
               subject_id: str,
               subject_relation: Optional[str]) -> BatchedLookup:
        """Only now-less lookups batch (callers pinning an explicit
        evaluation time bypass the batcher — the engine dispatches those
        directly), so one dispatch-time clock is correct for the whole
        fused batch, exactly like the unbatched path.

        A late submit racing ``close()`` (disable_lookup_batching during
        shutdown reads ``engine._batcher`` before it is nulled) falls
        through to the direct engine path instead of queueing into a dead
        batcher whose timer will never fire."""
        fut = BatchedLookup()
        with self._lock:
            closed = self._closed
            batch = None
            if not closed:
                self._pending.append(
                    ((resource_type, permission, subject_type, subject_id,
                      subject_relation), fut))
                n = len(self._pending)
                if n >= self.max_rows:
                    batch = self._take_locked()
                elif n == 1:
                    self._timer = threading.Timer(self.window,
                                                  self._on_timer)
                    self._timer.daemon = True
                    self._timer.start()
        if closed:
            return self.engine._lookup_direct(
                resource_type, permission, subject_type, subject_id,
                subject_relation, None)
        if batch:
            self._flush(batch)
        return fut

    def _take_locked(self) -> list:
        batch = self._pending
        self._pending = []
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        return batch

    def _on_timer(self) -> None:
        with self._lock:
            batch = self._take_locked()
        if batch:
            self._flush(batch)

    def _flush(self, batch: list) -> None:
        try:
            self._dispatch(batch)
        except BaseException as e:  # noqa: BLE001 - fan the error out
            for _, fut in batch:
                fut._reject(e)

    def _dispatch(self, batch: list) -> None:
        import time

        from ..utils.metrics import metrics
        from .engine import _count_dispatch_rows, mask_pseudo_objects

        metrics.counter("engine_lookup_batches_total").inc()
        metrics.counter("engine_lookups_total").inc(len(batch))
        e = self.engine
        cg = e.compiled()
        objs = e._objects_by_name()
        # canonicalize row order by (off, n): row assignment is arbitrary
        # (futures map back positionally via metas), and sorting collapses
        # the composition cache key from permutations to combinations
        def row_key(item):
            (rt, perm, _st, _sid, _srl), _fut = item
            off = cg.offset_of(rt, perm)
            return (-1 if off is None else off,
                    cg.type_sizes.get(rt) or 0)

        batch = sorted(batch, key=row_key)
        seeds = []
        q_parts = []
        qb_parts = []
        composition = []  # (off, n) per row: the fused-grid cache key
        metas = []  # (fut, interner, n) | (fut, None, 0) for trivial misses
        for (rt, perm, st, sid, srl), fut in batch:
            off = cg.offset_of(rt, perm)
            n = cg.type_sizes.get(rt)
            interner = objs.get(rt)
            if off is None or interner is None:
                metas.append((fut, None, 0))
                continue
            row = len(seeds)
            seeds.append(cg.encode_subject(st, sid, srl, objs))
            q_parts.append(off + np.arange(n, dtype=np.int32))
            qb_parts.append(np.full(n, row, dtype=np.int32))
            composition.append((off, n))
            metas.append((fut, interner, n))
        t0 = time.perf_counter()
        if seeds:
            # the fused query arrays are a pure function of the (sorted)
            # row composition: cache their device copies — concurrent
            # lists of the same resource types repeat the composition, and
            # re-uploading B x objects of slot ids per dispatch is
            # measurable host->device traffic. A single-row batch shares the
            # direct lookup path's key (identical array bytes).
            if len(composition) == 1:
                key = ("lookup",) + composition[0]
            else:
                key = ("lookup_batch", tuple(composition))
            # homogeneous batches (R concurrent lists of the SAME type +
            # permission — the common fleet shape) read R rows x one
            # shared window: promise the grid so the extraction is a
            # streamed dynamic_slice instead of an R x n random gather
            grid = None
            if len(set(composition)) == 1:
                off0, n0 = composition[0]
                grid = (off0, n0, len(composition))
            qfut = e._backend(cg).query_async(
                np.asarray(seeds, dtype=np.int32),
                np.concatenate(q_parts), np.concatenate(qb_parts),
                q_cache_key=key, q_contig_grid=grid)
            _count_dispatch_rows(len(seeds))
        else:
            qfut = None
        observed = threading.Event()

        def materialize(pos, n, interner):
            out = qfut.result()  # QueryFuture memoizes; thread-safe reads
            if not observed.is_set():
                observed.set()
                metrics.histogram("engine_lookup_seconds").observe(
                    time.perf_counter() - t0)
                # fused dispatches deny missing-context conditional
                # grants fail-closed like every other path — they must
                # tick the same counter (once per dispatch, not per row)
                missing = getattr(qfut, "caveats_missing", lambda: 0)()
                if missing:
                    metrics.counter(
                        "engine_caveat_denied_missing_context_total"
                    ).inc(missing)
            return mask_pseudo_objects(np.array(out[pos:pos + n])), interner

        pos = 0
        for fut, interner, n in metas:
            if interner is None:
                fut._resolve(lambda: (None, None))
                continue
            fut._resolve(
                (lambda p, k, it: lambda: materialize(p, k, it))(
                    pos, n, interner))
            pos += n

    def close(self) -> None:
        """Flush the pending batch and mark the batcher dead: submits
        from here on bypass it entirely (direct engine path)."""
        with self._lock:
            self._closed = True
            batch = self._take_locked()
        if batch:
            self._flush(batch)

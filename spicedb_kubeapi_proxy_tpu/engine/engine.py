"""The query engine: SpiceDB-equivalent API over the TPU reachability path.

Public surface mirrors what the reference proxy consumes from authzed-go
(SURVEY.md §2.5): WriteRelationships (create/touch/delete + preconditions),
ReadRelationships, DeleteRelationships(filter), CheckPermission /
CheckBulkPermissions, LookupResources, and Watch. All queries are fully
consistent — the reference always requests full consistency
(/root/reference/pkg/authz/check.go:42-44, lookups.go:50-52) — implemented
as compile-on-demand: a query against a stale snapshot recompiles first.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np

from ..models.bootstrap import Bootstrap, DEFAULT_BOOTSTRAP, parse_bootstrap
from ..models.schema import Schema
from ..models.tuples import Relationship
from ..obs.profile import install_jax_compile_hook
from ..obs.trace import tracer
from ..ops.reachability import (
    CompiledGraph,
    DELTA_CAPACITY,
    MAX_DELTA_RECORDS,
    _fallback,
    compile_graph,
    incremental_update,
)
from ..utils.metrics import metrics
from .batcher import LookupBatcher
from .decision_cache import DecisionCache, MISS, check_key, lookup_key
from .evaluator import OracleEvaluator
from .store import (
    Precondition,
    RelationshipFilter,
    Store,
    StoreError,
    WatchRecord,
    WriteOp,
)


class SchemaViolation(StoreError):
    pass


@dataclass(frozen=True)
class CheckItem:
    resource_type: str
    resource_id: str
    permission: str
    subject_type: str
    subject_id: str
    subject_relation: Optional[str] = None


@dataclass(frozen=True)
class WatchEvent:
    revision: int
    operation: str  # "touch" | "delete"
    relationship: Relationship


def context_digest(context) -> Optional[str]:
    """Stable digest of a request caveat-context dict, appended to
    decision-cache keys so conditional verdicts never leak across
    contexts. ``None`` for no/empty context — context-free queries keep
    today's cache keys byte-identical."""
    if not context:
        return None
    import hashlib
    import json

    try:
        blob = json.dumps(context, sort_keys=True,
                          separators=(",", ":"), default=str)
    except (TypeError, ValueError):
        blob = repr(sorted((str(k), str(v)) for k, v in context.items()))
    return hashlib.sha1(blob.encode()).hexdigest()


def mask_to_ids(mask, interner) -> list:
    """Materialize allowed id strings from a lookup mask: the ONE place
    the padded-index guard lives (padding indices can never be true — no
    edges — but the interner bound is guarded anyway). Shared by the
    in-process, remote, and multi-host lookup paths."""
    if mask is None:
        return []
    return [interner.string(i) for i in np.flatnonzero(mask).tolist()
            if i < len(interner)]


def mask_pseudo_objects(mask: np.ndarray) -> np.ndarray:
    """Clear the reserved per-type pseudo-object indices (0 = void,
    1 = the wildcard object '*') from a lookup mask — shared by the direct
    and batched lookup paths so the slot layout lives in one place."""
    mask[0] = False
    mask[1] = False
    return mask


def validate_caveat(schema: Schema, rel: Relationship) -> None:
    """A caveated write must name a DECLARED caveat and carry a
    context that encodes under the declared parameter types — a
    malformed context stored now would become missing-context
    denials (or a recompile-time error) at read time. Module-level so
    the schema migrator can re-validate stored tuples against a
    CANDIDATE schema without mutating any engine."""
    from ..caveats.ast import (
        CaveatError,
        StringInterner,
        UnencodableListError,
        encode_list,
        encode_scalar,
    )

    cdef = (schema.caveat_defs or {}).get(rel.caveat)
    if cdef is None:
        raise SchemaViolation(
            f"relationship names undeclared caveat {rel.caveat!r}")
    if not rel.caveat_context:
        return
    try:
        ctx = rel.context_dict()
    except ValueError as e:
        raise SchemaViolation(
            f"caveat {rel.caveat!r}: invalid context: {e}") from None
    scratch = StringInterner()
    for k, v in (ctx or {}).items():
        p = cdef.param(k)
        if p is None:
            raise SchemaViolation(
                f"caveat {rel.caveat!r} has no parameter {k!r}")
        try:
            if p.type.is_list:
                encode_list(v, p.type.elem, scratch)
            else:
                encode_scalar(v, p.type.name, scratch)
        except UnencodableListError:
            # well-typed but beyond the VM's list tables (an IPv6
            # element): the write is accepted — the parameter
            # resolves UNKNOWN at evaluation (fail closed, counted)
            pass
        except CaveatError as e:
            raise SchemaViolation(
                f"caveat {rel.caveat!r} context {k!r}: {e}") from None


def validate_relationship(schema: Schema, rel: Relationship) -> None:
    """Schema admission for one relationship tuple — the write path's
    gate, factored to take the schema EXPLICITLY so the migrator can ask
    "does every stored tuple still parse under S'?" before it commits to
    a transition."""
    if getattr(rel, "caveat", None):
        validate_caveat(schema, rel)
    d = schema.definitions.get(rel.resource_type)
    if d is None:
        raise SchemaViolation(f"unknown resource type {rel.resource_type!r}")
    if rel.resource_id == "*":
        # SpiceDB forbids wildcard resource ids; only subjects may be '*'
        raise SchemaViolation("resource id may not be the wildcard '*'")
    r = d.relations.get(rel.relation)
    if r is None:
        raise SchemaViolation(
            f"{rel.resource_type} has no relation {rel.relation!r}"
            + (" (permissions are not writable)"
               if rel.relation in d.permissions else "")
        )
    sub_def = schema.definitions.get(rel.subject_type)
    if sub_def is None:
        raise SchemaViolation(f"unknown subject type {rel.subject_type!r}")
    ok = False
    expiration_blocked = False
    caveat_blocked = False
    for a in r.allowed:
        if a.type != rel.subject_type:
            continue
        if rel.subject_id == "*":
            if not a.wildcard:
                continue
        elif a.wildcard or (a.relation or None) != rel.subject_relation:
            continue
        if (a.caveat or None) != (rel.caveat or None):
            # SpiceDB matches the caveat trait exactly: a caveated
            # tuple needs a `with <caveat>` entry, and an entry
            # REQUIRING a caveat never accepts an unconditional
            # tuple — another entry of the same subject type may
            # still match (`user | user with ip_allowlist`)
            caveat_blocked = True
            continue
        if rel.expiration is not None and not a.expiration:
            # another allowed entry of the same subject type may carry
            # the expiration trait (e.g. `user | user with expiration`)
            # — keep scanning instead of rejecting on the first match
            expiration_blocked = True
            continue
        ok = True
        break
    if not ok and expiration_blocked:
        raise SchemaViolation(
            f"{rel.resource_type}#{rel.relation} does not allow "
            "expiring relationships"
        )
    if not ok and caveat_blocked:
        raise SchemaViolation(
            f"{rel.resource_type}#{rel.relation} does not allow "
            + (f"subjects with caveat {rel.caveat!r}" if rel.caveat
               else "uncaveated subjects of this type")
        )
    if not ok:
        raise SchemaViolation(
            f"subject {rel.subject_type}"
            + (f"#{rel.subject_relation}" if rel.subject_relation else "")
            + f" not allowed on {rel.resource_type}#{rel.relation}"
        )
    if rel.subject_relation:
        if not schema.definitions[rel.subject_type].relation_or_permission(
            rel.subject_relation
        ):
            raise SchemaViolation(
                f"{rel.subject_type} has no relation "
                f"{rel.subject_relation!r}"
            )


def _count_dispatch_rows(rows: int) -> None:
    """One device dispatch of ``rows`` subject rows (the batch axis B
    of the fixpoint's state), counted where it is enqueued."""
    metrics.counter("engine_dispatch_rows_total").inc(rows)
    metrics.histogram(
        "engine_dispatch_batch_rows",
        buckets=(1, 2, 4, 8, 16, 64, 256, 1024, 4096, 16384, 65536),
    ).observe(rows)


class EngineFuture:
    """A dispatched engine query: ``result()`` blocks and post-processes.
    ``fut`` is a :class:`~...ops.reachability.QueryFuture` or ``None`` for
    trivially-resolved queries; multi-dispatch paths (chunked bulk checks)
    pass ``fut=None`` plus an ``iters`` callable joining their futures."""

    __slots__ = ("_fut", "_fin", "_iters")

    def __init__(self, fut, fin, iters=None):
        self._fut = fut
        self._fin = fin
        self._iters = iters

    def result(self):
        return self._fin(None if self._fut is None else self._fut.result())

    def iterations(self) -> int:
        """Fixpoint hops the query ran (dispatch-depth analog); valid
        after ``result()``."""
        if self._iters is not None:
            return self._iters()
        return 0 if self._fut is None else self._fut.iterations()


class Engine:
    """In-process relationship-graph engine (the ``embedded://`` / ``tpu://``
    backend). Thread-safe."""

    def __init__(self, bootstrap: Optional[str] = None,
                 schema: Optional[Schema] = None,
                 validate_writes: bool = True,
                 mesh=None, delta_capacity: int = DELTA_CAPACITY,
                 device_graph_budget_bytes: Optional[int] = None,
                 tier_spill_dir: Optional[str] = None):
        if schema is None:
            b: Bootstrap = parse_bootstrap(bootstrap or DEFAULT_BOOTSTRAP)
            schema = b.schema
            seed = b.relationships
        else:
            seed = []
        self.schema = schema
        self.store = Store()
        self.validate_writes = validate_writes
        self._lock = threading.RLock()
        self._compiled: Optional[CompiledGraph] = None
        self._batcher = None
        self.enable_lookup_batching()
        self._decision_cache: Optional[DecisionCache] = None
        self._persistence = None  # persistence/manager.py, opt-in
        # delta-overlay sizing for every graph this engine compiles, and
        # the optional background compactor (engine/compaction.py) that
        # folds the overlay into a fresh base off the write path
        self._delta_capacity = max(int(delta_capacity), 64)
        self._compactor = None
        # tiered graph storage (--device-graph-budget-bytes, storage/):
        # when set, every graph this engine compiles gets its dense
        # blocks residency-tracked under this device byte budget — cold
        # blocks live in host arenas and stream in on demand. 0/None =
        # classic all-resident placement.
        self._tier_budget = int(device_graph_budget_bytes or 0)
        self._tier_spill_dir = tier_spill_dir
        # (base revision, store revision) pair the incremental path
        # declined at write time — the read path must not retry (and
        # re-count) the identical suffix; any further write resets it
        self._incremental_declined: Optional[tuple] = None
        # host-side (q_slots, q_batch) arrays per (offset, size): a mask
        # lookup's query arrays are a pure function of the slot layout, so
        # rebuilding 2x400KB of arange/zeros per request is waste (their
        # DEVICE copies are already cached per key in query_async)
        self._q_host: dict[tuple, tuple] = {}
        # optional jax.sharding.Mesh ("data", "graph" axes): queries route
        # through a ShardedGraph pinned across it instead of one device
        self.mesh = mesh
        self._sharded = None
        # live schema migration (migration/migrator.py): the active
        # SchemaMigrator, the brief-freeze write gate it installs for
        # the atomic cutover, and the set of backfill-echo revisions
        # watch streams must suppress (a journaled backfill TOUCH of
        # identical content still logs a WatchRecord; replaying it to
        # watchers would break exactly-once across the cut)
        self._migrator = None
        self._write_gate = None
        self._watch_suppress: frozenset = frozenset()
        # XLA compilation is the engine's biggest latency cliff and the
        # one event it cannot time itself; the jax monitoring listener
        # mirrors compile events into the metrics registry (obs/profile)
        install_jax_compile_hook()
        if seed:
            self.write_relationships([WriteOp("touch", r) for r in seed])

    def enable_lookup_batching(self) -> None:
        """Fuse concurrent lookup_resources_mask calls into device
        dispatches of several subject rows (engine/batcher.py). Every
        engine starts with it on; this turns it back on after
        :meth:`disable_lookup_batching`."""
        self.disable_lookup_batching()
        self._batcher = LookupBatcher(self)

    def disable_lookup_batching(self) -> None:
        """Revert to one device dispatch per lookup. The retired batcher
        is closed: its pending batch flushes, and any racing submit that
        still holds a reference falls through to the direct engine path
        instead of queueing into a dead batcher."""
        b, self._batcher = self._batcher, None
        if b is not None:
            b.close()

    def enable_decision_cache(self, max_entries: int = 65536,
                              max_mask_bytes: int = 256 << 20) -> None:
        """Serve byte-identical repeat queries at an unchanged store
        revision from a revision-keyed LRU instead of re-dispatching, and
        coalesce concurrent identical misses into one dispatch
        (engine/decision_cache.py). Semantics are unchanged: writes bump
        the revision (new keys), expiring tuples bound every entry with
        the store's next-expiry watermark, and explicit-``now`` queries
        bypass the cache entirely."""
        self._decision_cache = DecisionCache(max_entries=max_entries,
                                             max_mask_bytes=max_mask_bytes)

    def disable_decision_cache(self) -> None:
        """Drop the cache (gauges zeroed); in-flight fills resolve but
        are no longer consulted."""
        c, self._decision_cache = self._decision_cache, None
        if c is not None:
            c.clear()

    def enable_persistence(self, data_dir: str, **kw):
        """Make the relationship store durable under ``data_dir``
        (``--data-dir``): recover whatever a previous process left there
        (newest valid snapshot + WAL tail, persistence/recovery.py), then
        journal every subsequent mutation through a write-ahead log with
        background snapshot checkpoints. Returns the
        :class:`~..persistence.Persistence` manager (its ``.recovery``
        says what was restored). Keyword args pass through to
        ``Persistence.open`` (wal_fsync, checkpoint thresholds...)."""
        from ..persistence import Persistence

        with self._lock:
            if self._persistence is not None:
                raise RuntimeError("persistence is already enabled")
            p = Persistence.open(self.store, data_dir, **kw)
            self._persistence = p
            self._compiled = None  # recovery replaced the store contents
        return p

    def close_persistence(self, final_checkpoint: bool = True) -> None:
        """Graceful shutdown of the durability layer (fsync + by default
        a final checkpoint so the next boot replays nothing)."""
        with self._lock:
            p, self._persistence = self._persistence, None
        if p is not None:
            p.close(final_checkpoint=final_checkpoint)

    @property
    def persistence(self):
        return self._persistence

    def enable_compaction(self, threshold: float = 0.75,
                          delta_capacity: Optional[int] = None):
        """Start the background overlay compactor (engine/compaction.py):
        a worker thread folds the accumulated delta tail into a fresh
        double-buffered compiled base off the write path and swaps it
        atomically at a recorded revision, and the write path sheds with
        a bounded Retry-After (:class:`~.compaction.OverlayBackpressure`)
        instead of letting overlay overflow force a synchronous full
        recompile onto the next fully-consistent read. ``threshold`` is
        the overlay-occupancy fraction that wakes the worker;
        ``delta_capacity`` resizes the overlay for graphs compiled from
        now on (``--delta-capacity``)."""
        from .compaction import Compactor

        with self._lock:
            if self._compactor is not None:
                raise RuntimeError("compaction is already enabled")
            if delta_capacity is not None:
                self._delta_capacity = max(int(delta_capacity), 64)
            self._compactor = Compactor(self, threshold)
        return self._compactor

    def close_compaction(self, drain: bool = False) -> None:
        """Stop the compactor worker (``drain=True`` folds once more
        first); writes stop shedding and overlay overflow reverts to the
        synchronous-recompile fallback."""
        with self._lock:
            c, self._compactor = self._compactor, None
        if c is not None:
            c.close(drain=drain)

    @property
    def compactor(self):
        return self._compactor

    # -- write path ---------------------------------------------------------

    def _validate_caveat(self, rel: Relationship) -> None:
        validate_caveat(self.schema, rel)

    def _validate(self, rel: Relationship) -> None:
        validate_relationship(self.schema, rel)

    def write_relationships(self, ops: list[WriteOp],
                            preconditions: list[Precondition] = (),
                            *, _headroom: bool = True) -> int:
        if self.validate_writes:
            for op in ops:
                self._validate(op.rel)
        if _headroom:
            self._write_headroom(len(ops))
        gate = self._write_gate
        if gate is not None:
            gate.enter()
        try:
            rev = self.store.write(list(ops), list(preconditions))
            self._advance_incremental()
        finally:
            if gate is not None:
                gate.exit()
        return rev

    def delete_relationships(self, f: RelationshipFilter,
                             preconditions: list[Precondition] = (),
                             *, _headroom: bool = True) -> int:
        # filter cardinality is unknown pre-scan: charge one record's
        # headroom (deletes mostly reuse overlay slots / the dead ledger;
        # a huge filter delete overflowing the ledger still falls back to
        # a counted full recompile, it just isn't shed preemptively)
        if _headroom:
            self._write_headroom(1)
        gate = self._write_gate
        if gate is not None:
            gate.enter()
        try:
            n = self.store.delete_by_filter(f, list(preconditions))
            self._advance_incremental()
        finally:
            if gate is not None:
                gate.exit()
        return n

    def _write_headroom(self, n_records: int) -> None:
        """Back-pressure gate run BEFORE any store mutation: when the
        compactor is enabled and the current overlay cannot absorb the
        write, shed with :class:`~.compaction.OverlayBackpressure`
        (bounded Retry-After) instead of letting the next read pay a
        synchronous full recompile. A shed write leaves no trace —
        nothing journaled, replicated, or applied — so retrying is always
        safe."""
        c = self._compactor
        if c is not None:
            c.check_headroom(self._compiled, n_records)

    def _advance_incremental(self) -> None:
        """Eagerly fold the write just applied into the compiled graph —
        an O(write) overlay append — so the write path itself keeps the
        graph current and the next fully-consistent read dispatches
        immediately. Never compiles: when the incremental path declines
        (layout growth, stratification inversion, overflow), the decline
        is counted and the read path's fallback recompile — or the
        background compactor, when enabled — picks it up."""
        with self._lock:
            cur = self._compiled
            if cur is None or cur.revision == self.store.revision:
                return
            inc = self._try_incremental(cur)
            if inc is not None:
                self._compiled = inc
                self._publish_graph_gauges(inc)
                c = self._compactor
                if c is not None:
                    c.notify(inc)
            else:
                # remember the exact (base, store) revision pair that
                # declined: the read path retrying the same suffix would
                # re-run the whole planning scan, fail identically, and
                # double-count the fallback reason
                self._incremental_declined = (cur.revision,
                                              self.store.revision)
                if self._compactor is not None:
                    # the overlay could not express this write: fold in
                    # the background so the serving path meets a fresh
                    # base instead of recompiling synchronously
                    self._compactor.request()

    def read_relationships(self, f: RelationshipFilter) -> Iterator[Relationship]:
        return self.store.read(f)

    def bulk_load(self, rels_cols: dict) -> int:
        if self.validate_writes and rels_cols.get("caveat") is not None:
            # validate the DISTINCT (caveat, context) pairs before any
            # store mutation: an undeclared name or a type-mismatched
            # context interned here would not fail until the next
            # compile_graph — bricking every subsequent query instead
            # of rejecting one bad load (the write path rejects the
            # same row cleanly via _validate_caveat)
            from ..models.tuples import canonical_context

            names = np.asarray(rels_cols["caveat"], dtype=str)
            ctx_col = rels_cols.get("caveat_context")
            ctxs = (np.asarray(ctx_col, dtype=str)
                    if ctx_col is not None
                    else np.full(len(names), "", dtype=str))
            seen: set = set()
            for nm, cx in zip(names.tolist(), ctxs.tolist()):
                if not nm or (nm, cx) in seen:
                    continue
                seen.add((nm, cx))
                self._validate_caveat(Relationship(
                    "", "", "", "", "", None, None, nm,
                    canonical_context(cx)))
        return self.store.bulk_load(rels_cols)

    # -- query path ---------------------------------------------------------

    def _objects_by_name(self) -> dict:
        # snapshot under the store lock: writers intern new types into
        # store.objects and a concurrent iteration would race
        with self.store._lock:
            return {
                self.store.types.string(tid): it
                for tid, it in self.store.objects.items()
            }

    def _publish_graph_gauges(self, cg: CompiledGraph) -> None:
        # TrieJax-style kernel accounting: the compiled graph's shape
        # gauges let a scrape correlate latency with graph scale (CSR
        # nnz = adjacency edges, M = slot space). Called only when the
        # graph CHANGED — compiled() itself is per-dispatch hot path
        metrics.gauge("engine_csr_nnz").set(cg.n_edges)
        # the edges no dense block took (unpadded): what the sparse
        # gather/segment path walks on every hop
        metrics.gauge("engine_residual_edges").set(
            cg.n_edges if cg.res_idx is None else len(cg.res_idx))
        metrics.gauge("engine_graph_slots").set(cg.M)
        # what the fixpoint's loop re-walks on every trip and how many
        # slot ranges iterate with it (those on a cycle or between two),
        # and what feeds them: walked once, before the loop
        metrics.gauge("engine_core_edges").set(cg.core_edges())
        metrics.gauge("engine_core_ranges").set(cg.core_ranges())
        metrics.gauge("engine_feeder_edges").set(cg.feeder_edges())
        metrics.gauge("engine_feeder_ranges").set(cg.feeder_ranges())
        # of the residual: the edges out of a subject's own range, which
        # a dispatch finds from its seeds, the padded rest that every
        # dispatch walks (and how much of that is padding), and how many
        # entries a seed looks up
        metrics.gauge("engine_seed_edges").set(cg.seed_edges())
        metrics.gauge("engine_walked_edges").set(cg.walked_edges())
        metrics.gauge("engine_walked_padding_edges").set(
            cg.walked_padding_edges())
        metrics.gauge("engine_seed_fanout").set(cg.seed_fanout())
        metrics.gauge("engine_delta_occupancy").set(cg.n_delta)
        if cg.tier is not None:
            cg.tier.publish_gauges()

    def compiled(self) -> CompiledGraph:
        """Fully-consistent snapshot: a stale compiled graph is brought
        current by an O(delta) incremental update (small writes — the
        dual-write hot path) or a full recompile (bulk loads, schema-shaped
        changes, oversized deltas)."""
        with self._lock:
            cur = self._compiled
            if cur is not None and cur.revision != self.store.revision \
                    and (cur.revision, self.store.revision) \
                    != self._incremental_declined:
                inc = self._try_incremental(cur)
                if inc is not None:
                    self._compiled = inc
                    self._publish_graph_gauges(inc)
                    c = self._compactor
                    if c is not None:
                        c.notify(inc)
                    return inc
            if self._compiled is None or \
               self._compiled.revision != self.store.revision:
                with tracer.stage("graph_compile"):
                    self._compiled = self._compile_fresh()
                self._publish_graph_gauges(self._compiled)
            return self._compiled

    def _compile_fresh(self) -> CompiledGraph:
        """One full compile from the current store snapshot — shared by
        the serving-path fallback (under the engine lock) and the
        background compactor's fold (deliberately OFF the lock: the old
        base keeps serving while the fold runs)."""
        t0 = time.perf_counter()
        cg = compile_graph(self.schema, self.store.snapshot(self.schema),
                           delta_capacity=self._delta_capacity)
        if self._tier_budget:
            # each compiled base gets a fresh TierStore: residency and
            # overlay pins start clean, which is exactly the "pinned
            # until folded" rule
            cg.enable_tiering(self._tier_budget,
                              spill_dir=self._tier_spill_dir)
        metrics.counter("engine_graph_compiles_total").inc()
        metrics.histogram("engine_graph_compile_seconds").observe(
            time.perf_counter() - t0)
        return cg

    def _replay_onto(self, fresh: CompiledGraph
                     ) -> Optional[CompiledGraph]:
        """Bring a freshly-compiled base current with the watch-log
        records that landed after its snapshot was cut (the compactor's
        catch-up replay, run under the engine lock so no further write
        can race the swap). Returns the advanced graph, ``fresh`` itself
        when nothing landed, or ``None`` when the suffix cannot be
        replayed incrementally (trimmed history, bulk load, overflow) —
        the caller re-folds from a newer snapshot."""
        st = self.store
        with st._lock:
            if fresh.revision < st.unlogged_revision:
                return None
            try:
                records = st.watch_since(fresh.revision)
            except StoreError:
                return None
            rev = st.revision
        if not records:
            return fresh
        if len(records) > MAX_DELTA_RECORDS:
            return None
        from .store import OP_DELETE

        delta = [(r.op == OP_DELETE, r.rel) for r in records]
        return incremental_update(fresh, delta, rev, st)

    def _try_incremental(self, cur: CompiledGraph) -> Optional[CompiledGraph]:
        from ..utils.features import features

        if not features.enabled("IncrementalGraphUpdates"):
            return None
        st = self.store
        with st._lock:
            if cur.revision < st.unlogged_revision:
                # bulk-loaded/restored changes aren't in the log
                _fallback("unlogged")
                return None
            try:
                records = st.watch_since(cur.revision)
            except StoreError:
                _fallback("history-trimmed")
                return None
            rev = st.revision
        if len(records) > MAX_DELTA_RECORDS:
            _fallback("overflow")
            return None
        t0 = time.perf_counter()
        from .store import OP_DELETE

        delta = [(r.op == OP_DELETE, r.rel) for r in records]
        new = incremental_update(cur, delta, rev, st)
        if new is not None:
            metrics.counter("engine_graph_incremental_updates_total").inc()
            metrics.histogram("engine_graph_incremental_seconds").observe(
                time.perf_counter() - t0)
        return new

    def check(self, item: CheckItem, now: Optional[float] = None,
              context: Optional[dict] = None) -> bool:
        return self.check_bulk([item], now=now, context=context)[0]

    def _cache_deadline(self, cg: CompiledGraph, now0: float,
                        context: Optional[dict]) -> float:
        """Validity horizon for a decision-cache entry filled at
        ``now0``: the store's next expiration boundary joined with the
        caveat table's next verdict-flip instant (time-window caveats
        revoke/grant without a write, exactly like tuple expiry)."""
        deadline = self.store.next_expiry(now0)
        cav = cg.caveats
        if cav is not None and cav.metas:
            deadline = min(deadline, cav.next_time_bound(
                now0, cav.request_ts(context)))
        return deadline

    def watch_gate(self, resource_type: str, name: str
                   ) -> tuple[frozenset, bool]:
        """(relevant types, reachable expiration) for watch streams:
        the types whose writes can affect ``resource_type#name``
        (models/schema.py watch_relevance), and whether a relation the
        watched permission can reach allows expiring tuples — watches skip
        allowed-set recomputes on unrelated write traffic, and only tick
        periodically for expiry when the WATCHED permission (not just the
        schema somewhere) can actually lose grants to the clock."""
        from ..models.schema import watch_relevance

        return watch_relevance(self.schema, resource_type, name)

    def check_bulk(self, items: list[CheckItem],
                   now: Optional[float] = None,
                   context: Optional[dict] = None) -> list[bool]:
        """CheckBulkPermissions: evaluate all items in one device pass,
        batching distinct subjects along B (reference check.go:22-48 issues
        one bulk RPC per request; here the whole bulk is one fixpoint).
        ``context`` is the request's caveat context (client IP, caller
        attributes...) gating conditional grants; the dispatch clock is
        auto-injected as the ``now`` caveat parameter."""
        return self.check_bulk_async(items, now=now,
                                     context=context).result()

    def try_cached_check(self, items: list[CheckItem],
                         context: Optional[dict] = None
                         ) -> Optional[list[bool]]:
        """Non-blocking decision-cache probe: the full verdict list when
        EVERY item is a hit at the current revision, else ``None``
        (a partial answer is useless to the authz chain — it would
        dispatch anyway). Never compiles, never dispatches, never blocks
        beyond a shard lock: callers on an event loop can probe before
        paying the ``asyncio.to_thread`` handoff
        (authz/middleware.py)."""
        cache = self._decision_cache
        if cache is None:
            return None
        if not items:
            return []
        rev = self.store.revision
        # digest-free keys for caveat-less graphs, parameter-scoped
        # digests otherwise (see check_bulk_async) — but ONLY when the
        # current compiled graph provably matches this revision; when
        # unsure, digesting the full context is merely a cache miss,
        # never a wrong answer
        cg = self._compiled
        if cg is not None and cg.revision == rev:
            digest = (context_digest(
                cg.caveats.relevant_context(context))
                if cg.caveats is not None and cg.caveats.metas
                else None)
        else:
            digest = context_digest(context)
        now = time.time()
        out: list[bool] = []
        for it in items:
            v = cache.get(check_key(rev, it, digest), now, record=False)
            if v is MISS:
                return None
            out.append(v)
        # counted only once the WHOLE probe served (partial probes fall
        # through to check_bulk_async, which records its own hits/misses)
        cache.note_hits("check", len(out))
        return out

    def _backend(self, cg: CompiledGraph):
        """The query executor for a compiled graph: the graph itself
        (single device) or a mesh-pinned ShardedGraph, rebuilt whenever the
        compiled graph changes revision. Both expose the same
        ``query_async(seeds, q_slots, q_batch, now)`` surface."""
        if self.mesh is None:
            return cg
        t = cg.tier
        if t is not None and t.total_bytes() > t.budget_bytes:
            # beyond-budget tiered graph: the mesh backend pins every
            # block resident (parallel/sharded.py streams nothing), so
            # a graph that cannot fit routes through the single-chip
            # demand-streaming path instead — counted so a mesh
            # deployment sees why its mesh idles on oversized groups
            metrics.counter("engine_tier_mesh_fallback_total").inc()
            return cg
        from ..parallel.sharded import ShardedGraph

        reason = ShardedGraph.unsupported_reason(cg)
        if reason is not None:
            # caveats evaluate ON the mesh now (the VM runs inside the
            # shard_map body against replicated instance tables); only
            # genuinely unsupported shapes — caveated graphs without
            # per-edge caveat rows, i.e. hand-built unstratified
            # layouts — still route to the single-device path, counted
            # so a mesh deployment sees why its mesh idles.
            metrics.counter("engine_caveat_mesh_fallback_total").inc()
            return cg
        with self._lock:
            sg = self._sharded
            if sg is None or sg.cg is not cg:
                t0 = time.perf_counter()
                if sg is None:
                    sg = ShardedGraph(cg, self.mesh)
                    metrics.counter("engine_sharded_builds_total").inc()
                else:
                    # incremental revision: reuses the jitted shard_map +
                    # resident base shards, applies only the delta
                    sg = sg.updated(cg)
                    metrics.counter("engine_sharded_updates_total").inc()
                metrics.histogram("engine_sharded_build_seconds").observe(
                    time.perf_counter() - t0)
                self._sharded = sg
            return sg

    # bulk checks dispatch in chunks this size so host encode of the next
    # chunk overlaps device execution of the previous one
    CHECK_PIPELINE_CHUNK = 16384

    def _encode_checks(self, cg, objs, items):
        """Single-pass check-batch encode with per-(type, permission) and
        per-type caches inlined, instead of two encode_* calls per item —
        the two calls' attribute/dict traffic was over half the bulk-check
        wall time at 65k items on a TPU chip (106ms of 176ms). Semantics
        identical to ``encode_target`` / ``encode_subject``; the columnar
        numpy alternative measured SLOWER (string-array materialization
        dominates), so this stays a lean Python loop."""
        from ..ops.reachability import VOID_IDX

        n = len(items)
        M = cg.M
        offset_of = cg.offset_of
        type_sizes = cg.type_sizes
        q_slots = np.empty(n, dtype=np.int32)
        q_batch = np.empty(n, dtype=np.int32)
        tp_off: dict[tuple, int] = {}  # (type, permission) -> offset | -1
        ti: dict[str, tuple] = {}  # type -> (id map | None, type size)
        subjects: dict[tuple, int] = {}
        seed_rows: list[tuple[int, int]] = []
        for i, it in enumerate(items):
            t = it.resource_type
            key = (t, it.permission)
            off = tp_off.get(key)
            if off is None:
                o = offset_of(t, it.permission)
                off = -1 if o is None else o
                tp_off[key] = off
            if off < 0:
                q_slots[i] = M
            else:
                ent = ti.get(t)
                if ent is None:
                    interner = objs.get(t)
                    ent = (interner.id_map() if interner is not None
                           else None, type_sizes.get(t, 0))
                    ti[t] = ent
                to_id, size = ent
                if to_id is None:
                    q_slots[i] = off + VOID_IDX
                else:
                    oi = to_id.get(it.resource_id)
                    q_slots[i] = off + (
                        oi if oi is not None and oi < size else VOID_IDX)
            skey = (it.subject_type, it.subject_id, it.subject_relation)
            row = subjects.get(skey)
            if row is None:
                row = len(seed_rows)
                subjects[skey] = row
                seed_rows.append(
                    cg.encode_subject(it.subject_type, it.subject_id,
                                      it.subject_relation, objs)
                )
            q_batch[i] = row
        return np.asarray(seed_rows, dtype=np.int32), q_slots, q_batch

    def check_bulk_async(self, items: list[CheckItem],
                         now: Optional[float] = None,
                         context: Optional[dict] = None
                         ) -> "EngineFuture":
        """Dispatch a bulk check without blocking (device→host readback
        overlaps with other in-flight queries); ``.result()`` to wait.

        With the decision cache enabled (and no explicit ``now`` — a
        pinned clock must see the store exactly as of that instant, so it
        bypasses the cache), per-item verdicts are served from the cache
        and only the miss residue dispatches; the answer list reassembles
        in the caller's order. Verdicts — positive and negative — are
        cached keyed by the snapshot revision (plus the request-context
        digest when a caveat context rides the call) with the store's
        next-expiry watermark ∧ the caveat table's next verdict flip as
        deadline."""
        cache = self._decision_cache
        if cache is None or now is not None or not items:
            return self._check_bulk_dispatch(items, now, context=context)
        # pin ONE compiled snapshot for the whole bulk — hits are keyed
        # at its revision and the miss residue dispatches against the
        # same graph, so the answer list reflects a single revision even
        # when a write lands mid-call (the uncached path's one-snapshot
        # guarantee)
        cg = self.compiled()
        now0 = time.time()
        # the digest partitions cache keys ONLY when the graph actually
        # carries caveat instances, and ONLY over the context keys the
        # compiled caveats declare — an uncaveated graph's verdicts
        # cannot depend on request context at all, and digesting
        # undeclared fields (the middleware's per-request name/verb/...)
        # would fragment the repeat-traffic working set for nothing
        digest = (context_digest(cg.caveats.relevant_context(context))
                  if cg.caveats is not None and cg.caveats.metas
                  else None)
        # stage ``bulk_cache``: the cache's two passes over the bulk (the
        # keys and one probe pass before the dispatch, one put pass after
        # it; each visits a shard once: decision_cache.get_many), a span
        # each and ONE observation a call, of their sum; each pass runs
        # on one thread, whose CPU seconds go to the counter (for the
        # one call in CPU_EVERY that reads them: obs/trace.py)
        spent = metrics.histogram("engine_bulk_cache_seconds")
        cpu = metrics.counter("engine_bulk_cache_cpu_seconds_total")
        cpu_weight = tracer.cpu_weight()
        t0 = time.perf_counter()
        c0 = time.thread_time() if cpu_weight else 0.0
        with tracer.span("bulk_cache"):
            # the tuple decision_cache.check_key builds, inline
            rev = cg.revision
            tail = () if digest is None else (digest,)
            keys = [("check", rev, it.resource_type, it.resource_id,
                     it.permission, it.subject_type, it.subject_id,
                     it.subject_relation) + tail for it in items]
            out, missed = cache.get_many(keys, now0)
        if cpu_weight:
            cpu.inc((time.thread_time() - c0) * cpu_weight)
        probe_s = time.perf_counter() - t0
        if not missed:
            spent.observe(probe_s)
            return EngineFuture(None, lambda _: list(out))
        if sum(len(positions) for _, positions in missed) == len(items):
            miss_idx = None  # every item missed: the bulk goes on as it is
            inner = self._check_bulk_dispatch(items, now0, cg=cg,
                                              context=context)
        else:
            miss_idx = [i for i, v in enumerate(out) if v is MISS]
            inner = self._check_bulk_dispatch(
                [items[i] for i in miss_idx], now0, cg=cg, context=context)

        def fin(_):
            got = inner.result()
            t1 = time.perf_counter()
            c1 = time.thread_time() if cpu_weight else 0.0
            with tracer.span("bulk_cache"):
                deadline = self._cache_deadline(cg, now0, context)
                if miss_idx is None:
                    verdicts = got
                else:
                    for i, v in zip(miss_idx, got):
                        out[i] = v
                    verdicts = list(out)
                cache.put_many(keys, verdicts, deadline, now0, missed)
            if cpu_weight:
                cpu.inc((time.thread_time() - c1) * cpu_weight)
            spent.observe(probe_s + time.perf_counter() - t1)
            return verdicts

        return EngineFuture(None, fin, iters=inner.iterations)

    def _check_bulk_dispatch(self, items: list[CheckItem],
                             now: Optional[float] = None,
                             cg: Optional[CompiledGraph] = None,
                             context: Optional[dict] = None
                             ) -> "EngineFuture":
        """The raw (cache-less) bulk check: one chunked device pass.
        ``cg`` pins an already-obtained snapshot (the cached path passes
        the graph its hits were keyed against)."""
        if not items:
            return EngineFuture(None, lambda _: [])
        if cg is None:
            cg = self.compiled()
        objs = self._objects_by_name()
        t0 = time.perf_counter()
        backend = self._backend(cg)
        n = len(items)
        chunk = self.CHECK_PIPELINE_CHUNK
        if now is None:
            # one clock for the whole bulk call: every chunk's expiration
            # mask must see the same instant (one CheckBulkPermissions =
            # one consistency snapshot, reference check.go:41-48)
            now = time.time()
        # request caveat context encodes ONCE for the whole logical call
        # (chunks share it; a per-chunk encode would also multi-count
        # the request-list-overflow counter by the chunk count)
        cav_req = None
        cavs = cg.caveats
        if cavs is not None and cavs.metas:
            cav_req, _ = cavs.encode_request(context, now)
        # chunked pipeline: dispatches are async, so encoding chunk k+1 on
        # the host overlaps chunk k's device execution and readback —
        # wall ≈ one_chunk_encode + transport + device, not encode + both
        futs = []
        distinct = 0
        for s in range(0, n, chunk):
            with tracer.stage("engine_encode",
                              metrics.histogram("engine_encode_seconds"),
                              metrics.counter(
                                  "engine_encode_cpu_seconds_total")):
                seeds, q_slots, q_batch = self._encode_checks(
                    cg, objs, items[s:s + chunk])
            futs.append(backend.query_async(seeds, q_slots, q_batch,
                                            now=now, context=context,
                                            cav_req=cav_req))
            _count_dispatch_rows(len(seeds))
            # what the chunk asks that it has not asked already: items
            # that name one (slot, subject row) are one question
            distinct += len(np.unique(
                q_batch.astype(np.int64) << 32 | q_slots))
        metrics.counter("engine_checks_total").inc(n)
        metrics.counter("engine_checks_distinct_total").inc(distinct)

        def iters():
            return max(f.iterations() for f in futs)

        def fin(_):
            with tracer.stage("device_wait", metrics.histogram(
                    "engine_device_wait_seconds")) as wait:
                out = [bool(x) for f in futs for x in f.result()]
            # engine_check_seconds covers the WHOLE bulk call including
            # host-side encode (what a caller experiences), not just
            # dispatch+device+readback as before the chunked pipeline
            metrics.histogram("engine_check_seconds").observe(
                time.perf_counter() - t0)
            it = iters()
            wait.set("fixpoint_iters", it)
            wait.set("core_edges", cg.core_edges())
            metrics.histogram("engine_fixpoint_iterations").observe(it)
            # caveat instances that resolved missing-context this call:
            # denied fail-closed, and LOUD — this counter replaces the
            # old silent load-time exclusion of conditional grants.
            # Semantics: DISTINCT instances lacking context per logical
            # call (every chunk shares one graph + one context, so the
            # per-chunk counts are identical — max, not sum), counted
            # whether or not the queried slots depended on them (the
            # mask evaluates once for the whole graph per dispatch).
            missing = max((getattr(f, "caveats_missing", lambda: 0)()
                           for f in futs), default=0)
            if missing:
                metrics.counter(
                    "engine_caveat_denied_missing_context_total").inc(
                    missing)
            return out

        return EngineFuture(None, fin, iters=iters)

    def lookup_resources(self, resource_type: str, permission: str,
                         subject_type: str, subject_id: str,
                         subject_relation: Optional[str] = None,
                         now: Optional[float] = None,
                         context: Optional[dict] = None) -> list[str]:
        """LookupResources: ids of ``resource_type`` on which the subject has
        ``permission`` (reference lookups.go:49-65 streams these; we return
        the whole set from one device pass)."""
        mask, interner = self.lookup_resources_mask(
            resource_type, permission, subject_type, subject_id,
            subject_relation, now=now, context=context)
        with tracer.stage("mask_to_ids",
                          metrics.histogram("engine_mask_to_ids_seconds"),
                          metrics.counter(
                              "engine_mask_to_ids_cpu_seconds_total")):
            return mask_to_ids(mask, interner)

    def lookup_subjects(self, resource_type: str, resource_id: str,
                        permission: str, subject_type: str,
                        subject_relation: Optional[str] = None,
                        now: Optional[float] = None,
                        context: Optional[dict] = None,
                        chunk: int = 4096) -> list[str]:
        """LookupSubjects: which subjects of ``subject_type`` hold
        ``permission`` on one resource — the reverse of
        :meth:`lookup_resources` (reference LookupSubjects RPC; the
        reconcile/debug shape "who can see this namespace?").

        Evaluated as bulk checks over the store's KNOWN subject universe
        (every distinct ``subject_type`` subject id appearing in any
        relationship): the forward fixpoint batches subjects along B
        already, so a reverse walk buys nothing a chunked bulk check
        doesn't, and checks honor wildcard grants — a ``user:*`` tuple
        makes every known subject pass. Wildcards are reported as the
        checks resolve them (concrete ids), never as a literal ``'*'``
        row. Sorted for determinism."""
        from .store import RelationshipFilter

        cands = sorted({
            rel.subject_id
            for rel in self.read_relationships(
                RelationshipFilter(subject_type=subject_type))
            if rel.subject_id != "*"
        })
        out: list[str] = []
        for i in range(0, len(cands), chunk):
            part = cands[i:i + chunk]
            got = self.check_bulk(
                [CheckItem(resource_type, resource_id, permission,
                           subject_type, sid, subject_relation)
                 for sid in part], now=now, context=context)
            out.extend(sid for sid, ok in zip(part, got) if ok)
        metrics.counter("engine_lookup_subjects_total").inc()
        return out

    def lookup_resources_mask(self, resource_type: str, permission: str,
                              subject_type: str, subject_id: str,
                              subject_relation: Optional[str] = None,
                              now: Optional[float] = None,
                              context: Optional[dict] = None):
        """Vectorized variant for the list-filter hot path: returns
        (bool mask over the type's object index space, per-type interner).
        Callers with a list of candidate names map name->index and test the
        mask directly — no per-object RPC or string materialization."""
        return self.lookup_resources_mask_async(
            resource_type, permission, subject_type, subject_id,
            subject_relation, now=now, context=context,
        ).result()

    def lookup_resources_mask_async(self, resource_type: str, permission: str,
                                    subject_type: str, subject_id: str,
                                    subject_relation: Optional[str] = None,
                                    now: Optional[float] = None,
                                    context: Optional[dict] = None):
        """Non-blocking mask lookup; ``.result()`` -> (mask, interner).
        Concurrent list requests dispatch back-to-back and overlap their
        readbacks — the reference's goroutine-per-prefilter overlap
        (pkg/authz/responsefilterer.go:165-183) without the goroutines.
        With batching enabled, concurrent calls fuse into one dispatch.

        The decision cache (when enabled, now-less queries only) sits in
        front of everything: repeats at an unchanged revision are served
        host-side with zero device work, and concurrent identical misses
        singleflight — one caller dispatches (through the batcher when
        enabled, which therefore only ever sees true misses), the rest
        piggyback on its future. Cached masks are copied on read so no
        caller can mutate the cache's array."""
        cache = self._decision_cache
        if cache is None or now is not None:
            return self._lookup_submit(resource_type, permission,
                                       subject_type, subject_id,
                                       subject_relation, now, context)
        cg = self.compiled()
        key = lookup_key(cg.revision, resource_type, permission,
                         subject_type, subject_id, subject_relation,
                         context_digest(cg.caveats.relevant_context(
                             context))
                         if cg.caveats is not None and cg.caveats.metas
                         else None)
        now0 = time.time()
        hit = cache.get(key, now0)
        if hit is not MISS:
            mask, interner = hit
            return EngineFuture(None, lambda _: (
                None if mask is None else mask.copy(), interner))
        leader, flight = cache.flight(key, now0)
        if not leader:

            def fin_follower(_):
                mask, interner = flight.result()
                return (None if mask is None else mask.copy(), interner)

            return EngineFuture(None, fin_follower)
        try:
            inner = self._lookup_submit(resource_type, permission,
                                        subject_type, subject_id,
                                        subject_relation, None, context)
        except BaseException as e:  # dispatch died before a future existed
            flight.abort(e)
            cache.release(key, flight)
            raise

        def finish():
            try:
                value = inner.result()
            except BaseException:
                cache.release(key, flight)  # errors are never cached
                raise
            mask, interner = value
            deadline = self._cache_deadline(cg, now0, context)
            flight.deadline = deadline
            cache.put(key, (mask, interner), deadline,
                      0 if mask is None else int(mask.nbytes), now0)
            cache.release(key, flight)
            return value

        flight.launch(finish)

        def fin_leader(_):
            mask, interner = flight.result()
            return (None if mask is None else mask.copy(), interner)

        return EngineFuture(None, fin_leader,
                            iters=getattr(inner, "iterations", None))

    def _lookup_submit(self, resource_type: str, permission: str,
                       subject_type: str, subject_id: str,
                       subject_relation: Optional[str],
                       now: Optional[float],
                       context: Optional[dict] = None):
        """Route one true-miss lookup: through the batcher, which fuses
        it with the lookups waiting beside it or sends it alone; direct
        for what cannot share a dispatch."""
        cg = self._compiled
        # a request context only matters when the graph actually holds
        # caveat instances: a fused batch evaluates ONE caveat mask per
        # dispatch, so rows with different contexts cannot share it —
        # but contexted lookups against a provably caveat-less current
        # graph still fuse (the middleware sends context on EVERY
        # request; bypassing unconditionally would disable batching)
        ctx_matters = bool(context) and not (
            cg is not None and cg.revision == self.store.revision
            and (cg.caveats is None or not cg.caveats.metas))
        batcher = self._batcher
        if batcher is not None and now is None and not ctx_matters \
                and self.mesh is None:
            # explicit-now callers bypass the batcher: a fused batch runs
            # at one dispatch-time clock, which is only equivalent to the
            # unbatched path for now-less queries. A mesh engine's
            # lookups go one a dispatch, as they always have; so do those
            # of a graph whose fused program does not pay, which the
            # batcher hands straight back (``fused_rows``).
            return batcher.submit(
                resource_type, permission, subject_type, subject_id,
                subject_relation)
        return self._lookup_direct(resource_type, permission, subject_type,
                                   subject_id, subject_relation, now,
                                   context)

    def _lookup_direct(self, resource_type: str, permission: str,
                       subject_type: str, subject_id: str,
                       subject_relation: Optional[str],
                       now: Optional[float],
                       context: Optional[dict] = None,
                       enqueued: Optional[list] = None):
        """One lookup, one dispatch of one row. ``enqueued``: where the
        batcher collects the dispatches it has on the device."""
        cg = self.compiled()
        objs = self._objects_by_name()
        off = cg.offset_of(resource_type, permission)
        n = cg.type_sizes.get(resource_type)
        interner = objs.get(resource_type)
        if off is None or interner is None:
            # trivial lookups (unknown type/permission) count too: tests
            # read engine_lookups_total as "lookups the engine answered",
            # cache hits excluded
            metrics.counter("engine_lookups_total").inc()
            return EngineFuture(None, lambda _: (None, None))
        with tracer.stage("engine_encode",
                          metrics.histogram("engine_encode_seconds"),
                          metrics.counter("engine_encode_cpu_seconds_total")):
            seeds = np.asarray(
                [cg.encode_subject(subject_type, subject_id,
                                   subject_relation, objs)],
                dtype=np.int32,
            )
            qk = (off, n)
            ent = self._q_host.get(qk)
            if ent is None:
                if len(self._q_host) >= 64:
                    try:
                        # pop-with-default: concurrent lookups may race
                        # the same oldest key (no lock on this path by
                        # design); RuntimeError = the dict mutated between
                        # iter() and next() — skip this eviction, the
                        # cache is bounded by whoever wins
                        self._q_host.pop(next(iter(self._q_host)), None)
                    except (StopIteration, RuntimeError):
                        pass
                ent = (off + np.arange(n, dtype=np.int32),
                       np.zeros(n, dtype=np.int32))
                self._q_host[qk] = ent
            q_slots, q_batch = ent
        t0 = time.perf_counter()
        # the query arrays are a pure function of (type, permission) slot
        # layout: cache their device copies across queries (the ~0.5MB
        # upload per 100k-object lookup is otherwise paid on every call)
        fut = self._backend(cg).query_async(
            seeds, q_slots, q_batch, now=now,
            q_cache_key=("lookup", off, n), q_contiguous=True,
            context=context)
        if enqueued is not None:
            enqueued.append(fut)
        metrics.counter("engine_lookups_total").inc()
        _count_dispatch_rows(len(seeds))

        def fin(_):
            with tracer.stage("device_wait", metrics.histogram(
                    "engine_device_wait_seconds")) as wait:
                out = fut.result()
            metrics.histogram("engine_lookup_seconds").observe(
                time.perf_counter() - t0)
            it = fut.iterations()
            wait.set("fixpoint_iters", it)
            wait.set("core_edges", cg.core_edges())
            metrics.histogram("engine_fixpoint_iterations").observe(it)
            missing = getattr(fut, "caveats_missing", lambda: 0)()
            if missing:
                metrics.counter(
                    "engine_caveat_denied_missing_context_total").inc(
                    missing)
            # QueryFuture.result() already materialized a fresh host
            # array; only copy again if it came back read-only
            m = np.asarray(out)
            if not m.flags.writeable:
                m = m.copy()
            m = mask_pseudo_objects(m)
            # final-frontier occupancy: how much of the queried slot
            # window the reachable set filled (TrieJax-style frontier
            # accounting, host-side off the readback — no device cost)
            occ = int(m.sum())
            metrics.histogram(
                "engine_frontier_occupancy",
                buckets=(0, 1, 8, 64, 512, 4096, 32768, 262144, 2**21),
            ).observe(occ)
            return m, interner

        return EngineFuture(None, fin, iters=fut.iterations)

    # -- durability ---------------------------------------------------------

    def save_snapshot(self, path: str) -> None:
        """Persist the relationship store (compacted, atomic) — the graph
        analog of the reference's durable state; a restored engine skips
        the bulk re-load entirely (51s at the 10M-relationship scale)."""
        self.store.save(path)

    def load_snapshot(self, path: str) -> None:
        with self._lock:
            if self._persistence is not None:
                # a file restore bypasses the journal: the WAL would
                # replay over the wrong lineage on the next boot
                raise StoreError(
                    "load_snapshot is incompatible with an enabled "
                    "persistence data dir (recovery owns restores)")
            self.store.load(path)
            self._compiled = None

    def load_snapshot_if_exists(self, path: Optional[str]) -> bool:
        """Boot-time restore shared by every entry point (proxy options,
        engine host CLI): load when the file exists, report whether it
        did."""
        import os

        if not path or not os.path.exists(path):
            return False
        self.load_snapshot(path)
        return True

    # -- watch --------------------------------------------------------------

    @property
    def revision(self) -> int:
        return self.store.revision

    def watch_since(self, revision: int) -> list[WatchEvent]:
        sup = self._watch_suppress
        return [
            WatchEvent(r.revision, "touch" if r.op == 2 else "delete", r.rel)
            for r in self.store.watch_since(revision)
            if r.revision not in sup
        ]

    def wait_events(self, revision: int, timeout: float) -> list[WatchEvent]:
        """Block until events past ``revision`` land (or ``timeout`` — then
        ``[]``). The push-latency form of :meth:`watch_since`: the watch
        hub parks ONE thread here per engine instead of every watcher
        polling on an interval. Migration-backfill echo revisions are
        filtered here too (an empty list after a suppressed-only batch
        just looks like a timeout to the hub, which re-parks)."""
        sup = self._watch_suppress
        return [
            WatchEvent(r.revision, "touch" if r.op == 2 else "delete", r.rel)
            for r in self.store.wait_since(revision, timeout)
            if r.revision not in sup
        ]

    # -- live schema migration (migration/migrator.py) -----------------------

    def begin_schema_migration(self, schema_text: str,
                               record_path: Optional[str] = None,
                               wait: bool = False, **cfg) -> dict:
        """Start a zero-downtime migration of this engine to the schema
        in ``schema_text``: diff-classify, dual-compile, journaled
        backfill, and an atomic revision-preserving cutover. Returns the
        initial status dict; ``wait=True`` blocks until done/failed.
        Raises :class:`~...models.schema.IncompatibleSchemaChange` (a
        ``SchemaError``) before any state changes when the transition is
        not performable online."""
        from ..migration import SchemaMigrator

        with self._lock:
            if self._migrator is not None and self._migrator.active:
                raise StoreError("a schema migration is already running")
            prev = self._migrator
            m = SchemaMigrator(self, schema_text,
                               record_path=record_path
                               or self._default_migration_record(), **cfg)
            self._migrator = m
        try:
            m.start()
        except BaseException:
            # a refused plan (e.g. incompatible diff) must not leave a
            # never-started migrator installed as "active" — that would
            # refuse every future begin
            with self._lock:
                if self._migrator is m:
                    self._migrator = prev
            raise
        if wait:
            m.join()
        return m.status()

    def _default_migration_record(self) -> Optional[str]:
        """Persist the migration phase machine beside the WAL when the
        engine is durable; memory-only engines migrate without a record
        (a crash loses the store anyway, so there is nothing to replay
        the phases against)."""
        p = self._persistence
        d = getattr(p, "data_dir", None) if p is not None else None
        if d is None:
            return None
        import os

        return os.path.join(d, "migration.json")

    def migration_status(self) -> Optional[dict]:
        """Phase/lag status of the running (or last) migration, or
        ``None`` when this engine never migrated — the /readyz and
        remote-op probe surface."""
        m = self._migrator
        return None if m is None else m.status()

    def abort_schema_migration(self) -> dict:
        """Abort the running migration (refused once any cut happened —
        the same one-way rule as the rebalancer's transition)."""
        m = self._migrator
        if m is None:
            raise StoreError("no schema migration to abort")
        return m.abort()

    def cut_schema_migration(self, wait: bool = True) -> dict:
        """Release a migration holding at the dual phase into its
        cutover (the planner's coordinated-cut hook). Idempotent: a
        migration already cut (or done) returns its status."""
        m = self._migrator
        if m is None:
            raise StoreError("no schema migration to cut")
        m.request_cut()
        if wait:
            m.join()
        return m.status()

    def recover_schema_migration(self,
                                 record_path: Optional[str] = None
                                 ) -> Optional[dict]:
        """Boot-time crash matrix: consult the persisted migration
        record (if any) and either cleanly abort (no cut persisted) or
        resume/finish the cutover (cut persisted). Returns the recovery
        outcome dict or ``None`` when there was nothing to recover."""
        from ..migration import recover

        return recover(self, record_path
                       or self._default_migration_record())

    # -- debugging ----------------------------------------------------------

    def oracle(self, now: Optional[float] = None,
               context: Optional[dict] = None) -> OracleEvaluator:
        return OracleEvaluator(self.schema, self.store.snapshot(),
                               now=now, context=context)

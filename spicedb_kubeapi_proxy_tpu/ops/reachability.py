"""Slot-space reachability: the TPU execution backend for permission checks.

This is the "native tier" the north star mandates (BASELINE.json): what the
reference delegates to SpiceDB's recursive graph dispatcher (CheckPermission
/ CheckBulkPermissions / LookupResources — reference pkg/authz/check.go:41-48,
pkg/authz/lookups.go:49-65) is compiled here into a fixed-shape, jit-friendly
fixpoint over a flat boolean state vector.

Design
------
Every ``(definition, relation-or-permission, object)`` triple is interned
into one flat "slot" index. The whole evaluation state is a single uint8
tensor ``V[M, B]`` (M = total slots, B = batch of subjects). Three kinds of
graph structure all become the SAME uniform edge form ``dst <- src``:

- direct relation tuples   ``pod:x#viewer@user:alice``
      src = slot(user, __self, alice),   dst = slot(pod, viewer, x)
- userset tuples           ``pod:x#viewer@group:eng#member``
      src = slot(group, member, eng),    dst = slot(pod, viewer, x)
- arrow terms              ``permission view = namespace->view`` over tuple
  ``pod:x#namespace@namespace:ns``
      src = slot(namespace, view, ns),   dst = slot(pod, __arrow_k, x)

Wildcard subjects (``user:*``) fall out for free: the wildcard object is
interned at index 1 of every type, and every query seeds both its concrete
subject slot and its type's wildcard slot.

One propagation step is then a gather + segment-max (boolean OR) over the
edge array, followed by a static elementwise program that recomputes every
permission slot range from its userset-rewrite expression (union ``|``,
intersection ``&``, exclusion ``& ^1``, nil ``0``). The full evaluation is
``V_{t+1} = elementwise(base | propagate(V_t))`` iterated to fixpoint in a
``lax.while_loop`` — monotone in the graph, so it converges in at most
graph-diameter steps; exclusion/intersection are re-evaluated every step so
userset rewrites keep exact semantics under vectorization (SURVEY.md §7
"hard parts" (a)). Relationship expiration is a per-edge timestamp mask
applied at query time.

Checks read single slots; LookupResources reads a slot range. Both are
encoded host-side as int32 slot indices, so the device computation has
fixed shapes (§7 hard part (b)): E, M, B, Q are bucket-padded and jit
re-specializes only when a bucket grows.
"""

from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field, replace
from functools import partial
from typing import TYPE_CHECKING, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import bitprop, semiring
from .. import native
from ..obs.trace import tracer
from ..utils.metrics import metrics
from ..models.schema import (
    Arrow,
    Exclude,
    Expr,
    Intersect,
    Nil,
    RelationRef,
    Schema,
    Union,
)

if TYPE_CHECKING:  # break the ops <-> engine import cycle: annotation only
    from ..engine.store import Snapshot

SELF_REL = "__self"
VOID_IDX = 0  # reserved per-type object index for unknown ids
WILDCARD_IDX = 1  # reserved per-type object index for '*'

DEFAULT_MAX_ITERS = 128

# Incremental-update sizing: small writes append edges into a separate
# FIXED-CAPACITY "delta" overlay segment (own gather/segment pass per hop)
# instead of recompiling the whole graph; invalidated base edges get their
# expiration forced to -inf on device (residual) or their dense-block cell
# cleared. The capacity is static — part of the jit signature — so overlay
# appends NEVER re-specialize; running out of capacity is a back-pressure
# signal (engine/compaction.py folds the tail into a fresh base off the
# write path), not a growth event.
DELTA_PAD_MIN = 1024  # legacy floor for hand-built graphs (signature only)
DELTA_CAPACITY = 4096  # default overlay capacity (engine --delta-capacity)
MAX_DELTA_RECORDS = 8192


def _fallback(reason: str) -> None:
    """Count one silent-no-more incremental fallback: the caller is about
    to decline the O(write) path and force a full recompile. Reasons:
    ``overflow`` (overlay/dead-ledger capacity or per-batch record cap),
    ``stratification-inversion`` (a first-ever dependency direction),
    ``closured-expiry`` (expiration attached to a closured block pair),
    ``closured-caveat`` (a conditional grant attached to a closured
    block pair — derived closure cells would serve it unconditionally),
    ``caveat`` (a caveat/context pair not expressible against the
    frozen instance tables: first-ever caveat, full row bucket, or an
    unencodable context),
    ``history-trimmed`` / ``unlogged`` (store-side, engine.py),
    ``layout`` (tuple not expressible against the frozen slot layout),
    ``unstratified`` (hand-built graph without overlay state)."""
    metrics.counter("engine_graph_incremental_fallback_total",
                    reason=reason).inc()

# jitted fixpoint functions shared across CompiledGraph revisions with equal
# signatures (bounded: distinct schemas/bucket layouts, not revisions)
_JIT_CACHE: dict = {}
_JIT_CACHE_MAX = 32

# Monotonic count of fresh jit traces built (cache misses in
# _jit_run_for). Tests freeze it after warmup to prove that residency
# churn — promote / demote / stream-in — never alters a jit signature:
# steady-state streaming must be ZERO recompiles.
_TRACE_BUILDS = 0

# serializes lazy device-state init across worker threads (one lock for all
# graphs: init is rare — once per store revision); re-entrant so the shared
# jit-cache helper can take it from both the init path (already holding it)
# and incremental_update
_DEV_INIT_LOCK = threading.RLock()


def _jit_run_for(cg: "CompiledGraph", active: Optional[tuple] = None):
    """The jitted fixpoint for cg's signature, shared across revisions.
    Cache mutation is serialized on _DEV_INIT_LOCK — _dev_locked and
    incremental_update would otherwise race the get/evict/insert.

    The closure captures a slim static-metadata view, NOT the graph: a
    captured CompiledGraph would pin its host edge arrays and _device HBM
    buffers for as long as the cache entry lives — a dead-revision memory
    leak proportional to graph size x cached signatures.

    Kernel/mode toggles that are baked into traces (bit kernel, dense
    Pallas kernel, forced semiring mode) discriminate the key — flipping
    one mid-process gets a fresh trace, never a stale one.

    ``active``: tiered dispatch passes the demand-set block indices
    (sorted tuple) — the trace consumes exactly those blocks. The key is
    a function of the QUERY SHAPE (which ranges seed / are read), never
    of residency, so promote/demote churn cannot cause a retrace."""
    global _TRACE_BUILDS
    sig = (cg.signature(), bitprop.kernel_enabled(),
           bitprop.dense_kernel_enabled(), semiring.resolved_mode(),
           active)
    with _DEV_INIT_LOCK:
        run = _JIT_CACHE.get(sig)
        if run is None:
            _TRACE_BUILDS += 1
            run = jax.jit(fixpoint_program(cg.run_meta(active)),
                          static_argnames=("max_iters", "q_contig_len",
                                           "q_contig_rows"))
            if len(_JIT_CACHE) >= _JIT_CACHE_MAX:
                _JIT_CACHE.pop(next(iter(_JIT_CACHE)))
            _JIT_CACHE[sig] = run
    return run


def fixpoint_program(meta: "RunMeta"):
    """``_run`` over ``meta``, under the name the device trace shows:
    jitted, it is the XLA module ``jit_sdbkp_fixpoint`` (a bare partial
    has no name, and the profiler's module line read ``jit__unknown``)."""
    run = partial(_run, meta)
    run.__name__ = run.__qualname__ = "sdbkp_fixpoint"
    return run


class ConvergenceError(RuntimeError):
    """The fixpoint hit its iteration budget before converging — the analog
    of SpiceDB's dispatch-depth error (embedded depth 50, reference
    pkg/spicedb/spicedb.go:33). Raised instead of silently denying."""


def _next_bucket(n: int, minimum: int = 8) -> int:
    """Pad sizes to power-of-two buckets to bound jit re-specialization."""
    b = minimum
    while b < n:
        b *= 2
    return b


# Every slot range is padded to a multiple of LANE so the state tensor can
# live as [B, rows, LANE] with ranges row-aligned: slot s = (s // LANE,
# s % LANE). Without this, a [M, B] layout at B=1 pads the lane axis 1->128
# and every elementwise op streams 128x more HBM than the state holds.
LANE = 128


@dataclass
class _BlockMeta:
    """One dense relation block: edges between a (src slot range, dst slot
    range) pair compiled to a dense int8 matrix ``A[n_dst, n_src]`` so one
    propagation hop over the block is an MXU matmul ``A @ V[src_range]``
    instead of elementwise gathers (TPU gathers are scalar-bound; matmuls
    stream at HBM bandwidth). Only never-expiring edges are eligible —
    expiring edges stay on the residual gather/segment path where the
    query-time clock masks them."""

    dst_off: int
    n_dst: int
    src_off: int
    n_src: int
    # host-side local edge coordinates used to materialize A on device;
    # None in the slim run_meta() view (the traced code reads offsets only)
    dst_local: Optional[np.ndarray]
    src_local: Optional[np.ndarray]
    # the phase that applies the block (see _stratify): its dst range's
    # level (0 = on every trip of the loop; otherwise once, before the
    # loop if negative, after it if positive), or -1, the entry phase,
    # when the dst range is core and the src range a feeder
    level: int = 0
    # True when dst_local/src_local hold the REFLEXIVE-TRANSITIVE CLOSURE
    # of a self-pair (src range == dst range) instead of its base edges:
    # one application then yields every multi-hop value, so the range
    # peels out of the iterated core (see _stratify's ignore_self). The
    # diagonal keeps already-merged values alive across the replacing
    # per-level merge. Derived cells cannot be deleted individually —
    # incremental deletes RE-CLOSE the block from its base edges
    # (base_dst_local/base_src_local, kept for exactly this) in O(block).
    closured: bool = False
    base_dst_local: Optional[np.ndarray] = None
    base_src_local: Optional[np.ndarray] = None

    def slim(self) -> "_BlockMeta":
        return _BlockMeta(self.dst_off, self.n_dst, self.src_off,
                          self.n_src, None, None, self.level, self.closured)

    def reclosed(self, remove: set) -> Optional["_BlockMeta"]:
        """A new closured block with ``remove`` (local (dst, src) pairs)
        deleted from the BASE edge set and the closure recomputed — the
        O(block) alternative to a full graph recompile on membership
        deletes. None when the closure overflows (caller recompiles)."""
        keep = np.fromiter(
            ((int(d), int(s)) not in remove
             for d, s in zip(self.base_dst_local.tolist(),
                             self.base_src_local.tolist())),
            dtype=bool, count=len(self.base_dst_local))
        nb_dst = self.base_dst_local[keep]
        nb_src = self.base_src_local[keep]
        coo = _closure_pairs(nb_dst, nb_src, self.n_dst)
        if coo is None:
            return None
        dl, sl = coo
        return _BlockMeta(self.dst_off, self.n_dst, self.src_off,
                          self.n_src, dl, sl, self.level, True,
                          nb_dst, nb_src)


# dense-block eligibility: a block must carry enough edges to beat the
# segment path (DENSE_MIN_EDGES), must fit in memory (DENSE_MAX_CELLS), and
# big blocks must additionally be dense enough that streaming A beats
# scalar gathers (DENSE_MIN_DENSITY). Measured on v5e at the 10M-rel
# bench shape: the 9.85M-edge pod#viewer block (density 4.6e-3) runs
# ~3ms/query bit-packed vs ~310ms on the gather/segment path — TPU
# gathers are ~100x worse per edge, so lean strongly toward blocks.
DENSE_MIN_EDGES = 1024
DENSE_MIN_CELLS = 1 << 24  # 16M cells (16 MiB int8) — density waived below
DENSE_MIN_DENSITY = 5e-4
DENSE_MAX_CELLS = 3 << 30  # 3 GiB


@dataclass
class _PermProgram:
    """One permission's elementwise recompute: (dst_offset, size, expr),
    with expression leaves resolved to slot offsets."""

    dst_off: int
    size: int
    expr: Expr
    # leaf name -> slot offset (RelationRef name or Arrow term id)
    leaf_off: dict
    # stratification level of the permission range (see _stratify)
    level: int = 0


def _range_id(offs: np.ndarray, slot) -> int:
    """Range id owning a slot: offs is ascending range offsets."""
    return int(np.searchsorted(offs, slot, side="right")) - 1


# self-pair closures larger than this many pairs fall back to the plain
# iterated-core block (the closure of a dense DAG can approach n^2 pairs;
# the dense matrix tolerates that, but host join memory should stay bounded)
CLOSURE_MAX_PAIRS = 1 << 24


def _closure_pairs(dst_local: np.ndarray, src_local: np.ndarray,
                   n: int) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """Reflexive-transitive closure of an n-node COO self-block (edge
    ``src -> dst`` flows the src slot's value to the dst slot). Returns
    (dst_local, src_local) int32 arrays INCLUDING the diagonal, or None
    when the closure exceeds CLOSURE_MAX_PAIRS. Sparse semi-join on the
    host: group graphs are shallow and narrow, so this is microseconds
    where a dense matrix power would stream gigabytes. Handles instance
    cycles (recursive groups) — the pair-set union converges regardless."""
    base_order = np.argsort(src_local, kind="stable")
    b_src = src_local[base_order].astype(np.int64)
    b_dst = dst_local[base_order].astype(np.int64)
    cur = np.unique(src_local.astype(np.int64) * n + dst_local)
    while True:
        cs, cd = cur // n, cur % n
        # compose: (s -> d) ∘ (d -> d2) gives (s -> d2)
        lo = np.searchsorted(b_src, cd, side="left")
        hi = np.searchsorted(b_src, cd, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if total:
            starts = np.repeat(lo, counts)
            offsets = np.arange(total) - np.repeat(
                np.cumsum(counts) - counts, counts)
            new_pairs = (np.repeat(cs, counts) * n
                         + b_dst[starts + offsets])
            merged = np.unique(np.concatenate([cur, new_pairs]))
        else:
            merged = cur
        if len(merged) > CLOSURE_MAX_PAIRS:
            return None
        if len(merged) == len(cur):
            break
        cur = merged
    diag = np.arange(n, dtype=np.int64)
    cur = np.unique(np.concatenate([cur, diag * n + diag]))
    return (cur % n).astype(np.int32), (cur // n).astype(np.int32)


def _stratify(offs: np.ndarray, src_rid: np.ndarray, dst_rid: np.ndarray,
              programs: list, ignore_self: frozenset = frozenset(),
              potential: frozenset = frozenset()) -> tuple[dict, int, int]:
    """Range-level stratification of the dependency graph: where every
    slot range sits in the order of evaluation.

    Build the range-granularity dependency graph (edges: src range feeds
    dst range; programs: every leaf range feeds the permission range) and
    peel it from both ends:

    1. from the **sink** end, repeatedly, every range NOTHING still
       depends on. Peeled ranges get levels 1..L in reverse peel order,
       so every level-k range's inputs sit strictly below k and one
       application per level suffices, after the loop. What is left is
       every cycle and all that feeds one.
    2. from the **source** end of what is left, repeatedly, every range
       nothing left feeds: the *feeder* levels, final before the loop
       starts and applied once each, in order, before it. Ranges nothing
       feeds at all (the subjects' own ``__self`` ranges) are *roots*:
       final the moment they are seeded, so they take the lowest level
       and no phase.

    What neither peel removes — the ranges on a cycle (recursive
    groups/orgs, a permission that rests on itself through an arrow) and
    the ranges between two cycles — is the **core** (level 0), the only
    part the fixpoint iterates. The sink-end peel comes first, so a graph
    without a cycle is peeled whole by it and has neither feeders nor
    core.

    Levels are the order of execution: feeders at ``-n_pre .. -2``
    (roots one lower still), ``-1`` for the *entry* phase (the core's
    in-edges whose source is a feeder range, walked once; no range sits
    there), the core at 0, the rest at ``1 .. n_levels``. Returns
    ``({range_id: level}, n_levels, n_pre)``: ``n_pre`` counts the
    one-shot phases before the loop, entry included (0 = no feeders).

    Why it matters: an edge is worth walking again only if its source
    can still change. In kube-shaped graphs the largest ranges (per-pod
    relations) are acyclic sinks, and where a cycle exists nearly every
    edge that reaches it starts in a range that is final before the
    loop (users into groups, grants into a namespace tree).

    ``ignore_self``: range ids whose self-dependency (r -> r edges) is
    satisfied by a closured dense block (one application = all hops), so
    the self-edge must not force the range into the core.

    ``potential``: (src range, dst range) pairs the SCHEMA admits whether
    or not a tuple uses them yet. They order the peeled levels too (sink
    end and feeder end alike), so the first write along one (a relation
    or type no loaded tuple had) fits the frozen levels and rides the
    overlay. The core stays what the data makes it: a pair that would
    close a cycle the data does not have, pull a peeled range into the
    core, or feed a feeder from the core is left out and stays a
    ``stratification-inversion`` when it is first written.
    """
    n_ranges = len(offs)
    consumers: list[set] = [set() for _ in range(n_ranges)]
    if len(src_rid):
        # dedup range pairs vectorized (millions of edges -> dozens of
        # pairs) before touching Python objects
        pairs = np.unique(src_rid.astype(np.int64) * n_ranges + dst_rid)
        for p in pairs.tolist():
            s, d = divmod(p, n_ranges)
            if s == d and s in ignore_self:
                continue
            consumers[s].add(d)
    for p in programs:
        p_rid = _range_id(offs, p.dst_off)
        for off in set(p.leaf_off.values()):
            consumers[_range_id(offs, off)].add(p_rid)

    def peel_sinks() -> tuple[list, set]:
        remaining = set(range(n_ranges))
        peel: list[list[int]] = []
        while True:
            removable = [r for r in remaining
                         if not (consumers[r] & remaining)]
            if not removable:
                return peel, remaining
            peel.append(removable)
            remaining -= set(removable)

    def peel_sources(left: set) -> tuple[set, list, set]:
        """(roots, feeder groups in order of execution, the core) of what
        the sink-end peel left. Every producer of such a range is such a
        range itself (it feeds a cycle too)."""
        producers: list[set] = [set() for _ in range(n_ranges)]
        for s in left:
            for d in consumers[s]:
                producers[d].add(s)
        # a closured self-pair nothing else feeds still needs its closure
        # applied: it is a feeder with phases, not a root
        roots = {r for r in left
                 if not producers[r] and r not in ignore_self}
        left = left - roots
        groups: list[list[int]] = []
        while True:
            ready = [r for r in left if not (producers[r] & left)]
            if not ready:
                return roots, groups, left
            groups.append(ready)
            left -= set(ready)

    def feeds(a: int, b: int) -> bool:
        """Whether range a's values reach range b along ``consumers``."""
        seen, todo = {a}, [a]
        while todo:
            for c in consumers[todo.pop()]:
                if c == b:
                    return True
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
        return False

    if potential:
        _, left = peel_sinks()
        roots, groups, _ = peel_sources(left)
        feeders = roots.union(*groups)
        for s, d in sorted(potential):
            # an acyclic pair among feeders leaves both feeders; feeder
            # -> core and feeder -> peeled are in order as they stand
            if s != d and not feeds(d, s) and (
                    (s not in left and d not in left)
                    or (s in feeders and d in feeders)):
                consumers[s].add(d)
    peel, left = peel_sinks()
    roots, groups, core = peel_sources(left)
    n_levels = len(peel)
    n_feed = len(groups)
    level = {r: 0 for r in core}  # on a cycle, or between two
    for i, grp in enumerate(peel):  # peeled first -> evaluated last
        for r in grp:
            level[r] = n_levels - i
    for j, grp in enumerate(groups):  # peeled first -> evaluated first
        for r in grp:
            level[r] = -(n_feed - j + 1)
    for r in roots:
        level[r] = -(n_feed + 2)
    return level, n_levels, (n_feed + 1 if roots or groups else 0)


@dataclass(frozen=True)
class SeedLayout:
    """The *seeded* parts of the residual: within each phase's slice the
    edges that start in a subject's own ``__self`` range lie behind the
    walked part, sorted by (source, destination), and a row-pointer
    table (``CompiledGraph.res_ptr``) finds a source's run there. A
    ``__self`` range is written by nothing but the seeding, so a dispatch
    reads the runs of its 2 * B seed slots (``semiring.propagate_seeded``)
    where a walk of the slice would multiply every other edge by zero.
    One entry per phase, in ``res_level_bounds``' order; all static."""

    # where the phase's seeded part starts (its slice's end: it has none)
    starts: tuple
    # D_k, a power-of-two bucket of the longest run the table serves in
    # the phase (0 = no seeded part). Chosen by cost from the phase's
    # out-degrees (``_seed_fanout``): a source with a longer run (a
    # ``user:*`` grant, an account bound everywhere) stays walked
    fanout: tuple
    # ((slot offset, size, row-pointer base), ...): the ``__self`` ranges
    # with runs in the phase, each with a window of size + 1 pointers.
    # Slot ``s`` of one owns the run ``ptr[i] .. ptr[i + 1]``, ``i = base
    # + s - offset``, counted from the seeded part's start
    ranges: tuple

    def lengths(self, bounds: tuple) -> list:
        """Padded length of each phase's seeded part, given
        ``res_level_bounds``."""
        return [hi - mid for mid, hi in zip(self.starts, bounds[1:])]


def seed_looked(rows: int, fanout: int, length: int) -> bool:
    """Whether a dispatch of ``rows`` rows reads a seeded part of
    ``length`` padded edges through the table: only where its
    ``2 * rows * fanout`` entries are fewer than the part itself. A bulk
    check of thousands of subject rows walks the part as ordinary
    edges."""
    return 0 < 2 * rows * fanout < length


def _seed_fanout(degrees: np.ndarray) -> int:
    """D for one phase, from the out-degrees of its ``__self`` sources:
    the power-of-two bucket that costs a one-row dispatch least, counting
    the edges a cap D leaves walked (sources with longer runs) against
    the 2 * D entries it looks up. One long run among short ones never
    pays for itself (2 * D > its edges), so it stays walked and D is
    what the rest need."""
    top = _next_bucket(int(degrees.max()))
    return min((top >> i for i in reversed(range(top.bit_length() - 3))),
               key=lambda d: int(degrees[degrees > d].sum()) + 2 * d)


@dataclass(frozen=True)
class RunMeta:
    """What the traced fixpoint reads from the graph: slot count,
    permission programs, dense-block offsets, the schedule (see
    ``_stratify``), and the caveat VM's static shapes. Captured by jit
    closures in place of the full CompiledGraph (see _jit_run_for).

    The schedule is a run of phases numbered in the order they execute:
    ``-n_pre .. -2`` the feeder levels, ``-1`` the entry phase, ``0`` the
    loop over the core, ``1 .. n_levels`` the levels after it. Every
    phase but the loop is applied exactly once (``apply_level_once``).
    ``res_level_bounds`` slices the residual in that order, so phase k's
    edges are ``bounds[k + n_pre] : bounds[k + n_pre + 1]``; blocks and
    programs carry their phase in ``level`` (a core range's programs
    carry 0 and run at the entry phase and on every trip)."""

    M: int
    programs: tuple
    blocks: tuple
    res_level_bounds: tuple  # len n_pre + n_levels + 2
    n_levels: int
    # per level 1..L: tuple of (offset, size) slot ranges finalized at
    # that level (merged via per-range slice writes — no dense masks)
    level_ranges: tuple
    # caveat VM static meta (caveats/vm.py CavMeta per caveat) and the
    # total validity-row count (1 = no caveats: the VM is skipped and
    # edge activation is the expiration mask alone)
    caveats: tuple = ()
    cav_rows: int = 1
    # semiring propagation-mode policy baked into the trace: "auto" =
    # per-iteration lax.cond on traced occupancy; "push"/"pull" force one
    # branch (ops/semiring.py force_mode)
    spmm_mode: str = "auto"
    # one-shot phases before the loop (0 = none: no cycle, or nothing
    # feeds it), and per phase -n_pre..-1 the slot ranges it finalizes;
    # the entry phase (last) merges into the core's ranges
    n_pre: int = 0
    pre_ranges: tuple = ()
    # the seeded parts of the slices (None: every edge is walked)
    seed: Optional[SeedLayout] = None

    def level_slice(self, k: int) -> tuple[int, int]:
        """Bounds of phase k's residual slice, both parts."""
        i = k + self.n_pre
        return self.res_level_bounds[i], self.res_level_bounds[i + 1]

    def parts(self, k: int) -> tuple[int, int, int]:
        """``(lo, mid, hi)`` of phase k's slice: the walked part
        ``lo:mid`` (dst-sorted), the seeded part ``mid:hi`` (see
        SeedLayout)."""
        lo, hi = self.level_slice(k)
        return (lo, hi if self.seed is None
                else self.seed.starts[k + self.n_pre], hi)

    def seed_looked(self, k: int, rows: int) -> bool:
        """Whether a dispatch of ``rows`` rows reads phase k's seeded
        part through the table (else it walks it)."""
        _, mid, hi = self.parts(k)
        return self.seed is not None and seed_looked(
            rows, self.seed.fanout[k + self.n_pre], hi - mid)

    def scope(self, k: int) -> str:
        """Phase k's named scope in the device trace."""
        if k >= 0:
            return f"level{k}" if k else "core"
        return "entry" if k == -1 else f"feed{k + self.n_pre + 1}"

    def windows(self, k: int) -> tuple:
        """The (offset, size) slot ranges one-shot phase k merges."""
        return (self.level_ranges[k - 1] if k > 0
                else self.pre_ranges[k + self.n_pre])

    def programs_at(self, k: int) -> list:
        """The programs phase k runs: its own ranges', and at the entry
        phase the core's (their feeder leaves are final by then)."""
        level = 0 if k == -1 else k
        return [p for p in self.programs if p.level == level]


def convergence_fuse_steps(meta: "RunMeta") -> int:
    """Propagation steps the mesh backend fuses per convergence
    collective — the K in parallel/sharded.py's K-step fused while body.

    Derived from the compiled graph's stratification: a stratified graph
    iterates only its small cyclic core (recursive groups/orgs, which
    converge in a few hops — what feeds them and the per-pod bulk are
    peeled into one-shot levels), so K=2 halves the convergence
    collectives without wasting propagation work; an unstratified graph
    (hand-built, no level split) iterates everything with unknown
    diameter, so a deeper fuse amortizes better. The fixpoint is monotone — steps past
    convergence are no-ops — so K only trades at most K-1 cheap wasted
    hops against saved cross-axis collectives and host syncs."""
    return 2 if meta.n_levels or meta.n_pre else 4


@dataclass
class CompiledGraph:
    """An immutable device-ready compilation of (schema, snapshot)."""

    schema: Schema
    revision: int
    base_time: float
    M: int  # real slots (M is also the trash slot index; arrays sized M+1)
    slot_offset: dict  # (type_name, rel_name) -> offset
    type_sizes: dict  # type_name -> object count (incl. void/wildcard)
    # host edge arrays, sorted by dst, padded to bucket; pad rows point at
    # the trash slot with -inf expiration (never valid). The FULL edge set
    # lives here (the sharded path consumes it directly); the single-chip
    # path splits it into dense blocks + a residual at _dev() time.
    src: np.ndarray
    dst: np.ndarray
    exp_rel: np.ndarray  # float32 seconds relative to base_time; +inf = never
    n_edges: int
    programs: list  # topo-ordered _PermProgram list
    # dense-block split (see _BlockMeta): blocks cover the big never-expiring
    # relation ranges; res_idx indexes the edges that stay on the
    # gather/segment path (expiring, tiny, or too-sparse-to-densify)
    blocks: list = field(default_factory=list)
    res_idx: Optional[np.ndarray] = None
    # incremental-update state (engine write path, incremental_update()):
    # a FIXED-CAPACITY delta overlay segment consumed by its own
    # gather/segment pass each hop (append order, NOT dst-sorted), and the
    # (src, dst) pairs of base edges invalidated since the last full
    # compile (consumed by ShardedGraph so a sharded view of an
    # incrementally-updated graph stays consistent). The host arrays are
    # SHARED across incremental descendants of one compiled base and
    # mutated in place under ``host_lock`` — per-revision immutability
    # lives in the watermarks (n_delta / n_dead) and the functional
    # device arrays, not in host copies.
    delta_src: Optional[np.ndarray] = None  # int32 [cap], trash-padded
    delta_dst: Optional[np.ndarray] = None
    delta_exp: Optional[np.ndarray] = None  # float32 rel to base_time
    delta_cav: Optional[np.ndarray] = None  # int32 [cap] caveat rows
    n_delta: int = 0
    dead_pairs: Optional[np.ndarray] = None  # int64 [K, 2] (src, dst) view
    n_dead: int = 0
    delta_cap: int = 0  # static overlay capacity (0 = legacy/hand-built)
    # shared writer-state (one object per compiled base, carried by every
    # incremental descendant; reads/writes only under the engine's
    # graph-advance lock + host_lock):
    delta_pos: Optional[dict] = None  # (src, dst) -> overlay slot
    dead_set: Optional[set] = None  # (src, dst) pairs killed in the base
    dead_buf: Optional[np.ndarray] = None  # int64 [cap, 2] append buffer
    host_lock: Optional[object] = None  # guards shared host-array reads
    block_codes: Optional[dict] = None  # id(_BlockMeta) -> sorted codes
    # host residual views (padded; one slice per phase — see
    # _stratify/res_level_bounds — each a walked part ordered by dst and
    # a seeded part ordered by (src, dst), see SeedLayout) for device
    # upload + incremental search
    res_src: Optional[np.ndarray] = None
    res_dst: Optional[np.ndarray] = None
    res_exp: Optional[np.ndarray] = None
    # per-residual-edge caveat validity row (0 = unconditional); the
    # edge participates in a hop iff its expiration passes AND its row
    # in the per-dispatch cav_ok vector reads 1 (caveats/vm.py)
    res_cav: Optional[np.ndarray] = None
    # compiled caveat table (caveats/vm.py CompiledCaveats): instance
    # context columns + op tapes, shared across incremental descendants
    caveats: Optional[object] = None
    # stratification (see _stratify / RunMeta): residual slice bounds per
    # phase in order of execution (len n_pre + n_levels + 2), the count
    # of one-shot phases before and after the loop, and the level of
    # every slot range (range_offs-aligned; negative = feeder)
    res_level_bounds: Optional[tuple] = None
    n_levels: int = 0
    n_pre: int = 0
    range_levels: Optional[np.ndarray] = None
    # the seeded parts of the residual slices and their row pointers
    # (int32, every window end to end; see SeedLayout). None on
    # hand-built graphs: every edge is walked
    seed: Optional[SeedLayout] = None
    res_ptr: Optional[np.ndarray] = None
    n_seed_edges: int = 0  # real (unpadded) edges the seeded parts hold
    # compile-time lookup tables reused by the incremental path
    range_offs: Optional[np.ndarray] = None  # ascending slot-range offsets
    block_index: dict = field(default_factory=dict)  # (dst_off,src_off)->i
    self_off: Optional[np.ndarray] = None  # [n_types+1]
    rel_off: Optional[np.ndarray] = None  # [n_types+1, n_rels+1]
    relperm_off: Optional[np.ndarray] = None
    # (resource tid, tupleset rel id, term slot offset, tgt_off[n_types+1])
    arrow_maps: list = field(default_factory=list)
    # range-granularity dependency adjacency retained from compile time:
    # sorted ((src range id, dst range id), ...) pairs covering the FULL
    # edge set (computed before the dense split) plus every program's
    # leaf -> permission edge. The tiered dispatch path intersects
    # forward reachability from the seed ranges with backward
    # reachability from the queried ranges over this graph (plus the
    # live overlay) to pick the dense blocks a dispatch actually needs.
    # None on hand-built graphs (tiering then streams every block).
    range_adj: Optional[tuple] = None
    # tiered-storage residency state (storage/tiers.TierStore), attached
    # by enable_tiering(); None = classic all-resident placement. NOT
    # part of signature(): residency is invisible to traces. Shared
    # across incremental descendants of one compiled base (carried by
    # dataclasses.replace), rebuilt fresh by each compaction fold.
    tier: Optional[object] = None
    # push/pull crossover threshold fed to the semiring primitive as a
    # TRACED scalar (ops/semiring.propagate): push while the traced
    # per-iteration occupancy is <= this. Mutated in place by the engine
    # from its frontier-occupancy EWMA
    # (semiring.crossover_from_occupancy) — tuning costs zero recompiles.
    spmm_crossover: float = 1.0
    # lazily-populated device state
    _device: dict = field(default_factory=dict)

    # -- host-side encoding ------------------------------------------------

    def offset_of(self, type_name: str, rel_name: str) -> Optional[int]:
        return self.slot_offset.get((type_name, rel_name))

    def encode_subject(self, type_name: str, obj_id: str,
                       subject_relation: Optional[str] = None,
                       objects=None) -> tuple[int, int]:
        """-> (subject_seed_slot, wildcard_seed_slot); trash slot when
        unknown so unknown subjects simply seed nothing."""
        trash = self.M
        if subject_relation:
            off = self.offset_of(type_name, subject_relation)
            # wildcards match only concrete subjects (oracle: a userset
            # subject query never matches a `type:*` tuple), so userset
            # subjects must not seed the wildcard slot
            wc_off = None
        else:
            off = self.offset_of(type_name, SELF_REL)
            wc_off = off
        if off is None:
            return trash, trash
        idx = self._obj_index(type_name, obj_id, objects)
        seed = off + idx if idx is not None else trash
        wc = wc_off + WILDCARD_IDX if wc_off is not None else trash
        return seed, wc

    def encode_target(self, type_name: str, permission: str, obj_id: str,
                      objects=None) -> int:
        """Slot to read a check result from; trash slot (always 0) when the
        type/permission/object is unknown."""
        off = self.offset_of(type_name, permission)
        if off is None:
            return self.M
        idx = self._obj_index(type_name, obj_id, objects)
        return off + idx if idx is not None else off + VOID_IDX

    def _obj_index(self, type_name: str, obj_id: str, objects) -> Optional[int]:
        if objects is None:
            return None
        it = objects.get(type_name)
        if it is None:
            return None
        i = it.lookup(obj_id)
        # ids interned after this snapshot was compiled have no edges; void
        # behaves identically (no edges) and keeps indices in range.
        if i is None or i >= self.type_sizes.get(type_name, 0):
            return VOID_IDX
        return i

    # -- device execution --------------------------------------------------

    def signature(self) -> tuple:
        """Everything baked statically into the traced computation. Two
        CompiledGraphs with equal signatures can share one jitted function —
        type sizes are bucket-padded, so steady-state writes (new tuples,
        even new objects within a bucket) keep the signature stable and hit
        the XLA compile cache."""

        def expr_sig(e: Expr, leaf_off: dict) -> tuple:
            if isinstance(e, Nil):
                return ("nil",)
            if isinstance(e, (RelationRef, Arrow)):
                return ("leaf", leaf_off[e])
            if isinstance(e, Union):
                return ("or",) + tuple(expr_sig(o, leaf_off) for o in e.operands)
            if isinstance(e, Intersect):
                return ("and",) + tuple(expr_sig(o, leaf_off) for o in e.operands)
            if isinstance(e, Exclude):
                return ("sub", expr_sig(e.base, leaf_off),
                        expr_sig(e.subtract, leaf_off))
            raise TypeError(e)

        return (
            self.M,
            tuple((p.dst_off, p.size, p.level,
                   expr_sig(p.expr, p.leaf_off))
                  for p in self.programs),
            tuple((b.dst_off, b.n_dst, b.src_off, b.n_src, b.level,
                   b.closured)
                  for b in self.blocks),
            # padded delta-segment length (grows by buckets under
            # incremental updates; each growth re-specializes once). The
            # residual's traced shape is fully determined by
            # res_level_bounds below (per-level buckets).
            self._delta_pad(),
            # stratification: the traced program slices the residual at
            # these bounds and bakes per-level merge ranges, so two graphs
            # may share a jit ONLY with identical stratification. The
            # unstratified fallback (hand-built graphs) discriminates on
            # its full padded residual length instead.
            self.n_levels,
            self.res_level_bounds if self.res_level_bounds is not None
            else ("unstratified", len(self.res_src)
                  if self.res_src is not None else len(self.src)),
            # where each slice's seeded part starts, its fan-out and its
            # row-pointer windows: all baked into the trace
            self._seed_layout(),
            None if self.range_levels is None
            else tuple(self.range_levels.tolist()),
            # the per-level merge windows (RunMeta.level_ranges) derive
            # from the range offsets; pin them so signature-equal graphs
            # cannot differ in any baked slice coordinate
            None if self.range_offs is None
            else tuple(self.range_offs.tolist()),
            # caveat VM shapes: tape lengths, register/context/list
            # layouts, instance-row buckets — all baked into the trace
            None if self.caveats is None else self.caveats.signature(),
        )

    def _delta_pad(self) -> int:
        if self.delta_src is not None:
            return len(self.delta_src)
        if self.delta_cap:
            return self.delta_cap
        return _next_bucket(max(self.n_delta, 1), DELTA_PAD_MIN)

    def _host_guard(self):
        """Context guarding reads of the SHARED mutable host arrays
        (delta segment, res_exp) against an in-flight overlay append."""
        return self.host_lock if self.host_lock is not None \
            else nullcontext()

    def _level_bounds(self) -> tuple:
        """``res_level_bounds``, or one slice for an unstratified
        (hand-built) graph: everything is core."""
        if self.res_level_bounds is not None:
            return tuple(self.res_level_bounds)
        return (0, len(self.res_src) if self.res_src is not None
                else len(self.src))

    def _seed_layout(self) -> Optional[SeedLayout]:
        """``seed``, which describes parts of ``res_level_bounds``'
        slices: a graph stripped of those has none."""
        return self.seed if self.res_level_bounds is not None else None

    def run_meta(self, active: Optional[tuple] = None) -> "RunMeta":
        """Slim static-metadata view for jit closures: everything the
        traced fixpoint reads from the graph object, nothing that holds
        host edge arrays or device buffers alive.

        ``active`` (tiered dispatch): keep only these block indices —
        the trace then takes exactly that many block operands. The
        per-level merge windows stay UNFILTERED: an excluded closured
        block's range merges plain propagation values, which is safe
        because demand closure guarantees excluded ranges cannot
        influence any queried slot."""
        bounds = self._level_bounds()

        def windows(k: int) -> tuple:
            """Slot ranges phase k finalizes (the entry phase, -1, holds
            no range of its own: it merges into the core's)."""
            offs = self.range_offs
            ends = np.append(offs[1:], self.M)
            wins = [
                (int(offs[rid]), int(ends[rid]) - int(offs[rid]))
                for rid in np.flatnonzero(
                    self.range_levels == (0 if k == -1 else k)).tolist()]
            # even phases merge exactly the closured blocks' ranges
            # (their in-edges merged at the odd phase just before;
            # the closure application finalizes them here)
            wins += [(b.dst_off, b.n_dst) for b in self.blocks
                     if b.closured and b.level == k]
            return tuple(wins)

        level_ranges = pre_ranges = ()
        if self.range_levels is not None:
            level_ranges = tuple(
                windows(k) for k in range(1, self.n_levels + 1))
            pre_ranges = tuple(windows(k) for k in range(-self.n_pre, 0))
        cav = self.caveats
        kept = (self.blocks if active is None
                else [self.blocks[i] for i in active])
        return RunMeta(
            M=self.M,
            programs=tuple(self.programs),
            blocks=tuple(b.slim() for b in kept),
            res_level_bounds=bounds,
            n_levels=self.n_levels,
            level_ranges=level_ranges,
            caveats=cav.metas if cav is not None else (),
            cav_rows=cav.n_rows if cav is not None else 1,
            spmm_mode=semiring.resolved_mode(),
            n_pre=self.n_pre,
            pre_ranges=pre_ranges,
            seed=self._seed_layout(),
        )

    def _dev(self):
        # concurrent first queries (asyncio.to_thread workers) race to
        # initialize; build into a local dict and publish atomically
        d = self._device
        if not d:
            with _DEV_INIT_LOCK:
                return self._dev_locked()
        return d

    def memo(self, key, make):
        """``make()`` once, kept beside this graph's device arrays and
        compiled programs: it lives as long as they do, incremental
        updates included (engine/batcher.py keeps a window's fused lookup
        program here)."""
        d = self._dev()
        if key not in d:
            with _DEV_INIT_LOCK:
                if key not in d:
                    d[key] = make()
        return d[key]

    def _dev_locked(self):
        d = self._device
        if not d:
            with self._host_guard():
                d = self._dev_build()
                self._device = d
        return self._device

    def _dev_build(self):
        d = {}
        res_cav = self.res_cav
        if self.res_src is not None:
            res_src, res_dst, res_exp = \
                self.res_src, self.res_dst, self.res_exp
        elif self.res_idx is None:
            # no dense split computed: everything rides the segment path
            res_src, res_dst, res_exp = self.src, self.dst, self.exp_rel
        else:
            n_res = len(self.res_idx)
            E_pad = _next_bucket(max(n_res, 1))
            res_src = np.full(E_pad, self.M, dtype=np.int32)
            res_dst = np.full(E_pad, self.M, dtype=np.int32)
            res_exp = np.full(E_pad, -np.inf, dtype=np.float32)
            # res_idx is ascending into dst-sorted edge arrays, so the
            # residual stays dst-sorted (indices_are_sorted=True relies
            # on this)
            res_src[:n_res] = self.src[self.res_idx]
            res_dst[:n_res] = self.dst[self.res_idx]
            res_exp[:n_res] = self.exp_rel[self.res_idx]
        if res_cav is None or len(res_cav) != len(res_src):
            res_cav = np.zeros(len(res_src), dtype=np.int32)
        d["src"] = jnp.asarray(res_src)
        d["dst"] = jnp.asarray(res_dst)
        d["exp"] = jnp.asarray(res_exp)
        d["cav"] = jnp.asarray(res_cav)
        d["ptr"] = jnp.asarray(self._res_ptr())
        d["dsrc"], d["ddst"], d["dexp"], d["dcav"] = (
            jnp.asarray(a) for a in self._delta_host())
        # caveat VM instance tables (tapes + per-tuple context columns);
        # () when the graph carries no conditional grants
        d["cav_static"] = (self.caveats.device_static()
                          if self.caveats is not None
                          and self.caveats.metas else ())

        # Tiered placement: NOTHING is device-resident up front — every
        # block starts cold and streams in on first demand, which is
        # what makes "namespaces never touched by traffic cost zero
        # device bytes" literally true. The placeholder tuples keep the
        # dict shape for non-dispatch consumers; the dispatch path
        # assembles its own per-demand-set operand tuples.
        if self.tier is not None:
            d["blocks"] = tuple(None for _ in self.blocks)
            d["blocks_bits"] = tuple(None for _ in self.blocks)
            return d

        # dense blocks from host meta, minus any cells killed by
        # incremental updates since the last full compile (host meta is
        # not rewritten by incremental_update; dead_pairs is the ledger)
        blocks_dev = []
        bits_on = bitprop.kernel_enabled()
        bits_dev = []
        for b in self.blocks:
            dl_dead, sl_dead = self._dead_cells(b)
            A = jnp.zeros((b.n_dst, b.n_src), dtype=jnp.int8) \
                .at[jnp.asarray(b.dst_local),
                    jnp.asarray(b.src_local)].set(1)
            if len(dl_dead):
                A = A.at[jnp.asarray(dl_dead),
                         jnp.asarray(sl_dead)].set(0)
            blocks_dev.append(A)
            # bit-packed dual for the small-batch latency path
            # (ops/bitprop.py); None = block stays matmul-only. Packing
            # + device residency is skipped entirely when the bit
            # kernel cannot run (the toggle is part of the jit-cache
            # key, so no trace reads the bits in that case).
            if bits_on and bitprop.eligible(b.n_dst, b.n_src):
                bits = bitprop.pack_block_host(
                    b.dst_local, b.src_local, b.n_dst, b.n_src)
                if len(dl_dead):
                    np.bitwise_and.at(
                        bits, (dl_dead, sl_dead // 32),
                        ~(np.uint32(1) << (sl_dead % 32).astype(
                            np.uint32)))
                bits_dev.append(jnp.asarray(bits))
            else:
                bits_dev.append(None)
        d["blocks"] = tuple(blocks_dev)
        d["blocks_bits"] = tuple(bits_dev)
        # kernel/mode toggles are baked into traces, so they are part of
        # the shared-function cache key; query_async keeps a per-mode
        # entry so a force_mode() flip (the differential tests) cannot
        # dispatch through a stale trace
        d[("run", semiring.resolved_mode())] = _jit_run_for(self)
        return d

    def _dead_cells(self, bm: _BlockMeta) -> tuple[np.ndarray, np.ndarray]:
        """Local (dst, src) coordinates of dead_pairs falling inside a
        dense block's ranges."""
        if self.dead_pairs is None or not len(self.dead_pairs):
            z = np.empty(0, dtype=np.int64)
            return z, z
        s, t = self.dead_pairs[:, 0], self.dead_pairs[:, 1]
        m = ((t >= bm.dst_off) & (t < bm.dst_off + bm.n_dst)
             & (s >= bm.src_off) & (s < bm.src_off + bm.n_src))
        return t[m] - bm.dst_off, s[m] - bm.src_off

    def _res_ptr(self) -> np.ndarray:
        """The seeded parts' row pointers (one zero where the graph has
        no seeded part: the trace then never reads it)."""
        if self.res_ptr is None or self._seed_layout() is None:
            return np.zeros(1, dtype=np.int32)
        return self.res_ptr

    def _delta_host(self) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                   np.ndarray]:
        """Host delta overlay segment (fixed capacity, append order —
        NOT dst-sorted); empty = all trash. Shared across incremental
        descendants; callers snapshotting it hold ``_host_guard``."""
        if self.delta_src is not None:
            cav = self.delta_cav if self.delta_cav is not None \
                else np.zeros(len(self.delta_src), dtype=np.int32)
            return self.delta_src, self.delta_dst, self.delta_exp, cav
        pad = self._delta_pad()
        return (np.full(pad, self.M, dtype=np.int32),
                np.full(pad, self.M, dtype=np.int32),
                np.full(pad, -np.inf, dtype=np.float32),
                np.zeros(pad, dtype=np.int32))

    # -- tiered storage ----------------------------------------------------

    def enable_tiering(self, budget_bytes: int,
                       spill_dir: Optional[str] = None):
        """Split this graph's dense blocks into residency-tracked tiers
        under an explicit device byte budget (storage/): every block's
        COO is encoded into a host-cold arena, nothing is uploaded until
        a dispatch demands it, and streamed blocks stay hot only while
        the budget allows. Call before serving queries (the engine does,
        right after compile); any previously built device block state is
        dropped. Returns the TierStore."""
        from ..storage import ColdArena, TierStore
        if self.tier is not None:
            # re-enable (budget change): retire the old store's
            # prefetch workers before the fresh one takes over
            self.tier.close()
        arena = ColdArena(spill_dir)
        tier = TierStore(budget_bytes, arena)
        bits_on = bitprop.kernel_enabled()
        for i, b in enumerate(self.blocks):
            nb = b.n_dst * b.n_src  # int8 dense cells
            if bits_on and bitprop.eligible(b.n_dst, b.n_src):
                k_pad = -(-((b.n_src + 31) // 32) // bitprop.LANES) \
                    * bitprop.LANES
                nb += b.n_dst * k_pad * 4  # packed dual rides along
            cols = {"dst_local": np.asarray(b.dst_local, dtype=np.int32),
                    "src_local": np.asarray(b.src_local, dtype=np.int32)}
            if b.closured:
                cols["base_dst_local"] = np.asarray(
                    b.base_dst_local, dtype=np.int32)
                cols["base_src_local"] = np.asarray(
                    b.base_src_local, dtype=np.int32)
            arena.put(i, cols)
            tier.register(i, nb, b.level)
        self.tier = tier
        with _DEV_INIT_LOCK:
            self._device = {}
        tier.publish_gauges()
        return tier

    def _demand_blocks(self, seed_slots: np.ndarray,
                       q_slots: np.ndarray) -> Optional[tuple]:
        """Block indices this dispatch can possibly exercise: a block is
        demanded iff its src range is forward-reachable from the seed
        ranges AND its dst range is backward-reachable from the queried
        ranges, over the compile-retained range adjacency plus the live
        overlay pairs. Everything outside that intersection provably
        cannot influence a queried slot, so it neither uploads nor
        counts an access. None = no adjacency (hand-built graph):
        stream everything.

        The result is cached per (seed ranges, query ranges, overlay
        watermark) — the demand key is a pure function of query shape,
        so steady traffic reuses both the active set and its trace."""
        offs = self.range_offs
        if offs is None or self.range_adj is None or not len(self.blocks):
            return None
        trash = self.M

        def ranges_of(slots) -> frozenset:
            s = np.asarray(slots).ravel()
            s = s[(s >= 0) & (s < trash)]
            if not len(s):
                return frozenset()
            rid = np.searchsorted(offs, s, side="right") - 1
            return frozenset(np.unique(rid).tolist())

        seed_r = ranges_of(seed_slots)
        q_r = ranges_of(q_slots)
        key = (seed_r, q_r, self.n_delta)
        cached = self.tier.demand_cache_get(key)
        if cached is not None:
            return cached
        n_ranges = len(offs)
        fwd: list = [set() for _ in range(n_ranges)]
        back: list = [set() for _ in range(n_ranges)]
        for s, t in self.range_adj:
            fwd[s].add(t)
            back[t].add(s)
        if self.n_delta and self.delta_src is not None:
            with self._host_guard():
                ds = self.delta_src[:self.n_delta].copy()
                dt = self.delta_dst[:self.n_delta].copy()
            keep = (ds >= 0) & (ds < trash) & (dt >= 0) & (dt < trash)
            if np.any(keep):
                srid = np.searchsorted(offs, ds[keep], side="right") - 1
                drid = np.searchsorted(offs, dt[keep], side="right") - 1
                for s, t in zip(srid.tolist(), drid.tolist()):
                    fwd[s].add(t)
                    back[t].add(s)

        def close_over(starts, edges) -> set:
            seen = set(starts)
            frontier = list(starts)
            while frontier:
                nxt = []
                for r in frontier:
                    for t in edges[r]:
                        if t not in seen:
                            seen.add(t)
                            nxt.append(t)
                frontier = nxt
            return seen

        reach_f = close_over(seed_r, fwd)
        reach_b = close_over(q_r, back)
        active = tuple(
            i for i, b in enumerate(self.blocks)
            if _range_id(offs, b.src_off) in reach_f
            and _range_id(offs, b.dst_off) in reach_b)
        self.tier.demand_cache_put(key, active)
        return active

    def _stream_blocks(self, active: tuple) -> tuple:
        """Assemble the dispatch's block operand tuples, streaming cold
        demanded blocks in through the double-buffered prefetcher in
        stratification order (level L lands before level L+1). The wall
        time the dispatch actually blocks on arrivals is the miss stall
        (engine_tier_miss_stall_seconds)."""
        tier = self.tier
        hot, missing = tier.lookup(active)
        if missing:
            t0 = time.perf_counter()
            futs = tier.prefetcher.fetch(
                missing, partial(_materialize_block, self))
            for i in missing:
                payload = futs[i].result()
                hot[i] = payload
                tier.admit(i, payload)
            tier.observe_stall(time.perf_counter() - t0)
        return (tuple(hot[i][0] for i in active),
                tuple(hot[i][1] for i in active))

    def query_async(
        self,
        seed_slots: np.ndarray,  # int32 [B, 2] (subject slot, wildcard slot)
        q_slots: Optional[np.ndarray],  # int32 [Q]; None with a grid
        q_batch: Optional[np.ndarray],  # int32 [Q] batch row per query
        now: Optional[float] = None,
        max_iters: int = DEFAULT_MAX_ITERS,
        q_cache_key: Optional[tuple] = None,
        q_contiguous: Optional[bool] = None,
        q_contig_grid: Optional[tuple] = None,  # (lo, L, R): R rows x
        # one shared [lo, lo+L) window (the fused-batch shape)
        context: Optional[dict] = None,  # request caveat context
        cav_req: Optional[tuple] = None,  # pre-encoded request arrays
        # (CompiledCaveats.encode_request) — chunked bulk callers encode
        # ONCE for the whole logical call instead of per chunk
    ) -> "QueryFuture":
        """Dispatch the fixpoint without blocking.

        The device→host copy is started eagerly (``copy_to_host_async``) so
        concurrent queries overlap their readback latency — the analog of
        the reference overlapping its LookupResources RPC with the upstream
        kube request (pkg/authz/responsefilterer.go:165-183). Call
        ``.result()`` on the returned future to wait.

        ``q_cache_key``: callers whose (q_slots, q_batch) are a pure
        function of the slot layout (list-filter masks read a type's whole
        permission range every time) pass a key so the padded device
        arrays are built and uploaded ONCE per compiled-graph generation —
        at the 100k-object scale that upload is ~0.5MB per query.
        """
        d = self._dev()
        B = seed_slots.shape[0]
        # a grid names its own Q: its caller builds no [Q] arrays
        Q = len(q_slots) if q_slots is not None \
            else q_contig_grid[1] * q_contig_grid[2]
        B_pad = _next_bucket(B, 1)
        Q_pad = _next_bucket(Q, 8)
        seeds = np.full((B_pad, 2), self.M, dtype=np.int32)
        seeds[:B] = seed_slots
        # Contiguous-window queries (the list-filter shape: one type's full
        # permission range) take a dynamic_slice extraction instead of the
        # latency-bound random gather, and ship two scalars instead of a
        # padded ~0.5MB index upload. Two forms:
        #   rows=1: one window (``q_contiguous=True`` is a caller promise —
        #           the engine builds ``off + arange(n)`` itself; None
        #           auto-detects);
        #   rows=R: the fused-batch grid (``q_contig_grid=(lo, L, R)``
        #           promise from engine/batcher.py) — R rows reading the
        #           SAME window, q order = row-major concatenation.
        # Slice lengths are exact (static, but unconstrained), so the
        # window always lies inside the state tensor (no clamp) and the
        # flat output needs no padding re-map; jit re-specialization is
        # bounded because callers repeat the same few (off, n) windows.
        Mp_state = (self.M // LANE + 1) * LANE
        contig = q_contiguous
        if contig is None and q_contig_grid is None and Q >= 1024:
            # auto-detect only LARGE windows: q_contig_len is a static
            # jit arg, so every distinct detected length is its own XLA
            # compile — a caller whose small query sets happen to be
            # consecutive must not accumulate per-length recompiles it
            # never asked for. Big windows are where the gather hurts,
            # and their lengths (full type ranges) barely vary. Explicit
            # promises (the engine/batcher) are always honored.
            contig = (int(q_slots[-1]) - int(q_slots[0]) == Q - 1
                      and not np.any(q_batch != q_batch[0])
                      and np.array_equal(
                          q_slots,
                          q_slots[0] + np.arange(Q, dtype=np.int64)))
        run_kwargs = {}
        qs_dev = qb_dev = None
        if q_contig_grid is not None:
            lo, L, R = q_contig_grid
            if (Q == L * R and 0 < L and 0 < R <= B_pad
                    and lo + L <= Mp_state):
                qs_dev = np.int32(lo)
                qb_dev = np.int32(0)
                run_kwargs["q_contig_len"] = L
                run_kwargs["q_contig_rows"] = R
        elif contig and Q and int(q_slots[0]) + Q <= Mp_state:
            qs_dev = np.int32(q_slots[0])
            qb_dev = np.int32(q_batch[0])
            run_kwargs["q_contig_len"] = Q
        if qs_dev is None:
            cached = d.get(("q", q_cache_key)) if q_cache_key else None
            if cached is not None:
                qs_dev, qb_dev = cached
            else:
                qs = np.full(Q_pad, self.M, dtype=np.int32)
                qs[:Q] = q_slots
                qb = np.zeros(Q_pad, dtype=np.int32)
                qb[:Q] = q_batch
                qs_dev, qb_dev = jnp.asarray(qs), jnp.asarray(qb)
                if q_cache_key:
                    # bounded: each entry pins megabytes of device arrays;
                    # evict the oldest rather than grow with key cardinality
                    q_keys = [k for k in d if isinstance(k, tuple)
                              and k and k[0] == "q"]
                    if len(q_keys) >= 32:
                        d.pop(q_keys[0], None)
                    d[("q", q_cache_key)] = (qs_dev, qb_dev)
        now_abs = time.time() if now is None else now
        now_rel = np.float32(now_abs - self.base_time)
        # request caveat context -> tiny per-caveat arrays riding the
        # dispatch (scalars + known flags per declared parameter); the
        # VM merges them under the tuple contexts ON DEVICE, so the
        # caveat mask lands in the same dispatch as the fixpoint
        cav = self.caveats
        if cav is not None and cav.metas:
            if cav_req is None:
                cav_req, _ = cav.encode_request(context, now_abs)
        else:
            cav_req = ()
        # per-mode jitted entry (force_mode flips between dispatches must
        # hit their own trace); built lazily under the shared cache lock
        mk = semiring.resolved_mode()
        if self.tier is None:
            run = d.get(("run", mk))
            if run is None:
                run = _jit_run_for(self)
                d[("run", mk)] = run
            blocks_arg = d["blocks"]
            bits_arg = d["blocks_bits"]
        else:
            # tiered dispatch: demand-set the blocks, stream in the cold
            # ones, and run the per-(mode, active-set) trace. The run
            # key depends only on query shape — residency churn between
            # dispatches reuses this exact entry (zero recompiles).
            active = self._demand_blocks(seed_slots, q_slots)
            if active is None:
                active = tuple(range(len(self.blocks)))
            blocks_arg, bits_arg = self._stream_blocks(active)
            rk = ("run", mk, active)
            run = d.get(rk)
            if run is None:
                run = _jit_run_for(self, active)
                d[rk] = run
        seed_mode = self.seed_mode(B_pad)
        if seed_mode == "lookup":
            metrics.counter("engine_seed_lookups_total").inc()
        elif seed_mode == "walk":
            metrics.counter("engine_seed_walks_total").inc()
        # the enqueue alone: the call returns once the program is handed
        # to the device's queue, not when it has run
        with tracer.stage("engine_enqueue",
                          metrics.histogram("engine_enqueue_seconds"),
                          metrics.counter("engine_enqueue_cpu_seconds_total"),
                          rows=B):
            # seeds ride the jit call as a host array: jax folds the
            # transfer into the dispatch instead of a separate device_put
            # round trip
            out, converged, iters, n_push, cav_missing = run(
                blocks_arg, bits_arg, d["src"], d["dst"], d["exp"],
                d["cav"], d["ptr"],
                d["dsrc"], d["ddst"], d["dexp"], d["dcav"],
                d["cav_static"], cav_req,
                seeds, qs_dev, qb_dev,
                now_rel, np.float32(self.spmm_crossover),
                max_iters=max_iters, **run_kwargs,
            )
        try:
            out.copy_to_host_async()
            converged.copy_to_host_async()
            # iters feeds the fixpoint-iterations metric in the engine's
            # result finalizer; without the prefetch that int() is a
            # synchronous device roundtrip per query
            iters.copy_to_host_async()
            n_push.copy_to_host_async()
            cav_missing.copy_to_host_async()
        except AttributeError:  # non-jax array backends in tests
            pass
        return QueryFuture(out, converged, iters, Q, max_iters,
                           cav_missing, n_push)

    def query(
        self,
        seed_slots: np.ndarray,
        q_slots: np.ndarray,
        q_batch: np.ndarray,
        now: Optional[float] = None,
        max_iters: int = DEFAULT_MAX_ITERS,
    ) -> np.ndarray:
        """Run the fixpoint synchronously; returns bool [Q]."""
        return self.query_async(
            seed_slots, q_slots, q_batch, now=now, max_iters=max_iters
        ).result()

    def core_edges(self) -> int:
        """What every trip of ``_run``'s while_loop walks again: the
        padded residual edges of level 0 plus the cells of level-0 dense
        blocks, source and destination both in a core range."""
        bounds = self._level_bounds()
        return int(bounds[self.n_pre + 1] - bounds[self.n_pre] + sum(
            b.n_dst * b.n_src for b in self.blocks if b.level == 0))

    def core_ranges(self) -> int:
        """Slot ranges at level 0: those on a cycle or between two (see
        ``_stratify``)."""
        return (0 if self.range_levels is None
                else int(np.count_nonzero(self.range_levels == 0)))

    def feeder_edges(self) -> int:
        """What the phases before the loop hold: the padded residual
        edges (walked and seeded parts) and dense-block cells of the
        feeder levels and of the entry phase."""
        bounds = self._level_bounds()
        return int(bounds[self.n_pre] - bounds[0] + sum(
            b.n_dst * b.n_src for b in self.blocks if b.level < 0))

    def feeder_ranges(self) -> int:
        """Slot ranges final before the loop starts: all that feeds a
        cycle and lies on none, roots included."""
        return (0 if self.range_levels is None
                else int(np.count_nonzero(self.range_levels < 0)))

    def seed_edges(self) -> int:
        """Edges the seeded parts hold, unpadded: found from a
        dispatch's seeds, walked only by a dispatch of too many rows."""
        return self.n_seed_edges if self._seed_layout() is not None else 0

    def walked_edges(self) -> int:
        """Padded residual edges of the walked parts: what a dispatch
        gathers and scatters whatever its seeds (the loop's part once a
        trip)."""
        bounds, seed = self._level_bounds(), self._seed_layout()
        return int(bounds[-1] - bounds[0] - (
            0 if seed is None else sum(seed.lengths(bounds))))

    def seed_fanout(self) -> int:
        """Sum of the phases' D_k: a row's seed looks up that many
        entries a dispatch, twice (subject and wildcard)."""
        seed = self._seed_layout()
        return 0 if seed is None else int(sum(seed.fanout))

    def seed_mode(self, rows: int) -> Optional[str]:
        """How a dispatch of ``rows`` rows applies the seeded parts:
        ``"lookup"`` where some phase reads its part through the table,
        ``"walk"`` where every one is walked as ordinary edges, None
        where the graph has no seeded part."""
        seed = self._seed_layout()
        if seed is None or not any(seed.fanout):
            return None
        return "lookup" if any(
            seed_looked(rows, d, n) for d, n in zip(
                seed.fanout, seed.lengths(self.res_level_bounds))
        ) else "walk"

    def hop_bytes(self, batch: int = 1) -> dict:
        """Estimated HBM traffic (bytes) for roofline reporting, split by
        the stratified schedule: ``total`` is the per-ITERATION cost of
        the cyclic core (what multiplies by the fixpoint iteration count);
        ``tail_once`` is the one-shot cost of every other phase (feeder
        levels and entry before the loop, acyclic levels after it). Streams
        counted: residual gather/segment, dense-block operands (bit-packed
        or int8 A), elementwise program passes. An estimate of bytes
        *touched* — XLA fusion can only reduce it.

        ``modes`` reports the core dense-block bytes PER SEMIRING MODE
        (ops/semiring.py) so collective-bytes baselines (ROADMAP item 1)
        can be stated per branch instead of assuming one layout:
        ``push`` streams each block's bit-packed dual (its eligible
        blocks) or the full int8 A where no dual exists; ``pull`` always
        streams the full int8 A; ``pallas`` adds the MXU kernel's
        frontier re-stream (the [b32, n_src] operand is re-read once per
        dst-tile row of the grid). ``blocks``/``total`` keep reporting
        the mode the CURRENT configuration would run (bits when the bit
        kernel is live and the batch fits, else dense)."""
        rows = self.M // LANE + 1
        Mp = rows * LANE

        def res_bytes(n):  # src+dst int32 + valid uint8 + B gathered
            return n * (4 + 4 + 1 + batch) + batch * Mp

        def bits_bytes(b):
            k0 = (b.n_src + 31) // 32
            k_pad = -(-k0 // bitprop.LANES) * bitprop.LANES
            return b.n_dst * k_pad * 4

        def push_bytes(b):
            # bit-packed dual when one exists for this batch, else the
            # push pass degrades to the dense pull stream for the block
            if batch <= bitprop.BIT_B_MAX and bitprop.eligible(
                    b.n_dst, b.n_src):
                return bits_bytes(b)
            return b.n_dst * b.n_src

        def pull_bytes(b):
            return b.n_dst * b.n_src

        def pallas_bytes(b):
            # dense MXU kernel: A streamed once + the padded frontier
            # tile re-streamed per dst-tile grid row
            if not bitprop.dense_eligible(b.n_dst, b.n_src, batch):
                return pull_bytes(b)
            b32 = -(-batch // bitprop.SUBLANE) * bitprop.SUBLANE
            return b.n_dst * b.n_src \
                + b32 * b.n_src * (b.n_dst // bitprop.MXU_TILE)

        def block_bytes(b):
            use_bits = (batch <= bitprop.BIT_B_MAX
                        and bitprop.kernel_enabled())
            if use_bits and bitprop.eligible(b.n_dst, b.n_src):
                return bits_bytes(b)
            return b.n_dst * b.n_src

        bounds = self.res_level_bounds
        if bounds is None:
            n_core = (len(self.res_idx) if self.res_idx is not None
                      else self.n_edges)
            tail_res = 0
        else:
            n_core = bounds[self.n_pre + 1] - bounds[self.n_pre]
            # the seeded parts are read from the seeds, 2 * batch runs
            # of at most the phase's fan-out, not walked
            tail_res = self.walked_edges() - n_core \
                + 2 * batch * self.seed_fanout()
        delta = self._delta_pad() * (4 + 4 + 1 + batch)
        core_res = res_bytes(n_core) + delta
        core_blk = [b for b in self.blocks if b.level == 0]
        core_blocks = sum(block_bytes(b) for b in core_blk)
        core_prog = sum(2 * p.size * batch for p in self.programs
                        if p.level == 0)
        tail = (res_bytes(tail_res) if tail_res else 0) \
            + sum(block_bytes(b) for b in self.blocks if b.level) \
            + sum(2 * p.size * batch for p in self.programs if p.level) \
            + (self.n_pre + self.n_levels) \
            * (delta + 2 * batch * Mp)  # merges + delta
        return {"residual": core_res, "blocks": core_blocks,
                "programs": core_prog, "tail_once": tail,
                "total": core_res + core_blocks + core_prog,
                "modes": {
                    "push": sum(push_bytes(b) for b in core_blk),
                    "pull": sum(pull_bytes(b) for b in core_blk),
                    "pallas": sum(pallas_bytes(b) for b in core_blk),
                }}


def _materialize_block(cg: "CompiledGraph", i: int) -> tuple:
    """Build one dense block's device arrays from its cold-arena COO
    (falling back to the compiled host meta for blocks the arena never
    saw), minus the dead-ledger cells — the streaming twin of the loop
    in ``_dev_build``. Runs on prefetch worker threads; reads only
    per-revision-immutable state (arena payloads are replaced whole by
    recloses, dead_pairs is a frozen watermark view)."""
    bm = cg.blocks[i]
    tier = cg.tier
    dl = sl = None
    if tier is not None and tier.arena.has(i):
        coo = tier.arena.get(i)
        dl, sl = coo["dst_local"], coo["src_local"]
    if dl is None:
        dl, sl = bm.dst_local, bm.src_local
    dl = np.asarray(dl)
    sl = np.asarray(sl)
    dl_dead, sl_dead = cg._dead_cells(bm)
    A = jnp.zeros((bm.n_dst, bm.n_src), dtype=jnp.int8) \
        .at[jnp.asarray(dl), jnp.asarray(sl)].set(1)
    if len(dl_dead):
        A = A.at[jnp.asarray(dl_dead), jnp.asarray(sl_dead)].set(0)
    bits = None
    if bitprop.kernel_enabled() and bitprop.eligible(bm.n_dst, bm.n_src):
        bits_h = bitprop.pack_block_host(dl, sl, bm.n_dst, bm.n_src)
        if len(dl_dead):
            np.bitwise_and.at(
                bits_h, (dl_dead, sl_dead // 32),
                ~(np.uint32(1) << (sl_dead % 32).astype(np.uint32)))
        bits = jnp.asarray(bits_h)
    return (A, bits)


def _tier_apply_update(cg: "CompiledGraph", blocks_host: list,
                       reclose: dict, block_cells: dict) -> None:
    """Incremental edits against tiered blocks (incremental_update's
    device section when a TierStore owns placement). Re-closed blocks
    re-encode their arena payload from the new closure COO and, when
    resident, rebuild their device arrays whole; plain cell edits apply
    the same functional scatter/bit-word updates the resident path uses
    — but only to hot payloads (cold blocks need nothing: the next
    materialization reads the updated host meta and dead ledger).
    Every touched block is PINNED hot until the next compaction fold
    rebuilds the graph — and with it a fresh TierStore, which is how
    pins reset."""
    tier = cg.tier
    for b in reclose:
        bm = blocks_host[b]
        tier.arena.put(b, {
            "dst_local": np.asarray(bm.dst_local, dtype=np.int32),
            "src_local": np.asarray(bm.src_local, dtype=np.int32),
            "base_dst_local": np.asarray(bm.base_dst_local,
                                         dtype=np.int32),
            "base_src_local": np.asarray(bm.base_src_local,
                                         dtype=np.int32)})
        if tier.peek(b) is not None:
            A = jnp.zeros((bm.n_dst, bm.n_src), dtype=jnp.int8) \
                .at[jnp.asarray(bm.dst_local),
                    jnp.asarray(bm.src_local)].set(1)
            bits = None
            if bitprop.kernel_enabled() and bitprop.eligible(
                    bm.n_dst, bm.n_src):
                bits = jnp.asarray(bitprop.pack_block_host(
                    bm.dst_local, bm.src_local, bm.n_dst, bm.n_src))
            tier.replace(b, (A, bits))
        tier.pin(b)
    for b, cells in block_cells.items():
        payload = tier.peek(b)
        if payload is not None:
            A, bits = payload
            dl = np.fromiter((c[0] for c in cells), dtype=np.int32,
                             count=len(cells))
            sl = np.fromiter((c[1] for c in cells), dtype=np.int32,
                             count=len(cells))
            vals = np.fromiter(cells.values(), dtype=np.int8,
                               count=len(cells))
            A = A.at[dl, sl].set(vals)
            if bits is not None:
                # group per (row, word): multiple cells can share a
                # packed word, and a gather-modify-scatter with
                # duplicate indices would drop updates
                agg: dict = {}
                for (dli, sli), v in cells.items():
                    k = (dli, sli // 32)
                    setm, clrm = agg.get(k, (0, 0))
                    bit = 1 << (sli % 32)
                    if v:
                        setm |= bit
                    else:
                        clrm |= bit
                    agg[k] = (setm, clrm)
                rows = np.array([k[0] for k in agg], dtype=np.int32)
                words = np.array([k[1] for k in agg], dtype=np.int32)
                sets = np.array([v[0] for v in agg.values()],
                                dtype=np.uint32)
                clrs = np.array([v[1] for v in agg.values()],
                                dtype=np.uint32)
                cur = bits[rows, words]
                bits = bits.at[rows, words].set(
                    (cur & jnp.asarray(~clrs)) | jnp.asarray(sets))
            tier.replace(b, (A, bits))
        tier.pin(b)


def tier_maintain(cg: "CompiledGraph") -> None:
    """Placement sweep, run off the serving path (the Compactor's
    worker thread — engine/compaction.py is the placement engine):
    decay access recency, demote blocks that went cold while the store
    is over headroom, and eagerly re-materialize pinned-but-cold blocks
    so the write path never pays a stream-in for its own overlay's
    dense cells. Publishes the occupancy gauges afterwards."""
    tier = getattr(cg, "tier", None)
    if tier is None:
        return
    for i in tier.place():
        tier.admit(i, _materialize_block(cg, i), pinned=True)
    tier.publish_gauges()


@dataclass
class QueryFuture:
    """A dispatched reachability query. ``result()`` blocks and validates
    convergence. ``iterations()`` (valid after result/convergence check)
    reports how many fixpoint hops the query ran — the analog of SpiceDB's
    dispatch depth, exported to the metrics registry by the engine.
    ``caveats_missing()`` is the number of caveat instances that resolved
    to the missing-context tri-state this dispatch (denied fail-closed;
    feeds ``engine_caveat_denied_missing_context_total``).
    ``push_steps()`` is how many of those hops took the semiring PUSH
    branch (ops/semiring.py; the rest took pull) — the per-iteration
    mode telemetry behind ``engine_semiring_push_steps_total``."""

    _out: object
    _converged: object
    _iters: object
    _q: int
    _max_iters: int
    _cav_missing: object = None
    _push: object = None

    def result(self) -> np.ndarray:
        if not bool(self._converged):
            raise ConvergenceError(
                f"reachability did not converge within {self._max_iters} "
                "iterations (graph deeper than the dispatch budget)"
            )
        return np.asarray(self._out)[: self._q]

    def iterations(self) -> int:
        return int(self._iters)

    def push_steps(self) -> int:
        return 0 if self._push is None else int(self._push)

    def caveats_missing(self) -> int:
        return 0 if self._cav_missing is None else int(self._cav_missing)


def _apply_program(cg: CompiledGraph, V, programs=None):
    """Recompute permission slot ranges from their expressions (all of
    cg's programs, or an explicit subset). V is [B, rows, LANE]; every
    range offset/size is a multiple of LANE, so a range is a row-aligned
    static slice along axis 1."""

    def ev(expr: Expr, p: _PermProgram):
        if isinstance(expr, Nil):
            return jnp.zeros((V.shape[0], p.size // LANE, LANE),
                             dtype=V.dtype)
        if isinstance(expr, (RelationRef, Arrow)):
            off = p.leaf_off[expr]
            return jax.lax.dynamic_slice_in_dim(
                V, off // LANE, p.size // LANE, axis=1)
        if isinstance(expr, Union):
            out = ev(expr.operands[0], p)
            for e in expr.operands[1:]:
                out = out | ev(e, p)
            return out
        if isinstance(expr, Intersect):
            out = ev(expr.operands[0], p)
            for e in expr.operands[1:]:
                out = out & ev(e, p)
            return out
        if isinstance(expr, Exclude):
            return ev(expr.base, p) & (ev(expr.subtract, p) ^ 1)
        raise TypeError(f"unknown expr {expr!r}")

    for p in (cg.programs if programs is None else programs):
        V = jax.lax.dynamic_update_slice_in_dim(
            V, ev(p.expr, p), p.dst_off // LANE, axis=1)
    return V


def _seed_base(cg: CompiledGraph, seeds):
    """Seed the [B, rows, LANE] state from subject/wildcard slot pairs and
    run the permission programs once. The single source of the layout
    invariants (rows = M/LANE + trash row; trash row stays 0 so unknown
    subjects seed nothing) — both the single-chip and sharded fixpoints
    build their base here."""
    B = seeds.shape[0]
    rows = cg.M // LANE + 1  # + trash row (slots M .. M+LANE-1)
    Mp = rows * LANE
    brange = jnp.arange(B, dtype=jnp.int32)
    base = jnp.zeros((B, Mp), dtype=jnp.uint8)
    base = base.at[brange, seeds[:, 0]].max(1)
    base = base.at[brange, seeds[:, 1]].max(1)
    base = base.at[:, cg.M:].set(0)
    return _apply_program(cg, base.reshape(B, rows, LANE))


def apply_level_once(meta: "RunMeta", prop_level, V, baseflat, k: int):
    """One-shot phase ``k`` of the schedule (any phase but the loop):
    propagate the phase's edges, blocks and the delta overlay from ``V``,
    whose sources are final by now; write the result into the phase's
    slot ranges and no other (a replacing merge, so finalized levels are
    untouched and no dense masks exist anywhere); run the phase's
    programs. ``prop_level(V, k) -> (prop [B, Mp], is_push)`` is the
    caller's hop (the mesh joins its shards inside it), so the
    single-chip and the sharded fixpoint share this step letter for
    letter. Returns ``(V, is_push)``."""
    B, rows = V.shape[0], V.shape[1]
    Mp = rows * LANE
    with jax.named_scope(meta.scope(k)):
        prop, is_push = prop_level(V, k)
        propb = prop | baseflat
        Vflat = V.reshape(B, Mp)
        for off, size in meta.windows(k):
            Vflat = jax.lax.dynamic_update_slice(
                Vflat,
                jax.lax.dynamic_slice(propb, (0, off), (B, size)),
                (0, off))
        return _apply_program(meta, Vflat.reshape(B, rows, LANE),
                              meta.programs_at(k)), is_push


def _seed_runs(cg: "RunMeta", k: int, seeds, ptr):
    """``(start, length)`` [B, 2] of the runs that a dispatch's seed
    slots own in phase k's seeded part (SeedLayout): length 0 for a slot
    in no ``__self`` range with runs there (a userset subject, the trash
    slot of an unknown one) or with no edge in the phase."""
    at = jnp.zeros_like(seeds)
    owns = jnp.zeros(seeds.shape, dtype=jnp.bool_)
    for off, size, base in cg.seed.ranges[k + cg.n_pre]:
        inside = (seeds >= off) & (seeds < off + size)
        at = jnp.where(inside, seeds - off + base, at)
        owns = owns | inside
    return ptr[at], jnp.where(owns, ptr[at + 1] - ptr[at], 0)


def _run(cg: "RunMeta", blocks, blocks_bits, src, dst, exp_rel, cav, ptr,
         dsrc, ddst, dexp, dcav, cav_static, cav_req,
         seeds, q_slots, q_batch, now_rel, crossover, *,
         max_iters: int, q_contig_len: int = 0, q_contig_rows: int = 1):
    """The jitted stratified fixpoint. V layout: [B, rows, LANE] uint8 —
    the slot space rides the lane axis so a B=1 query streams exactly M
    bytes per elementwise pass instead of a lane-padded 128x that; slot s
    lives at (s // LANE, s % LANE) and every range is row-aligned.

    Schedule (see _stratify, RunMeta): an edge is walked again only if
    its source can still change.

    1. feeder levels ``-n_pre .. -2``, once each, in order: the ranges
       that feed a cycle and lie on none are final before the loop;
    2. the entry phase ``-1``, once: the core's in-edges whose source is
       a feeder range, merged into the core's ranges, and the core's
       programs over their (final) feeder leaves. The state this leaves
       is the loop's start and its constant term;
    3. the while_loop over the CORE (level 0): only edges whose source
       and destination are both core ranges;
    4. each level ``1 .. n_levels`` after it, once: their ranges'
       in-edges all live at their level and their sources are final.

    A graph without a cycle has no feeders and no core: it runs 3 over
    an empty slice (the convergence probe) and 4. In kube-shaped graphs
    4 keeps the dominant per-pod blocks out of the loop entirely; where a
    cycle exists 1 and 2 keep out of it nearly every edge that reaches it.

    Every hop is ONE call into the masked-semiring primitive
    (ops/semiring.propagate) — the same primitive the shard_map body
    uses — over the walked part of the phase's slice, and where the
    slice has a seeded part (SeedLayout: edges out of a ``__self`` range)
    one into ``semiring.propagate_seeded``, which reads the runs of this
    dispatch's seed slots from ``ptr``; a dispatch of too many rows for
    that (``seed_looked``, from the shapes the trace sees) walks the
    seeded part with the overlay. Both read the ``(exp > now) ∧
    cav_ok[row]`` edge-activation mask
    computed exactly once per dispatch (semiring.edge_activation) and
    fused into the multiply. The caveat VM evaluates every instance's
    tri-state once up front when the graph carries caveat instances
    (cg.cav_rows > 1); caveated edges never enter dense blocks
    (compile_graph routes them residual, like expiring edges).
    ``crossover`` is the traced push/pull threshold (CompiledGraph
    .spmm_crossover): the per-iteration mode branch is a lax.cond on
    traced occupancy, so neither tuning nor the runtime flip
    re-specializes."""
    B = seeds.shape[0]
    rows = cg.M // LANE + 1  # + trash row (slots M .. M+LANE-1)
    Mp = rows * LANE
    if cg.cav_rows > 1:
        from ..caveats.vm import eval_caveats

        cav_ok, cav_missing = eval_caveats(
            cg.caveats, cav_static, cav_req, cg.cav_rows)
    else:
        cav_ok = None
        cav_missing = jnp.int32(0)
    # fused edge activation, once per dispatch (not per hop/level)
    act = semiring.edge_activation(exp_rel, now_rel, cav, cav_ok)
    dact = semiring.edge_activation(dexp, now_rel, dcav, cav_ok)
    base = _seed_base(cg, seeds)
    baseflat = base.reshape(B, Mp)
    core_progs = cg.programs_at(0)

    def prop_level(V, k):
        Vflat = V.reshape(B, Mp)
        lo, mid, hi = cg.parts(k)
        looked = cg.seed_looked(k, B)
        unsorted = (dsrc, ddst, dact)
        if mid < hi and not looked:
            # too many rows for the table to be the shorter way: the
            # seeded part (sorted by source) is walked with the overlay,
            # the pass that expects no order
            unsorted = tuple(
                jnp.concatenate([a, b[mid:hi]])
                for a, b in zip(unsorted, (src, dst, act)))
        occ = semiring.frontier_occupancy(Vflat)
        prop, is_push = semiring.propagate(
            cg.blocks, blocks, blocks_bits, src[lo:mid], dst[lo:mid],
            act[lo:mid], *unsorted, Vflat, occ, crossover,
            level=k, mode=cg.spmm_mode)
        if looked:
            start, length = _seed_runs(cg, k, seeds, ptr)
            prop = semiring.propagate_seeded(
                prop, start, length, dst[mid:hi], act[mid:hi],
                cg.seed.fanout[k + cg.n_pre])
        return prop, is_push

    # jax.named_scope names the phases for HLO dumps and xprof (op_name
    # metadata only: the computation is the same). No one-shot phase may
    # be skipped — incremental delta edges can target any level and only
    # that phase's re-application establishes their values.
    V, n_push = base, jnp.int32(0)
    for k in range(-cg.n_pre, 0):
        V, is_push = apply_level_once(cg, prop_level, V, baseflat, k)
        n_push = n_push + is_push
    # what the loop ORs into every trip: the seeds, the feeders' final
    # values and the entry edges' contribution (``base`` itself where
    # nothing feeds the core)
    const = V

    def step(V):
        prop, is_push = prop_level(V, 0)
        return _apply_program(
            cg, prop.reshape(B, rows, LANE) | const, core_progs), is_push

    def cond(state):
        V, prev_changed, it, _ = state
        return prev_changed & (it < max_iters)

    def body(state):
        V, _, it, n_push = state
        V2, is_push = step(V)
        return V2, jnp.any(V2 != V), it + 1, n_push + is_push

    with jax.named_scope(cg.scope(0)):
        V, still_changing, iters, n_push = jax.lax.while_loop(
            cond, body, (V, jnp.bool_(True), 0, n_push))
    for k in range(1, cg.n_levels + 1):
        V, is_push = apply_level_once(cg, prop_level, V, baseflat, k)
        n_push = n_push + is_push
    # still_changing at loop exit means we hit max_iters before convergence;
    # surface it so the host can raise instead of silently denying
    with jax.named_scope("readout"):
        if q_contig_len:
            # contiguous query window (q_slots/q_batch are scalars: start
            # slot and start row): a dynamic_slice streams the window at
            # HBM rate, where the general fancy-index gather below is
            # latency-bound random access — on a v5e chip that gather was
            # 31% of the whole query's device time for the list-filter
            # shape (which always reads one type's full, contiguous
            # permission range). q_contig_rows > 1 is the fused-batch grid
            # (engine/batcher.py: R same-window rows); [R, L] row-major
            # flatten is exactly the concatenated per-row query order, so
            # no re-mapping is needed.
            out = jax.lax.dynamic_slice(
                V.reshape(B, Mp), (q_batch, q_slots),
                (q_contig_rows, q_contig_len)
            ).reshape(q_contig_rows * q_contig_len).astype(jnp.bool_)
        else:
            out = V.reshape(B, Mp)[q_batch, q_slots].astype(jnp.bool_)
    return out, jnp.logical_not(still_changing), iters, n_push, cav_missing


# ---------------------------------------------------------------------------
# Compilation: (schema, snapshot) -> CompiledGraph
# ---------------------------------------------------------------------------


def _topo_permissions(defn) -> list[str]:
    """Topologically order a definition's permissions by their intra-type
    RelationRef dependencies (cross-type and cyclic deps are resolved by the
    outer fixpoint; within a pass we just avoid reading an obviously stale
    sibling where possible)."""
    deps: dict[str, set] = {}
    for name, perm in defn.permissions.items():
        refs = set()

        def walk(e):
            if isinstance(e, RelationRef) and e.name in defn.permissions:
                refs.add(e.name)
            elif isinstance(e, (Union, Intersect)):
                for o in e.operands:
                    walk(o)
            elif isinstance(e, Exclude):
                walk(e.base)
                walk(e.subtract)

        walk(perm.expr)
        deps[name] = refs
    out: list[str] = []
    seen: set = set()

    def visit(n, path):
        if n in seen or n in path:
            return
        for d in sorted(deps[n]):
            visit(d, path | {n})
        seen.add(n)
        out.append(n)

    for n in sorted(deps):
        visit(n, set())
    return out


def compile_graph(schema: Schema, snapshot: Snapshot,
                  delta_capacity: int = DELTA_CAPACITY) -> CompiledGraph:
    """Compile a store snapshot into device-ready slot-space form.

    Everything here is vectorized numpy over the snapshot's columnar arrays
    — no per-relationship Python loops — so 10M-edge graphs compile in
    seconds on the host.

    ``delta_capacity`` preallocates the fixed-capacity delta overlay
    (``incremental_update``): its length is part of the jit signature, so
    overlay appends never re-specialize, and running out of slots is a
    compaction/back-pressure signal (engine/compaction.py) instead of a
    growth event.
    """
    types_in = snapshot.types
    rels_in = snapshot.relations
    cols = snapshot.cols

    # ---- slot layout ----
    slot_offset: dict[tuple, int] = {}
    type_sizes: dict[str, int] = {}
    arrow_terms: dict[tuple, list[Arrow]] = {}  # (type, perm) -> arrows in order
    off = 0
    for tname in sorted(schema.definitions):
        d = schema.definitions[tname]
        tid = types_in.lookup(tname)
        n = len(snapshot.objects[tid]) if tid is not None and tid in snapshot.objects \
            else 2
        # bucket-pad the per-type object space so slot offsets (and thus the
        # jit signature) stay stable as objects are interned within a
        # bucket; the LANE floor keeps every slot range row-aligned in the
        # [B, rows, LANE] state layout
        n = _next_bucket(max(n, 2), LANE)
        type_sizes[tname] = n
        slot_offset[(tname, SELF_REL)] = off
        off += n
        for rname in sorted(d.relations):
            slot_offset[(tname, rname)] = off
            off += n
        for pname in sorted(d.permissions):
            arrows: list[Arrow] = []

            def collect(e):
                if isinstance(e, Arrow):
                    arrows.append(e)
                elif isinstance(e, (Union, Intersect)):
                    for o in e.operands:
                        collect(o)
                elif isinstance(e, Exclude):
                    collect(e.base)
                    collect(e.subtract)

            collect(d.permissions[pname].expr)
            arrow_terms[(tname, pname)] = arrows
            for k in range(len(arrows)):
                slot_offset[(tname, f"__arrow_{pname}_{k}")] = off
                off += n
        for pname in sorted(d.permissions):
            slot_offset[(tname, pname)] = off
            off += n
    M = off

    # ---- store-id -> offset lookup tables ----
    n_st = len(types_in)
    n_sr = len(rels_in)
    self_off = np.full(n_st + 1, -1, dtype=np.int64)
    rel_off = np.full((n_st + 1, n_sr + 1), -1, dtype=np.int64)  # writable rels
    relperm_off = np.full((n_st + 1, n_sr + 1), -1, dtype=np.int64)
    for tname, d in schema.definitions.items():
        tid = types_in.lookup(tname)
        if tid is None:
            continue
        self_off[tid] = slot_offset[(tname, SELF_REL)]
        for rname in d.relations:
            rid = rels_in.lookup(rname)
            if rid is not None:
                rel_off[tid, rid] = slot_offset[(tname, rname)]
                relperm_off[tid, rid] = slot_offset[(tname, rname)]
        for pname in d.permissions:
            rid = rels_in.lookup(pname)
            if rid is not None:
                relperm_off[tid, rid] = slot_offset[(tname, pname)]

    # ---- edges ----
    srcs: list[np.ndarray] = []
    dsts: list[np.ndarray] = []
    exps: list[np.ndarray] = []
    cavs: list[np.ndarray] = []
    base_time = time.time()
    exp_rel_all = (cols.exp - base_time).astype(np.float32)

    # caveat instance table: one VM row per distinct (caveat, context)
    # pair among live tuples; every edge derived from a caveated tuple
    # (direct / userset / arrow alike) carries its instance row so the
    # traced fixpoint can gate it on the per-dispatch tri-state
    from ..caveats.vm import build_caveat_table

    cav_ids = cols.cav.astype(np.int64)
    used_cavs = np.unique(cav_ids[cav_ids > 0])
    caveat_table = build_caveat_table(
        getattr(schema, "caveat_defs", None) or {},
        getattr(snapshot, "caveat_instances", None) or [("", "")],
        used_cavs)
    cav_row_all = caveat_table.inst_row[cav_ids]

    rt = cols.rt.astype(np.int64)
    st = cols.st.astype(np.int64)
    rl = cols.rl.astype(np.int64)
    srl = cols.srl.astype(np.int64)

    dst_all = rel_off[rt, rl] + cols.rid  # -1-based stays negative
    dst_valid = rel_off[rt, rl] >= 0

    # direct tuples (includes wildcard subjects: wildcard object index is 1)
    m = (srl == 0) & dst_valid & (self_off[st] >= 0)
    srcs.append(self_off[st[m]] + cols.sid[m])
    dsts.append(dst_all[m])
    exps.append(exp_rel_all[m])
    cavs.append(cav_row_all[m])

    # userset tuples: src is the subject's (type, relation|permission) slot
    us_off = relperm_off[st, srl]
    m = (srl != 0) & dst_valid & (us_off >= 0) & (cols.sid != WILDCARD_IDX)
    srcs.append(us_off[m] + cols.sid[m])
    dsts.append(dst_all[m])
    exps.append(exp_rel_all[m])
    cavs.append(cav_row_all[m])

    # arrow term edges
    arrow_maps: list = []
    for (tname, pname), arrows in arrow_terms.items():
        if not arrows:
            continue
        tid = types_in.lookup(tname)
        if tid is None:
            continue
        for k, a in enumerate(arrows):
            ts_id = rels_in.lookup(a.tupleset)
            if ts_id is None:
                continue
            term_off = slot_offset[(tname, f"__arrow_{pname}_{k}")]
            # per-subject-type offset of the arrow target
            tgt_off = np.full(n_st + 1, -1, dtype=np.int64)
            d = schema.definitions[tname]
            for asub in d.relations[a.tupleset].allowed:
                if asub.relation:
                    continue  # arrows walk concrete subjects only
                sub_tid = types_in.lookup(asub.type)
                if sub_tid is None:
                    continue
                if schema.definitions[asub.type].relation_or_permission(a.target):
                    tgt_off[sub_tid] = slot_offset[(asub.type, a.target)]
            arrow_maps.append((int(tid), int(ts_id), term_off, tgt_off))
            m = (
                (rt == tid) & (rl == ts_id) & (srl == 0)
                & (tgt_off[st] >= 0) & (cols.sid != WILDCARD_IDX)
            )
            srcs.append(tgt_off[st[m]] + cols.sid[m])
            dsts.append(term_off + cols.rid[m])
            exps.append(exp_rel_all[m])
            cavs.append(cav_row_all[m])

    src = np.concatenate(srcs) if srcs else np.empty(0, dtype=np.int64)
    dst = np.concatenate(dsts) if dsts else np.empty(0, dtype=np.int64)
    exp = np.concatenate(exps) if exps else np.empty(0, dtype=np.float32)
    cav = np.concatenate(cavs) if cavs else np.empty(0, dtype=np.int64)

    order = native.sort_perm(dst)
    if order is None:
        order = np.argsort(dst, kind="stable")
    src, dst, exp, cav = src[order], dst[order], exp[order], cav[order]

    n_edges = len(src)
    E_pad = _next_bucket(max(n_edges, 1))
    src_p = np.full(E_pad, M, dtype=np.int32)
    dst_p = np.full(E_pad, M, dtype=np.int32)
    exp_p = np.full(E_pad, -np.inf, dtype=np.float32)
    cav_p = np.zeros(E_pad, dtype=np.int32)
    src_p[:n_edges] = src
    dst_p[:n_edges] = dst
    exp_p[:n_edges] = exp
    cav_p[:n_edges] = cav

    # ---- elementwise programs ----
    programs: list[_PermProgram] = []
    for tname in sorted(schema.definitions):
        d = schema.definitions[tname]
        n = type_sizes[tname]
        for pname in _topo_permissions(d):
            arrows = arrow_terms[(tname, pname)]
            leaf_off: dict = {}
            arrow_seen = 0

            # loop vars bound as defaults: the closure is invoked within
            # this iteration, but the explicit binding keeps it correct
            # even if it ever escapes (flake8-bugbear B023)
            def resolve(e, tname=tname, pname=pname):
                nonlocal arrow_seen
                if isinstance(e, RelationRef):
                    leaf_off[e] = slot_offset[(tname, e.name)]
                elif isinstance(e, Arrow):
                    # nth arrow occurrence maps to its own term range
                    leaf_off[e] = slot_offset[
                        (tname, f"__arrow_{pname}_{arrow_seen}")
                    ]
                    arrow_seen += 1
                elif isinstance(e, (Union, Intersect)):
                    for o in e.operands:
                        resolve(o)
                elif isinstance(e, Exclude):
                    resolve(e.base)
                    resolve(e.subtract)

            expr = d.permissions[pname].expr
            resolve(expr)
            programs.append(
                _PermProgram(slot_offset[(tname, pname)], n, expr, leaf_off)
            )

    # ---- stratification + dense/residual split (single-chip path) ----
    # ranges: every (type, rel) slot range, ascending; edges map to a
    # (dst range, src range) pair by binary search
    range_items = sorted(slot_offset.items(), key=lambda kv: kv[1])
    offs = np.asarray([o for _, o in range_items], dtype=np.int64)
    sizes = np.asarray(
        [type_sizes[t] for (t, _), _ in range_items], dtype=np.int64
    )
    if n_edges:
        dst_rid = np.searchsorted(offs, dst, side="right") - 1
        src_rid = np.searchsorted(offs, src, side="right") - 1
    else:
        dst_rid = src_rid = np.empty(0, dtype=np.int64)

    # Dense-pair decisions come BEFORE stratification: a dense SELF-pair
    # (recursive relation like `group#member: group#member`) with no
    # expiring edges gets its block replaced by the reflexive-transitive
    # closure, which satisfies the self-dependency in ONE application —
    # so _stratify may peel the range instead of iterating it with the
    # core. Nested-group workloads (BASELINE config 3) then converge
    # without core iterations at all.
    dense_sel: dict[int, np.ndarray] = {}  # pair key -> edge indices
    res_parts: list[np.ndarray] = []
    closure_rids: set[int] = set()
    closure_coo: dict[int, tuple] = {}  # self range id -> closured COO
    if n_edges:
        never_expires = exp == np.inf
        # caveated edges ride the residual path like expiring edges:
        # their activation is a per-dispatch condition, and a dense
        # (let alone closured) block cell cannot carry one
        special = (~never_expires) | (cav != 0)
        key = dst_rid * len(offs) + src_rid
        key = np.where(~special, key, -1)
        uniq, inv, counts = np.unique(key, return_inverse=True,
                                      return_counts=True)
        expiring_pairs = (set(np.unique(
            dst_rid[special] * len(offs) + src_rid[special]
        ).tolist()) if special.any() else set())
        for ui, (k, cnt) in enumerate(zip(uniq.tolist(), counts.tolist())):
            sel = np.flatnonzero(inv == ui)
            if k < 0:
                res_parts.append(sel)
                continue
            d_rid, s_rid = divmod(k, len(offs))
            n_dst, n_src = int(sizes[d_rid]), int(sizes[s_rid])
            cells = n_dst * n_src
            if (cnt < DENSE_MIN_EDGES or cells > DENSE_MAX_CELLS
                    or (cells > DENSE_MIN_CELLS
                        and cnt / cells < DENSE_MIN_DENSITY)):
                res_parts.append(sel)
                continue
            dense_sel[k] = sel
            if d_rid == s_rid and k not in expiring_pairs:
                coo = _closure_pairs(
                    (dst[sel] - offs[d_rid]).astype(np.int32),
                    (src[sel] - offs[s_rid]).astype(np.int32), n_dst)
                if coo is not None:
                    closure_rids.add(d_rid)
                    closure_coo[d_rid] = coo

    # range pairs the schema admits (mirrors the three edge extractions
    # above: direct, userset, arrow), data or no data
    admitted: set = set()
    for tname, d in schema.definitions.items():
        for rname, r in d.relations.items():
            for a in r.allowed:
                feeder = (a.type, a.relation or SELF_REL)
                if feeder in slot_offset:
                    admitted.add((feeder, (tname, rname)))
        for pname in d.permissions:
            for k, arrow in enumerate(arrow_terms[(tname, pname)]):
                for a in d.relations[arrow.tupleset].allowed:
                    if not a.relation and (a.type, arrow.target) \
                            in slot_offset:
                        admitted.add(((a.type, arrow.target),
                                      (tname, f"__arrow_{pname}_{k}")))
    level_map, n_levels, n_pre = _stratify(
        offs, src_rid, dst_rid, programs,
        ignore_self=frozenset(closure_rids),
        potential=frozenset(
            (_range_id(offs, slot_offset[s]), _range_id(offs, slot_offset[t]))
            for s, t in admitted))

    # Retain the range-granularity adjacency for tiered demand closure:
    # every (src range, dst range) pair the FULL edge set crosses (the
    # rids above were computed before the dense split, so block edges
    # are covered) plus each program's leaf -> permission edges. Self
    # pairs stay in — unlike _stratify, reachability wants them.
    adj_pairs: set = set()
    if n_edges:
        for p in np.unique(
                src_rid.astype(np.int64) * len(offs) + dst_rid).tolist():
            adj_pairs.add(divmod(p, len(offs)))
    for p in programs:
        p_rid = _range_id(offs, p.dst_off)
        for off_ in set(p.leaf_off.values()):
            adj_pairs.add((_range_id(offs, off_), p_rid))
    range_adj = tuple(sorted(adj_pairs))
    range_levels = np.asarray(
        [level_map[r] for r in range(len(offs))], dtype=np.int32)
    if closure_rids:
        # Levels are DOUBLED so a peeled closured range gets two ordered
        # phases at its position in the topo order: odd phase 2k-1
        # applies the range's in-edges (+ normal blocks + programs) and
        # merges; even phase 2k applies only closure blocks, whose
        # diagonal re-gathers the freshly merged values and whose closure
        # cells complete every multi-hop chain. Feeder levels double the
        # same way below zero (-2, -3, .. become the odd phases -3, -5,
        # .. with the closure phases -2, -4, .. after each; the entry
        # phase stays -1). Without closured blocks the schedule keeps
        # its original single phase per level.
        range_levels = 2 * range_levels - np.sign(range_levels)
        n_levels *= 2
        n_pre = max(2 * n_pre - 1, 0)
    for p in programs:
        p.level = int(range_levels[_range_id(offs, p.dst_off)])

    blocks: list[_BlockMeta] = []
    if n_edges:
        # an edge is applied at its destination's level; into a core
        # range from a feeder range it is an entry edge, walked once
        # before the loop (phase -1) and not on every trip
        edge_level = np.where(
            (range_levels[dst_rid] == 0) & (range_levels[src_rid] < 0),
            -1, range_levels[dst_rid])
        for k, sel in dense_sel.items():
            d_rid, s_rid = divmod(k, len(offs))
            lvl = int(edge_level[sel[0]])  # one range pair, one phase
            if d_rid == s_rid and d_rid in closure_rids:
                dl, sl = closure_coo[d_rid]
                blocks.append(_BlockMeta(
                    dst_off=int(offs[d_rid]), n_dst=int(sizes[d_rid]),
                    src_off=int(offs[s_rid]), n_src=int(sizes[s_rid]),
                    dst_local=dl, src_local=sl,
                    level=lvl + 1 if lvl else 0, closured=True,
                    base_dst_local=(dst[sel] - offs[d_rid]).astype(np.int32),
                    base_src_local=(src[sel] - offs[s_rid]).astype(np.int32),
                ))
            else:
                blocks.append(_BlockMeta(
                    dst_off=int(offs[d_rid]), n_dst=int(sizes[d_rid]),
                    src_off=int(offs[s_rid]), n_src=int(sizes[s_rid]),
                    dst_local=(dst[sel] - offs[d_rid]).astype(np.int32),
                    src_local=(src[sel] - offs[s_rid]).astype(np.int32),
                    level=lvl,
                ))
    res_idx = (np.sort(np.concatenate(res_parts)) if res_parts
               else np.empty(0, dtype=np.int64))

    # padded host residual views, one slice per phase in order of
    # execution (res_level_bounds: feeder levels, entry, core, levels).
    # A slice is two parts, each padded to its own power-of-two bucket so
    # the bounds (part of the jit signature) stay stable as edge counts
    # drift between recompiles: the *walked* part, dst-sorted for
    # segment_max's indices_are_sorted, then the *seeded* part
    # (SeedLayout): the edges that start in a ``__self`` range, sorted by
    # (src, dst) under a row pointer per source, but for the sources
    # whose run is longer than the phase's fan-out, which stay walked
    n_res = len(res_idx)
    n_slices = n_pre + n_levels + 1
    self_rid = np.asarray([r == SELF_REL for (_, r), _ in range_items])
    walked: list = []  # per phase: indices into the edge arrays
    seeded: list = []
    fanout: list = []
    res_phase = edge_level[res_idx] + n_pre if n_res else res_idx
    for i in range(n_slices):
        sel = res_idx[res_phase == i]
        own = self_rid[src_rid[sel]]
        d_k = 0
        if own.any():
            _, inv, runs = np.unique(src[sel[own]], return_inverse=True,
                                     return_counts=True)
            d_k = _seed_fanout(runs)
            own[np.flatnonzero(own)[runs[inv] > d_k]] = False
        w, t = sel[~own], sel[own]
        walked.append(w[np.argsort(dst[w], kind="stable")])
        seeded.append(t[np.lexsort((dst[t], src[t]))])
        fanout.append(d_k if len(t) else 0)
    bounds, seed_starts = [0], []
    for w, t in zip(walked, seeded):
        seed_starts.append(bounds[-1] + _next_bucket(max(len(w), 1)))
        bounds.append(seed_starts[-1]
                      + (_next_bucket(len(t)) if len(t) else 0))
    res_level_bounds = tuple(bounds)
    res_src = np.full(res_level_bounds[-1], M, dtype=np.int32)
    res_dst = np.full(res_level_bounds[-1], M, dtype=np.int32)
    res_exp = np.full(res_level_bounds[-1], -np.inf, dtype=np.float32)
    res_cav = np.zeros(res_level_bounds[-1], dtype=np.int32)
    ptrs: list = []  # every window's row pointers, end to end
    seed_ranges: list = []
    n_ptr = 0
    for lo, mid, w, t in zip(bounds, seed_starts, walked, seeded):
        for at, sel in ((lo, w), (mid, t)):
            res_src[at:at + len(sel)] = src_p[sel]
            res_dst[at:at + len(sel)] = dst_p[sel]
            res_exp[at:at + len(sel)] = exp_p[sel]
            res_cav[at:at + len(sel)] = cav_p[sel]
        # CSR by source, a window per ``__self`` range with a run here:
        # slot ``off + j`` owns ptr[base + j] .. ptr[base + j + 1] of the
        # part, where its sources first reach ``off + j`` and the next
        wins = []
        for rid in np.unique(src_rid[t]).tolist():
            wins.append((int(offs[rid]), int(sizes[rid]), n_ptr))
            ptrs.append(np.searchsorted(
                src[t], offs[rid] + np.arange(sizes[rid] + 1)))
            n_ptr += int(sizes[rid]) + 1
        seed_ranges.append(tuple(wins))
    res_ptr = (np.concatenate(ptrs) if ptrs
               else np.zeros(1)).astype(np.int32)

    # fixed-capacity delta overlay: preallocated trash-padded segments the
    # incremental path appends into IN PLACE (watermarked by n_delta /
    # n_dead on each revision view); sized once so the jit signature never
    # moves under write churn
    # NO gauge writes here: engine_delta_occupancy belongs to the engine
    # layer (_publish_graph_gauges / incremental_update) — a background
    # compactor's off-path compile must not zero the LIVE overlay's
    # occupancy reading while it is full and shedding
    cap = max(int(delta_capacity), 64)
    return CompiledGraph(
        schema=schema,
        revision=snapshot.revision,
        base_time=base_time,
        M=M,
        slot_offset=slot_offset,
        type_sizes=type_sizes,
        src=src_p,
        dst=dst_p,
        exp_rel=exp_p,
        n_edges=n_edges,
        programs=programs,
        blocks=blocks,
        res_idx=res_idx,
        delta_src=np.full(cap, M, dtype=np.int32),
        delta_dst=np.full(cap, M, dtype=np.int32),
        delta_exp=np.full(cap, -np.inf, dtype=np.float32),
        delta_cav=np.zeros(cap, dtype=np.int32),
        n_delta=0,
        dead_pairs=None,
        n_dead=0,
        delta_cap=cap,
        delta_pos={},
        dead_set=set(),
        dead_buf=np.zeros((cap, 2), dtype=np.int64),
        host_lock=threading.Lock(),
        block_codes={},
        res_src=res_src,
        res_dst=res_dst,
        res_exp=res_exp,
        res_cav=res_cav,
        caveats=caveat_table,
        res_level_bounds=res_level_bounds,
        n_levels=n_levels,
        n_pre=n_pre,
        range_levels=range_levels,
        seed=SeedLayout(tuple(seed_starts), tuple(fanout),
                        tuple(seed_ranges)),
        res_ptr=res_ptr,
        n_seed_edges=sum(len(t) for t in seeded),
        range_offs=offs,
        block_index={(b.dst_off, b.src_off): i
                     for i, b in enumerate(blocks)},
        self_off=self_off,
        rel_off=rel_off,
        relperm_off=relperm_off,
        arrow_maps=arrow_maps,
        range_adj=range_adj,
    )


# ---------------------------------------------------------------------------
# Incremental updates: (CompiledGraph, write delta) -> CompiledGraph
# ---------------------------------------------------------------------------


def _edges_for_tuple(cg: CompiledGraph, store, rel):
    """Slot-space (src, dst) edges for one relationship, mirroring the
    vectorized extraction in compile_graph (direct / userset / arrow).
    Returns None when the tuple cannot be mapped onto the existing slot
    layout (new type/relation id beyond the compile-time tables, or an
    object interned past its type's padded bucket) — the caller falls back
    to a full recompile."""
    tid = store.types.lookup(rel.resource_type)
    stid = store.types.lookup(rel.subject_type)
    rl = store.relations.lookup(rel.relation)
    srl = store.relations.lookup(rel.subject_relation or "")
    if None in (tid, stid, rl, srl):
        return None
    # the lookup tables carry a defensive +1 slack row, so the covered id
    # range is [0, len-1): an id interned AFTER compile lands on the slack
    # row's -1 and must force a recompile, not read as "no edge"
    n_types = len(cg.self_off) - 1
    n_rels = cg.rel_off.shape[1] - 1
    if tid >= n_types or stid >= n_types or rl >= n_rels or srl >= n_rels:
        return None  # interned after compile: tables don't cover it
    r_objs = store.objects.get(tid)
    s_objs = store.objects.get(stid)
    rid = r_objs.lookup(rel.resource_id) if r_objs else None
    sid = s_objs.lookup(rel.subject_id) if s_objs else None
    if rid is None or sid is None:
        return None
    if rid >= cg.type_sizes.get(rel.resource_type, 0) \
            or sid >= cg.type_sizes.get(rel.subject_type, 0):
        return None  # object bucket overflow: slot layout must grow
    dst_off = int(cg.rel_off[tid, rl])
    if dst_off < 0:
        return []  # not a writable relation slot (compile drops these too)
    dst = dst_off + rid
    edges: list[tuple[int, int]] = []
    if srl == 0:
        so = int(cg.self_off[stid])
        if so >= 0:  # wildcard subjects included (index 1)
            edges.append((so + sid, dst))
    elif sid != WILDCARD_IDX:
        uo = int(cg.relperm_off[stid, srl])
        if uo >= 0:
            edges.append((uo + sid, dst))
    if srl == 0 and sid != WILDCARD_IDX:
        for a_tid, ts_id, term_off, tgt_off in cg.arrow_maps:
            if a_tid == tid and ts_id == rl and int(tgt_off[stid]) >= 0:
                edges.append((int(tgt_off[stid]) + sid, term_off + rid))
    return edges


def _level_order_ok(cg: CompiledGraph, src: int, dst: int) -> bool:
    """A delta edge is compatible with the frozen stratification iff its
    source finalizes before (or iterates with) its destination: both in
    the iterated core, or level(src) < level(dst). Levels are the order
    of execution (feeders below zero, the core at 0, the rest above), so
    feeder -> core and core -> level are in order and core -> feeder is
    not. Violations — a first-ever dependency direction between two
    ranges — need a re-stratifying full recompile."""
    if cg.range_levels is None:
        return True  # unstratified graph: single full fixpoint
    offs = cg.range_offs
    ls = int(cg.range_levels[_range_id(offs, src)])
    ld = int(cg.range_levels[_range_id(offs, dst)])
    return (ls == 0 and ld == 0) or ls < ld


def _pair_block(cg: CompiledGraph, src: int, dst: int):
    """Dense-block index covering a (src, dst) slot pair, or None."""
    if not cg.block_index:
        return None
    offs = cg.range_offs
    d_rid = int(np.searchsorted(offs, dst, side="right")) - 1
    s_rid = int(np.searchsorted(offs, src, side="right")) - 1
    return cg.block_index.get((int(offs[d_rid]), int(offs[s_rid])))


def _res_positions(cg: CompiledGraph, src: int, dst: int) -> list[int]:
    """Base-residual positions holding the (src, dst) edge. A phase's
    walked part is ordered by dst and its seeded part by (src, dst), so
    each is binary-searched by its own key and the key's run scanned for
    the other end."""
    bounds = cg.res_level_bounds or (0, len(cg.res_dst))
    seed = cg._seed_layout()
    out: list[int] = []
    for k in range(len(bounds) - 1):
        mid = bounds[k + 1] if seed is None else seed.starts[k]
        for b0, b1, keys, key, ends, end in (
                (bounds[k], mid, cg.res_dst, dst, cg.res_src, src),
                (mid, bounds[k + 1], cg.res_src, src, cg.res_dst, dst)):
            lo = b0 + int(np.searchsorted(keys[b0:b1], key, side="left"))
            hi = b0 + int(np.searchsorted(keys[b0:b1], key, side="right"))
            if lo < hi:
                out.extend(
                    (lo + np.flatnonzero(ends[lo:hi] == end)).tolist())
    return out



def _block_base_codes(cg: CompiledGraph, b: int) -> np.ndarray:
    """Sorted ``dst_local * n_src + src_local`` codes of a closured
    block's BASE edges, cached on the shared ``block_codes`` dict (keyed
    by block index, validated against the block object's identity so a
    re-close invalidates the entry). O(block log block) once per base
    edge-set, O(log block) per membership probe after that."""
    bm = cg.blocks[b]
    cache = cg.block_codes
    if cache is not None:
        ent = cache.get(b)
        if ent is not None and ent[0] == id(bm):
            return ent[1]
    codes = np.sort(bm.base_dst_local.astype(np.int64) * bm.n_src
                    + bm.base_src_local)
    if cache is not None:
        cache[b] = (id(bm), codes)
    return codes


def incremental_update(cg: CompiledGraph, records, new_revision: int,
                       store) -> Optional[CompiledGraph]:
    """Apply a write delta — ``records`` is an ordered list of
    ``(is_delete, Relationship)`` derived from the store watch log since
    cg.revision — to a compiled graph without recompiling.

    The delta overlay is a FIXED-CAPACITY device-resident COO tail shared
    (host side) by every incremental descendant of one compiled base:

    - a new edge takes the next free overlay slot — a host write plus a
      functional ``.at[slot].set`` on the resident device arrays, O(write)
      regardless of how much delta has accumulated since the last
      compaction (the previous implementation rebuilt a dict + re-sorted
      + re-uploaded the whole segment per write);
    - a re-touch/delete of an overlay edge updates its slot's expiration
      in place (slots are reused, so touch/delete churn on the same pairs
      never grows occupancy);
    - a touched/deleted BASE edge is killed where it lives (residual
      expiration forced to -inf, dense-block cell cleared) and recorded
      once in the append-only dead ledger (``dead_buf``/``dead_set``) for
      ShardedGraph replay and lazy device builds.

    Capacity is static — part of the jit signature — so appends NEVER
    re-specialize; running out of slots (or dead-ledger room) declines the
    update, which the engine turns into compaction back-pressure rather
    than a growth event. Returns a new CompiledGraph view sharing the
    overlay (per-revision immutability lives in the n_delta/n_dead
    watermarks and the functional device arrays), or None when the delta
    cannot be expressed against the frozen layout — every decline is
    counted in ``engine_graph_incremental_fallback_total{reason}``.

    Keeps the fully-consistent-read contract (reference
    pkg/authz/check.go:42-44) at O(write) instead of O(graph) per write.
    """
    if cg.res_src is None or cg.self_off is None or cg.delta_pos is None \
            or cg.delta_src is None or cg.dead_buf is None \
            or cg.delta_cav is None:
        _fallback("unstratified")
        return None
    if len(records) > MAX_DELTA_RECORDS:
        _fallback("overflow")
        return None

    delta_pos = cg.delta_pos
    dead_set = cg.dead_set

    # ---- plan (NO mutation): a fallback must leave the shared overlay
    # exactly as it was — the caller recompiles from a fresh snapshot and
    # in-flight queries keep serving the untouched current view ----------
    appends: dict = {}  # pair -> (exp, cav row) for a new overlay slot
    updates: dict = {}  # overlay slot -> (new exp, cav row | None=keep)
    res_kill: list[int] = []
    block_cells: dict[int, dict[tuple[int, int], int]] = {}
    new_dead: list[tuple[int, int]] = []
    dead_seen: set = set()
    # closured blocks whose BASE edges lost pairs: re-closed wholesale
    reclose: dict[int, set] = {}  # block idx -> local (dst, src) pairs
    # new (caveat, context) instance rows reserved this batch — applied
    # to the shared tables only at commit (caveats/vm.py plan_append)
    planned_inst: dict = {}

    for is_delete, relationship in records:
        edges = _edges_for_tuple(cg, store, relationship)
        if edges is None:
            _fallback("layout")
            return None
        cav_row = 0
        if not is_delete and relationship.caveat:
            # conditional grant: resolve (caveat, context) to a VM
            # instance row — an existing one, or a reserved spare row in
            # the caveat's padded bucket. No tape for the caveat (first
            # caveated tuple ever) or no spare row: the instance tables
            # must re-shape, which is a full recompile.
            table = cg.caveats
            ctx = relationship.caveat_context or ""
            row = (table.lookup_row(relationship.caveat, ctx)
                   if table is not None else None)
            if row is None and table is not None:
                row = table.plan_append(relationship.caveat, ctx,
                                        planned_inst)
            if row is None:
                _fallback("caveat")
                return None
            cav_row = row
        if not is_delete:
            for src, dst in edges:
                if relationship.expiration is not None \
                        or relationship.caveat:
                    b_ = _pair_block(cg, src, dst)
                    if b_ is not None and cg.blocks[b_].closured:
                        # a touch attaching an expiration (or a caveat)
                        # de-qualifies the pair from closure entirely
                        # (conditional/expiring edges must ride the
                        # residual path — a derived closure cell would
                        # serve the grant unconditionally). Classified
                        # BEFORE the level-order check: a closured
                        # self-block lifts its range out of the iterated
                        # core, so the generic check would fire first
                        # and miscount this as an inversion.
                        _fallback("closured-expiry"
                                  if relationship.expiration is not None
                                  else "closured-caveat")
                        return None
                if not _level_order_ok(cg, src, dst):
                    # the new edge would invert the frozen stratification
                    # (e.g. a first-ever dependency creating a cycle
                    # across levels): re-stratify via a full recompile
                    _fallback("stratification-inversion")
                    return None
        for src, dst in edges:
            pair = (src, dst)
            b = _pair_block(cg, src, dst)
            bm = cg.blocks[b] if b is not None else None
            if bm is not None and bm.closured:
                # (expiration-attaching touches on closured pairs already
                # fell back in the pre-classification loop above)
                if is_delete:
                    # closure cells are DERIVED reachability — clearing
                    # one cell would leave multi-hop products of the
                    # deleted edge alive (over-allow) and could kill
                    # cells still justified by alternative paths
                    # (under-allow). Instead RE-CLOSE the block from its
                    # base edges minus the deleted pair, O(block); the
                    # pair must NOT enter the dead ledger/block_cells —
                    # the recomputed closure is the sole truth.
                    dl_ = int(dst - bm.dst_off)
                    sl_ = int(src - bm.src_off)
                    codes = _block_base_codes(cg, b)
                    code = dl_ * bm.n_src + sl_
                    p_ = int(np.searchsorted(codes, code))
                    if p_ < len(codes) and codes[p_] == code:
                        reclose.setdefault(b, set()).add((dl_, sl_))
                    # overlay copy (delta-only or re-added): killing the
                    # slot is the rest of the delete
                    slot = delta_pos.get(pair)
                    if slot is not None:
                        updates[slot] = (float("-inf"), None)
                    appends.pop(pair, None)
                    continue
            # invalidate everywhere the BASE edge may live (once per pair
            # across the base's whole incremental lifetime — the dead
            # ledger makes the kill idempotent and the host arrays are
            # mutated in place, so an already-dead pair costs nothing):
            # dense-block cell cleared, residual expiration forced stale,
            # and the pair recorded so ShardedGraph can replay the kill
            if pair not in dead_set and pair not in dead_seen:
                dead_seen.add(pair)
                new_dead.append(pair)
                if bm is not None:
                    block_cells.setdefault(b, {})[
                        (dst - bm.dst_off, src - bm.src_off)] = 0
                res_kill.extend(_res_positions(cg, src, dst))
            slot = delta_pos.get(pair)
            if is_delete:
                if slot is not None:
                    updates[slot] = (float("-inf"), None)
                appends.pop(pair, None)
                continue
            # adds (including re-touches of block-covered pairs) always
            # land in the overlay — one ledger for both the single-chip
            # and sharded consumers; base copies are only ever cleared.
            # The caveat row rides the slot alongside the expiration:
            # a touch may attach, replace, or strip the condition.
            exp_rel = (np.inf if relationship.expiration is None
                       else relationship.expiration - cg.base_time)
            if slot is not None:
                updates[slot] = (float(exp_rel), cav_row)
            else:
                appends[pair] = (float(exp_rel), cav_row)

    n_app = len(appends)
    if cg.n_delta + n_app > cg.delta_cap \
            or cg.n_dead + len(new_dead) > len(cg.dead_buf):
        _fallback("overflow")
        return None

    blocks_host = cg.blocks
    if reclose:
        blocks_host = list(cg.blocks)
        for b, pairs in reclose.items():
            nb = blocks_host[b].reclosed(pairs)
            if nb is None:  # closure overflow: re-stratify instead
                _fallback("overflow")
                return None
            blocks_host[b] = nb
        if cg.block_codes is not None:
            for b in reclose:
                cg.block_codes.pop(b, None)

    # ---- apply: in-place host mutation under host_lock. Descendant
    # views see the appended slots via their n_delta watermark; an OLDER
    # revision that lazily builds device state afterwards may observe
    # newer writes — fully-consistent reads only promise at-least-as-
    # fresh, so that is correct (and rare: device state initializes on
    # the first query after compile) ------------------------------------
    app_items = list(appends.items())
    n0 = cg.n_delta
    nd0 = cg.n_dead
    with cg.host_lock:
        for i, ((s, t), (ex, cv)) in enumerate(app_items):
            slot = n0 + i
            cg.delta_src[slot] = s
            cg.delta_dst[slot] = t
            cg.delta_exp[slot] = ex
            cg.delta_cav[slot] = cv
            delta_pos[(s, t)] = slot
        for slot, (ex, cv) in updates.items():
            cg.delta_exp[slot] = ex
            if cv is not None:
                cg.delta_cav[slot] = cv
        if res_kill:
            cg.res_exp[np.asarray(res_kill, dtype=np.int64)] = -np.inf
        for j, (s, t) in enumerate(new_dead):
            cg.dead_buf[nd0 + j, 0] = s
            cg.dead_buf[nd0 + j, 1] = t
        dead_set.update(new_dead)
        # reserved caveat-instance rows land in the shared host tables
        # (same commit discipline as the overlay slots)
        inst_dev = (cg.caveats.apply_appends(planned_inst)
                    if planned_inst else [])
    n_delta2 = n0 + len(app_items)
    n_dead2 = nd0 + len(new_dead)
    metrics.gauge("engine_delta_occupancy").set(n_delta2)

    # ---- device state: functional O(write) updates against the current
    # resident arrays — published into the NEW view only, so concurrent
    # queries against older revisions keep their immutable arrays. If the
    # base never initialized single-chip device state (mesh engines query
    # through ShardedGraph instead), don't force it here: a later lazy
    # _dev_locked builds correctly from the updated host arrays ----------
    old = cg._device
    d = {}
    if old:
        d = dict(old)
        if app_items:
            ai = np.arange(n0, n0 + len(app_items), dtype=np.int64)
            d["dsrc"] = old["dsrc"].at[ai].set(np.asarray(
                [p[0] for p, _ in app_items], dtype=np.int32))
            d["ddst"] = old["ddst"].at[ai].set(np.asarray(
                [p[1] for p, _ in app_items], dtype=np.int32))
        if app_items or updates:
            ui = np.asarray(
                [n0 + i for i in range(len(app_items))]
                + list(updates.keys()), dtype=np.int64)
            uv = np.asarray(
                [ex for _, (ex, _) in app_items]
                + [ex for ex, _ in updates.values()],
                dtype=np.float32)
            d["dexp"] = d["dexp"].at[ui].set(uv)
        cav_slots = [n0 + i for i in range(len(app_items))] \
            + [slot for slot, (_, cv) in updates.items()
               if cv is not None]
        cav_vals = [cv for _, (_, cv) in app_items] \
            + [cv for _, cv in updates.values() if cv is not None]
        if cav_slots:
            d["dcav"] = d["dcav"].at[
                np.asarray(cav_slots, dtype=np.int64)].set(
                np.asarray(cav_vals, dtype=np.int32))
        if inst_dev and d.get("cav_static"):
            # new instance rows: O(row) functional column writes on the
            # resident context tables, published into this view only
            cs = list(d["cav_static"])
            for ci, local, cols_ in inst_dev:
                sce, scv, sck, lle, llv, lhe, lhv, lk = cols_
                ent = dict(cs[ci])
                ent["ce"] = ent["ce"].at[:, local].set(sce)
                ent["cv"] = ent["cv"].at[:, local].set(scv)
                ent["ck"] = ent["ck"].at[:, local].set(sck)
                ent["loe"] = ent["loe"].at[:, :, local].set(lle)
                ent["lov"] = ent["lov"].at[:, :, local].set(llv)
                ent["hie"] = ent["hie"].at[:, :, local].set(lhe)
                ent["hiv"] = ent["hiv"].at[:, :, local].set(lhv)
                ent["lk"] = ent["lk"].at[:, local].set(lk)
                ent["real"] = ent["real"].at[local].set(True)
                cs[ci] = ent
            d["cav_static"] = tuple(cs)
        if res_kill:
            d["exp"] = old["exp"].at[np.asarray(
                res_kill, dtype=np.int64)].set(-np.inf)
        if (block_cells or reclose) and cg.tier is None:
            blocks_dev = list(old["blocks"])
            bits_dev = list(old["blocks_bits"])
            for b in reclose:
                # re-closed block: fresh device matrix scattered from the
                # new closure COO (uploading the pairs, not the matrix)
                bm = blocks_host[b]
                blocks_dev[b] = jnp.zeros(
                    (bm.n_dst, bm.n_src), dtype=jnp.int8
                ).at[jnp.asarray(bm.dst_local),
                     jnp.asarray(bm.src_local)].set(1)
                if bits_dev[b] is not None:
                    bits_dev[b] = jnp.asarray(bitprop.pack_block_host(
                        bm.dst_local, bm.src_local, bm.n_dst, bm.n_src))
            for b, cells in block_cells.items():
                dl = np.fromiter((c[0] for c in cells), dtype=np.int32,
                                 count=len(cells))
                sl = np.fromiter((c[1] for c in cells), dtype=np.int32,
                                 count=len(cells))
                vals = np.fromiter(cells.values(), dtype=np.int8,
                                   count=len(cells))
                blocks_dev[b] = blocks_dev[b].at[dl, sl].set(vals)
                bits = bits_dev[b]
                if bits is not None:
                    # group per (row, word): multiple cells can share a
                    # packed word, and a gather-modify-scatter with
                    # duplicate indices would drop updates
                    agg: dict[tuple[int, int], tuple[int, int]] = {}
                    for (dli, sli), v in cells.items():
                        k = (dli, sli // 32)
                        setm, clrm = agg.get(k, (0, 0))
                        bit = 1 << (sli % 32)
                        if v:
                            setm |= bit
                        else:
                            clrm |= bit
                        agg[k] = (setm, clrm)
                    rows = np.array([k[0] for k in agg], dtype=np.int32)
                    words = np.array([k[1] for k in agg], dtype=np.int32)
                    sets = np.array([v[0] for v in agg.values()],
                                    dtype=np.uint32)
                    clrs = np.array([v[1] for v in agg.values()],
                                    dtype=np.uint32)
                    cur = bits[rows, words]
                    bits_dev[b] = bits.at[rows, words].set(
                        (cur & jnp.asarray(~clrs)) | jnp.asarray(sets))
            d["blocks"] = tuple(blocks_dev)
            d["blocks_bits"] = tuple(bits_dev)
        # capacity is static, so the signature — and with it d["run"] —
        # cannot change across overlay appends

    # Tiered placement: overlay-touched blocks update through the tier
    # store instead of the resident device tuples (which are
    # placeholders). Runs regardless of whether single-chip device state
    # ever initialized — the cold arena's COO must not go stale.
    if cg.tier is not None and (block_cells or reclose):
        _tier_apply_update(cg, blocks_host, reclose, block_cells)

    return replace(
        cg,
        revision=new_revision,
        n_delta=n_delta2,
        n_dead=n_dead2,
        dead_pairs=cg.dead_buf[:n_dead2],
        blocks=blocks_host,
        _device=d,
    )

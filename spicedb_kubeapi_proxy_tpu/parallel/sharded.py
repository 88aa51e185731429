"""Sharded slot-space reachability: the multi-chip execution path.

Wraps a :class:`~spicedb_kubeapi_proxy_tpu.ops.reachability.CompiledGraph`
and runs the same fixpoint over a ``("data", "graph")`` mesh:

- the (dst-sorted) residual edge arrays are split into contiguous chunks
  along the ``graph`` axis; every chip gathers/segment-maxes over its chunk
  and the partial propagations are joined with ``lax.pmax`` over ICI — the
  sparse analog of tensor-parallel partial-sum matmuls;
- dense relation blocks ride the MXU *inside* the shard_map body: each
  block's ``A[n_dst, n_src]`` int8 matrix is sharded along the src axis
  (``P(None, "graph")``), every chip contracts its frontier column chunk
  against its A chunk, and the same pmax join ORs the partial products —
  textbook tensor parallelism with (AND, OR) in place of (*, +);
- the query batch (rows of the state tensor) is sharded along the ``data``
  axis — concurrent requests, the reference's goroutine fan-out
  (pkg/authz/check.go:77-93), each chip answering its own requests;
- the convergence test is a collective OR over both axes, fused to run
  every K propagation steps (K-step fused fixpoint, see below) so every
  chip runs the same number of steps while small-diameter graphs stop
  paying one cross-axis collective + host-visible sync per hop;
- conditional grants evaluate ON the mesh: the caveat instance tables and
  compiled VM tapes are replicated across every device (``P()``), the
  per-edge caveat rows are sharded WITH their edge segments, and the
  vectorized caveat VM (caveats/vm.py) runs once per dispatch inside the
  shard_map body — edge activation = expiration ∧ ``cav_ok[row]`` is
  computed where the edges live, so caveated graphs no longer abandon
  the mesh for the single-device path.

K-step fused convergence: the while body applies K propagation steps and
pays ONE convergence collective per block — a pmax of the K per-step
local change flags (one [K] int32 vector, the same collective count as
the old single scalar) — so the collective-OR (and the host-side
while-condition sync it implies) fires ``ceil(iters / K)`` times instead
of ``iters`` times. K derives from the compiled graph's stratification
(:func:`~...ops.reachability.convergence_fuse_steps`): stratified graphs
iterate only their small cyclic core, unstratified ones fuse more. The
iteration is monotone, so steps past the fixpoint are no-ops — fusing
trades at most K-1 wasted cheap hops for the saved collectives — and the
loop carry buffers are donated/double-buffered by the ``while_loop``
lowering (no fresh HBM per block). Because the flags are per step,
``iterations()`` reports the ACTUAL converged-at step (the number of
steps that changed anything — no longer quantized to K, so the engine's
iteration metrics see true depths); ``conv_checks()`` reports the
convergence collectives actually paid.

Propagation itself is one call per level into the masked-semiring
primitive (ops/semiring.propagate) — the SAME primitive the
single-device fixpoint uses, with the ``(exp > now) ∧ cav_ok[row]``
edge-activation mask hoisted to once per dispatch (= once per K-step
fused window) — followed by the pmax partial-product join over ICI. The
mesh keeps no bit duals, so its blocks take the MXU dense kernel or
``lax.dot_general``.

The query surface is both a *grid* (``B`` subjects x ``Q`` result slots
per subject — bulk checks and concurrent list prefilters, BASELINE config
5's shape) and the engine's flat ``query_async(seeds, q_slots, q_batch)``
form, so :class:`~spicedb_kubeapi_proxy_tpu.engine.engine.Engine` can
route every check/lookup through the mesh unchanged (``Engine(mesh=...)``
/ ``--engine-mesh``).

Incremental updates are O(delta) here too: :meth:`ShardedGraph.updated`
reuses the jitted shard_map and the resident base edge shards, applying
only the new dead-pair kills (functional expiration/block-cell updates)
and patching the small sharded delta segment in place — including the
per-slot caveat rows, and new (caveat, context) instance rows appended
into the replicated context tables' spare rows — mirroring the
single-chip incremental path instead of rebuilding and re-placing the
whole graph per write.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import tracer
from ..ops import semiring
from ..utils.metrics import metrics
from ..ops.reachability import (
    CompiledGraph,
    ConvergenceError,
    DEFAULT_MAX_ITERS,
    LANE,
    _apply_program,
    _next_bucket,
    _seed_base,
    apply_level_once,
    convergence_fuse_steps,
)


def _run_sharded(meta, block_meta, ng: int, level_edges, blocks,
                 dsrc, ddst, dexp, dcav, cav_static, cav_req,
                 seeds, q_slots, now_rel, *, max_iters: int, k_steps: int):
    """Per-device body (inside shard_map). Shapes are the LOCAL shards:
    level_edges[k + n_pre] = (src, dst, exp, cav) [E_k/ng] (per phase of
    the schedule, in order of execution, each chunk dst-sorted);
    blocks[i] [n_dst, n_src/ng];
    dsrc/ddst/dexp/dcav [D/ng] (the incremental delta segment); seeds
    [B/nd, 2]; q_slots [B/nd, Q]. ``cav_static`` (instance tables + VM
    tapes) and ``cav_req`` (request context) are REPLICATED — every chip
    evaluates the same tiny caveat VM pass and masks its own edge shard
    with the resulting validity rows. ``meta`` is a slim RunMeta (not
    the CompiledGraph — the closure must not pin host/device graph
    state).

    Same stratified schedule as the single-chip _run, read from the same
    RunMeta (feeder levels and the entry phase once before the loop, each
    through the same ``apply_level_once``), and the SAME
    masked-semiring primitive (ops/semiring.propagate, with
    ``shard=(g_idx, ng)`` so block frontiers cover only this chip's
    src-axis chunk): only the cyclic core (level 0) iterates; every
    other phase is applied once; partial propagations are joined with
    pmax over ICI after the primitive returns. Edge activation
    (expiration ∧ caveat verdict) is hoisted to once per dispatch —
    once per K-step fused window, not once per hop. The while body fuses
    ``k_steps`` propagation steps per convergence collective, pmaxing the
    K per-step change flags as one vector so the true converged-at step
    survives fusing."""
    B = seeds.shape[0]
    rows = meta.M // LANE + 1  # + trash row
    Mp = rows * LANE
    if meta.cav_rows > 1:
        from ..caveats.vm import eval_caveats

        # one VM pass per dispatch (contexts don't change mid-query);
        # replicated inputs => every chip computes identical cav_ok
        cav_ok, cav_missing = eval_caveats(
            meta.caveats, cav_static, cav_req, meta.cav_rows)
    else:
        cav_ok = None
        cav_missing = jnp.int32(0)
    # fused edge activation, computed exactly once per dispatch for the
    # delta overlay and every level's edge shard (this chip's cav-row
    # shard rides with its edges)
    dact = semiring.edge_activation(dexp, now_rel, dcav, cav_ok)
    acts = tuple(
        semiring.edge_activation(exp_rel, now_rel, cav, cav_ok)
        for _, _, exp_rel, cav in level_edges)
    no_bits = tuple(None for _ in block_meta)  # mesh blocks: matmul/Pallas
    brange = jnp.arange(B, dtype=jnp.int32)
    base = _seed_base(meta, seeds)
    baseflat = base.reshape(B, Mp)
    g_idx = jax.lax.axis_index("graph")

    def prop_level(V, k):
        Vflat = V.reshape(B, Mp)
        src, dst = level_edges[k + meta.n_pre][:2]
        prop = semiring.propagate(
            block_meta, blocks, no_bits, src, dst, acts[k + meta.n_pre],
            dsrc, ddst, dact, Vflat, level=k, shard=(g_idx, ng))
        # join partials over ICI, widened to int32 for the wire: on v5e
        # chips a uint8 pmax compares the four bytes packed in a 32-bit
        # word as ONE number and keeps the larger word whole, dropping
        # the set bytes of the other (rows 0-2 of every 4; grants denied
        # for B > 1). int32 is exact. The CPU backend reduces bytes one
        # by one, so no virtual-device run can see this: chip_smoke.py
        # --chips 4 does, and tests/test_chip_compile.py pins the
        # compiled join to s32.
        joined = jax.lax.pmax(prop.astype(jnp.int32), "graph")
        return joined.astype(jnp.uint8)

    core_progs = meta.programs_at(0)
    # before the loop: feeder levels and the entry phase, once each (see
    # ops/reachability._run); what they leave is the loop's start and
    # its constant term
    V = base
    for k in range(-meta.n_pre, 0):
        V = apply_level_once(meta, prop_level, V, baseflat, k)
    const = V

    def step(V):
        return _apply_program(
            meta, prop_level(V, 0).reshape(B, rows, LANE) | const,
            core_progs)

    def cond(state):
        _, prev_changed, it, _ = state
        return (prev_changed > 0) & (it < max_iters)

    def body(state):
        V, _, it, checks = state
        # K-step fusing: k_steps hops per convergence collective. The
        # fixpoint is monotone, so hops past convergence are no-ops.
        # Each step's LOCAL change flag is recorded and the K flags are
        # joined in ONE pmax (a [K] vector — same collective count as
        # the old scalar): fl[k] == 0 iff step k changed nothing
        # anywhere, so fl.sum() is the number of real steps this block
        # ran (monotonicity makes the flags a 1*0* prefix pattern) and
        # fl[-1] == 0 means the fixpoint is reached. Every chip sees
        # identical fl, so all agree on the loop exit.
        Vk = V
        flags = []
        for _ in range(k_steps):
            V2 = step(Vk)
            flags.append(jnp.any(V2 != Vk).astype(jnp.int32))
            Vk = V2
        fl = jax.lax.pmax(jnp.stack(flags), ("data", "graph"))
        return Vk, fl[k_steps - 1], it + fl.sum(), checks + 1

    with jax.named_scope(meta.scope(0)):
        V, still_changing, iters, checks = jax.lax.while_loop(
            cond, body, (V, jnp.int32(1), jnp.int32(0), jnp.int32(0)))
    # acyclic levels: one application each (see ops/reachability._run)
    for k in range(1, meta.n_levels + 1):
        V = apply_level_once(meta, prop_level, V, baseflat, k)
    out = V.reshape(B, Mp)[brange[:, None], q_slots].astype(jnp.bool_)
    # replicate the (tiny, bool) result over the data axis so it is fully
    # addressable on EVERY process — under a multi-host mesh a
    # data-sharded output cannot be fetched by the serving process
    out = jax.lax.all_gather(out, "data", axis=0, tiled=True)
    converged = still_changing == 0
    # fl.sum() counts CHANGING hops; the single-chip loop also pays (and
    # reports) exactly one confirming no-op hop when it converges — count
    # it here too so both futures report the same converged-at step
    iters = iters + converged.astype(jnp.int32)
    return out, converged, iters, checks, cav_missing


class ShardedQueryFuture:
    """A dispatched sharded query (grid or flat form). ``result()`` blocks
    and validates convergence; ``iterations()`` mirrors the single-chip
    QueryFuture so the engine's metrics finalizers work unchanged — it
    reports the ACTUAL converged-at step (per-step change flags survive
    the K-step fuse), so iteration metrics are no longer quantized to
    K; ``conv_checks()`` is the number of convergence collectives
    actually paid; ``caveats_missing()`` the missing-context
    instance count (fail-closed denials, counted by the engine)."""

    __slots__ = ("_out", "_converged", "_iters", "_sel", "_max_iters",
                 "_cav_missing", "_k_steps", "_checks")

    def __init__(self, out, converged, iters, sel, max_iters,
                 cav_missing=None, k_steps=1, checks=None):
        self._out = out
        self._converged = converged
        self._iters = iters
        self._sel = sel  # None (grid) | (rows, cols) flat re-map |
        # ("contig_grid", L, R) row-major window slice
        self._max_iters = max_iters
        self._cav_missing = cav_missing
        self._k_steps = max(int(k_steps), 1)
        self._checks = checks

    def result(self) -> np.ndarray:
        if not bool(self._converged):
            raise ConvergenceError(
                f"sharded reachability did not converge within "
                f"{self._max_iters} iterations"
            )
        out = np.asarray(self._out)
        if self._sel is None:
            return out
        if isinstance(self._sel[0], str):  # ("contig_grid", L, R)
            # homogeneous fused batch: flat order IS the row-major grid
            _, L, R = self._sel
            return out[:R, :L].reshape(-1)
        rows, cols = self._sel
        return out[rows, cols]

    def iterations(self) -> int:
        return int(self._iters)

    def conv_checks(self) -> int:
        """Convergence collective-ORs this query paid: one per K-step
        block, vs one per hop before fusing. Counted directly by the
        traced loop (``iterations()`` now reports true steps, so the
        old ``iters / K`` reconstruction would under-count blocks whose
        tail steps were no-ops)."""
        if self._checks is not None:
            return int(self._checks)
        return -(-int(self._iters) // self._k_steps)

    def caveats_missing(self) -> int:
        return 0 if self._cav_missing is None else int(self._cav_missing)


def _pair_keys(pairs: Optional[np.ndarray]) -> np.ndarray:
    if pairs is None or not len(pairs):
        return np.empty(0, dtype=np.int64)
    return pairs[:, 0].astype(np.int64) * (1 << 32) + pairs[:, 1]


class ShardedGraph:
    """A CompiledGraph pinned across a device mesh.

    Edge tensors and dense-block matrices are placed once with ``graph``-
    axis shardings and stay device-resident across queries; the caveat
    instance tables + VM tapes are replicated across every device; only
    seeds/queries, the (tiny) per-request caveat context, and — after
    incremental writes — the small delta/instance patches move
    host->device.

    Tiered storage scope (storage/): the mesh backend keeps EVERY block
    resident — per-dispatch demand streaming is a single-chip-path
    feature (the shard_map's operand tuple is fixed at build time). A
    tiered graph whose blocks fit the budget builds here normally and
    simply accounts all blocks hot (``TierStore.mark_sharded``); one
    that exceeds the budget never reaches this class — Engine._backend
    routes it to the single-chip streaming path and counts the decision
    in ``engine_tier_mesh_fallback_total``.
    """

    def __init__(self, cg: CompiledGraph, mesh: Mesh,
                 max_iters: int = DEFAULT_MAX_ITERS,
                 k_steps: Optional[int] = None):
        reason = self.unsupported_reason(cg)
        if reason is not None:
            # serving such a graph here would FAIL OPEN (conditional
            # edges with no per-edge rows to mask). Engine._backend
            # routes these through the single-device path; refusing
            # here keeps any other caller honest.
            raise ValueError(f"ShardedGraph cannot serve this graph: "
                             f"{reason}")
        self.cg = cg
        self.mesh = mesh
        self.max_iters = max_iters
        self.nd = mesh.shape["data"]
        self.ng = mesh.shape["graph"]
        self._edge_sh = NamedSharding(mesh, P("graph"))
        self._block_sh = NamedSharding(mesh, P(None, "graph"))
        self._repl_sh = NamedSharding(mesh, P())

        meta = cg.run_meta()
        # the raw override (None = derive per graph) is kept so updated()'s
        # full-rebuild paths preserve an explicit caller choice instead of
        # silently reverting to the derived default mid-stream
        self._k_override = k_steps
        self.k_steps = (max(int(k_steps), 1) if k_steps
                        else convergence_fuse_steps(meta))

        # the overlay host arrays (delta segment, res_exp, dead ledger,
        # caveat instance tables) are SHARED and mutated in place by
        # incremental_update — read them under the graph's host guard so
        # a racing overlay append cannot tear the snapshot this build
        # uploads
        with cg._host_guard():
            level_arrays, kept = self._host_level_edges()
            # host copies for the incremental dead-pair search (per
            # level, each dst-sorted)
            self._h_levels = level_arrays
            self._level_edges = tuple(
                tuple(jax.device_put(a, self._edge_sh) for a in quad)
                for quad in level_arrays
            )
            self._block_meta = tuple(kept)
            self._blocks = tuple(
                jax.device_put(self._block_matrix(bm), self._block_sh)
                for bm in kept
            )
            (self._dsrc, self._ddst, self._dexp, self._dcav,
             self._h_dexp, self._h_dcav) = self._delta_device(cg)
            # caveat instance tables + tapes: replicated on every device
            # (tiny next to the edge shards), plus the per-caveat
            # applied-row watermark updated() syncs spare-row appends
            # against
            cavt = cg.caveats
            if cavt is not None and cavt.metas:
                self._cav_static = cavt.device_static(
                    sharding=self._repl_sh)
                self._applied_inst = cavt.applied_rows()
            else:
                self._cav_static = ()
                self._applied_inst = ()
        if cg.tier is not None:
            # mesh placement: every materialized block is device-resident
            # for the life of this build — account it hot so the
            # occupancy gauges tell the truth under a mesh too (outside
            # the host guard: the tier store has its own lock)
            idxs = [cg.block_index.get((bm.dst_off, bm.src_off))
                    for bm in self._block_meta]
            cg.tier.mark_sharded([i for i in idxs if i is not None])
            cg.tier.publish_gauges()
        # dead pairs already folded into this build (updated() applies
        # only the new tail); _applied_delta / _h_dexp / _h_dcav let
        # updated() patch only the overlay slots that actually changed
        # instead of re-uploading the whole segment per write
        self._applied_dead = _pair_keys(cg.dead_pairs)
        self._applied_delta = cg.n_delta
        # device query-grid cache for layout-pure queries (shared across
        # updated() generations: the slot layout is incremental-invariant)
        self._qgrid: dict = {}

        if meta.n_pre + meta.n_levels + 1 != len(self._level_edges):
            raise AssertionError(
                "level edge arrays out of step with stratification")
        self._run = self._program(mesh)

    def _program(self, mesh: Mesh):
        """The jitted shard_map fixpoint of this graph over ``mesh`` (the
        serving mesh; tests/test_chip_compile.py passes a described one
        to ask the chip's compiler about the same program)."""
        fn = partial(_run_sharded, self.cg.run_meta(), self._block_meta,
                     self.ng, max_iters=self.max_iters,
                     k_steps=self.k_steps)
        # the name the device trace shows (a bare partial has none)
        fn.__name__ = fn.__qualname__ = "sdbkp_fixpoint_sharded"
        # check_vma off: the all_gather'ed result and the pmax'ed flags
        # ARE replicated, but the checker infers them varying over "data"
        return jax.jit(shard_map(
            fn, mesh=mesh, check_vma=False,
            in_specs=(
                tuple((P("graph"),) * 4 for _ in self._level_edges),
                tuple(P(None, "graph") for _ in self._block_meta),
                P("graph"), P("graph"), P("graph"), P("graph"),
                P(), P(),
                P("data", None), P("data", None), P(),
            ),
            out_specs=(P(None, None), P(), P(), P(), P()),
        ))

    @staticmethod
    def unsupported_reason(cg: CompiledGraph) -> Optional[str]:
        """Why this graph cannot run on the mesh, or ``None`` (the
        common case — caveated graphs ARE served here). The one
        genuinely unsupported shape: a caveated graph without complete
        stratified per-edge caveat rows (hand-built layouts) — its
        level arrays would carry no rows to mask, so conditional edges
        would serve unconditionally (fail open). The predicate MIRRORS
        the branches ``_host_level_edges`` actually takes: the
        ``res_idx is None or res_src is None`` whole-edge-set path
        builds zero cav rows, and a ``res_cav``/``res_src`` length
        mismatch would zero-fill — both must refuse when caveat
        instances exist (compiled graphs always set all three
        together). Engine._backend counts these in
        ``engine_caveat_mesh_fallback_total`` and keeps them on the
        single-device path."""
        cavt = getattr(cg, "caveats", None)
        if cavt is not None and getattr(cavt, "metas", ()):
            if cg.res_idx is None or cg.res_src is None \
                    or cg.res_cav is None \
                    or len(cg.res_cav) != len(cg.res_src):
                return ("caveated graph without per-edge caveat rows "
                        "(unstratified/hand-built layout)")
        return None

    # -- host-side construction ---------------------------------------------

    def _dead_set(self):
        if self.cg.dead_pairs is None or not len(self.cg.dead_pairs):
            return None
        d = self.cg.dead_pairs
        return set(zip(d[:, 0].tolist(), d[:, 1].tolist()))

    def _pad_level(self, src, dst, exp, cav):
        """Pad one level's edges with trash rows so the graph axis
        divides evenly (at least ng rows so every chip has a chunk)."""
        n = max(len(src), 1)
        n_pad = ((n + self.ng - 1) // self.ng) * self.ng
        s = np.full(n_pad, self.cg.M, dtype=np.int32)
        d = np.full(n_pad, self.cg.M, dtype=np.int32)
        e = np.full(n_pad, -np.inf, dtype=np.float32)
        c = np.zeros(n_pad, dtype=np.int32)  # pad rows: uncaveated
        s[: len(src)] = src
        d[: len(dst)] = dst
        e[: len(exp)] = exp
        c[: len(cav)] = cav
        return s, d, e, c

    def _host_level_edges(self):
        """(level_arrays, kept_blocks): per phase of the schedule, in
        order of execution (feeder levels, entry, core, levels), the
        (src, dst, exp, cav) edge arrays this mesh gathers over (base
        residual slice, its walked and its seeded part merged back into
        one dst order: every chip walks its chunk of all of it, and the
        single chip's lookup from the seeds is not used here; + folded-
        back blocks of that phase, padded to the graph axis) and the
        dense blocks that stay on the
        MXU path (src axis divisible by the graph-axis size). Folded
        block edges are never caveated (caveated edges are excluded from
        dense blocks at compile, like expiring ones), so they carry
        row 0."""
        cg = self.cg
        dead = self._dead_set()
        if cg.res_idx is None or cg.res_src is None:
            # no dense split computed: whole edge set on the segment path
            # as one core level, with dead pairs killed in place
            # (unsupported_reason refuses caveated graphs in this shape,
            # so the cav rows are all 0)
            b_src = cg.src[: cg.n_edges].astype(np.int32, copy=False)
            b_dst = cg.dst[: cg.n_edges].astype(np.int32, copy=False)
            b_exp = cg.exp_rel[: cg.n_edges].astype(np.float32, copy=True)
            b_cav = np.zeros(cg.n_edges, dtype=np.int32)
            if dead:
                for s, t in dead:
                    lo = int(np.searchsorted(b_dst, t, side="left"))
                    hi = int(np.searchsorted(b_dst, t, side="right"))
                    if lo < hi:
                        hit = lo + np.flatnonzero(b_src[lo:hi] == s)
                        b_exp[hit] = -np.inf
            return [self._pad_level(b_src, b_dst, b_exp, b_cav)], []
        kept, folded = [], []
        for bm in cg.blocks:
            if bm.n_src % self.ng == 0:
                kept.append(bm)
            else:
                folded.append(bm)
        bounds = cg.res_level_bounds or (0, len(cg.res_src))
        res_cav = cg.res_cav
        if res_cav is None or len(res_cav) != len(cg.res_src):
            res_cav = np.zeros(len(cg.res_src), dtype=np.int32)
        seed = cg._seed_layout()
        out = []
        for i, k in enumerate(range(-cg.n_pre, cg.n_levels + 1)):
            # base residual slice for the phase, carrying incremental
            # invalidations (res_exp -> -inf); its bucket padding is
            # harmless trash that sorts last
            lo, hi = bounds[i], bounds[i + 1]
            parts = [(cg.res_src[lo:hi], cg.res_dst[lo:hi],
                      cg.res_exp[lo:hi], res_cav[lo:hi])]
            for bm in folded:
                if bm.level != k:
                    continue
                e_src = (bm.src_off + bm.src_local).astype(np.int32)
                e_dst = (bm.dst_off + bm.dst_local).astype(np.int32)
                keep = self._not_dead_mask(e_src, e_dst, dead)
                n_keep = int(keep.sum())
                parts.append((
                    e_src[keep], e_dst[keep],
                    np.full(n_keep, np.inf, dtype=np.float32),
                    np.zeros(n_keep, dtype=np.int32)))
            src = np.concatenate([p[0] for p in parts])
            dst = np.concatenate([p[1] for p in parts])
            exp = np.concatenate([p[2] for p in parts])
            cav = np.concatenate([p[3] for p in parts])
            if len(parts) > 1 or (seed is not None
                                  and seed.starts[i] < hi):
                # folded edges, or a seeded part (ordered by source):
                # restore dst order
                order = np.argsort(dst, kind="stable")
                src, dst, exp, cav = (src[order], dst[order], exp[order],
                                      cav[order])
            out.append(self._pad_level(src, dst, exp, cav))
        return out, kept

    @staticmethod
    def _not_dead_mask(e_src, e_dst, dead):
        if not dead:
            return np.ones(len(e_src), dtype=bool)
        return np.fromiter(
            ((s, t) not in dead for s, t in zip(e_src.tolist(),
                                                e_dst.tolist())),
            dtype=bool, count=len(e_src))

    def _block_matrix(self, bm) -> np.ndarray:
        A = np.zeros((bm.n_dst, bm.n_src), dtype=np.int8)
        A[bm.dst_local, bm.src_local] = 1
        dl, sl = self.cg._dead_cells(bm)
        if len(dl):
            A[dl, sl] = 0
        return A

    def _delta_device(self, cg: CompiledGraph):
        """Upload the delta segment, padded so the graph axis divides.
        Returns the four device arrays plus the padded host expiration
        and caveat-row copies — updated()'s change-detection mirrors."""
        d_src, d_dst, d_exp, d_cav = cg._delta_host()
        pad = len(d_src)
        if pad % self.ng:
            pad2 = ((pad + self.ng - 1) // self.ng) * self.ng
            d_src = np.concatenate(
                [d_src, np.full(pad2 - pad, cg.M, dtype=np.int32)])
            d_dst = np.concatenate(
                [d_dst, np.full(pad2 - pad, cg.M, dtype=np.int32)])
            d_exp = np.concatenate(
                [d_exp, np.full(pad2 - pad, -np.inf, dtype=np.float32)])
            d_cav = np.concatenate(
                [d_cav, np.zeros(pad2 - pad, dtype=np.int32)])
        return (jax.device_put(d_src, self._edge_sh),
                jax.device_put(d_dst, self._edge_sh),
                jax.device_put(d_exp, self._edge_sh),
                jax.device_put(d_cav, self._edge_sh),
                np.array(d_exp, dtype=np.float32),
                np.array(d_cav, dtype=np.int32))

    # -- incremental updates -------------------------------------------------

    def updated(self, cg: CompiledGraph) -> "ShardedGraph":
        """A ShardedGraph for an incrementally-updated revision of the same
        compiled graph, reusing the jitted shard_map and resident base
        shards; falls back to a full rebuild when the shape changed (delta
        bucket growth, different blocks, full recompile)."""
        old = self.cg
        if cg is old:
            return self

        def rebuild() -> "ShardedGraph":
            # ONE spelling of the full-rebuild fallback: every early
            # return must carry the same construction-time preferences
            # (an explicit k_steps override must survive a rebuild)
            return ShardedGraph(cg, self.mesh, self.max_iters,
                                self._k_override)

        if cg.signature() != old.signature():
            return rebuild()
        # signature equality only proves JIT compatibility (shapes,
        # layout, stratification) — delta-apply is valid ONLY for
        # incremental descendants, which share their base edge arrays BY
        # OBJECT (incremental_update builds the new graph with
        # res_src=cg.res_src). A FULL recompile can coincidentally keep
        # the signature (bucket padding absorbs small edge-count changes)
        # while folding the delta into NEW base arrays — the resident
        # shards would then silently miss those edges and answer stale
        # denials.
        if not (cg.res_src is old.res_src and cg.res_dst is old.res_dst
                and cg.src is old.src and cg.dst is old.dst):
            return rebuild()
        reclosed_idx: list[int] = []
        if cg.blocks is not old.blocks:
            # a re-closed closured block (incremental membership delete)
            # keeps shape/level/flags — only its cells changed. Re-upload
            # just those matrices instead of rebuilding the whole sharded
            # state; anything else (and folded blocks, whose closure
            # edges live inside the level arrays) needs the full rebuild.
            if len(cg.blocks) != len(old.blocks):
                return rebuild()
            for i, (nb, ob) in enumerate(zip(cg.blocks, old.blocks)):
                if nb is ob:
                    continue
                same_shape = (
                    nb.dst_off == ob.dst_off and nb.n_dst == ob.n_dst
                    and nb.src_off == ob.src_off and nb.n_src == ob.n_src
                    and nb.level == ob.level and nb.closured
                    and ob.closured)
                if not same_shape or nb.n_src % self.ng:
                    return rebuild()
                reclosed_idx.append(i)
        new = object.__new__(ShardedGraph)
        new.__dict__.update(self.__dict__)
        new.cg = cg
        if reclosed_idx:
            kept_pos = {}
            pos = 0
            for i, bm in enumerate(cg.blocks):
                if bm.n_src % self.ng == 0:
                    kept_pos[i] = pos
                    pos += 1
            blocks = list(new._blocks)
            for i in reclosed_idx:
                blocks[kept_pos[i]] = jax.device_put(
                    self._block_matrix(cg.blocks[i]), self._block_sh)
            new._blocks = tuple(blocks)
        # kill base edges for dead pairs not yet applied to these shards
        keys = _pair_keys(cg.dead_pairs)
        fresh = keys[~np.isin(keys, self._applied_dead)]
        if len(fresh):
            pairs = np.stack([fresh >> 32, fresh & ((1 << 32) - 1)], axis=1)
            pos_per_level: dict[int, list] = {}
            block_cells: dict[int, list] = {}
            for s, t in pairs.tolist():
                for k, (h_src, h_dst, _, _) in enumerate(self._h_levels):
                    lo = int(np.searchsorted(h_dst, t, side="left"))
                    hi = int(np.searchsorted(h_dst, t, side="right"))
                    if lo < hi:
                        pos_per_level.setdefault(k, []).extend(
                            (lo + np.flatnonzero(
                                h_src[lo:hi] == s)).tolist())
                for i, bm in enumerate(self._block_meta):
                    if (bm.dst_off <= t < bm.dst_off + bm.n_dst
                            and bm.src_off <= s < bm.src_off + bm.n_src):
                        block_cells.setdefault(i, []).append(
                            (t - bm.dst_off, s - bm.src_off))
            if pos_per_level:
                levels = list(self._level_edges)
                for k, pos in pos_per_level.items():
                    s_dev, d_dev, e_dev, c_dev = levels[k]
                    levels[k] = (s_dev, d_dev, jax.device_put(
                        e_dev.at[np.asarray(pos, dtype=np.int64)]
                        .set(-np.inf), self._edge_sh), c_dev)
                new._level_edges = tuple(levels)
            if block_cells:
                blocks = list(self._blocks)
                for i, cells in block_cells.items():
                    dl = np.asarray([c[0] for c in cells], dtype=np.int32)
                    sl = np.asarray([c[1] for c in cells], dtype=np.int32)
                    blocks[i] = jax.device_put(
                        blocks[i].at[dl, sl].set(0), self._block_sh)
                new._blocks = tuple(blocks)
        new._applied_dead = keys
        with cg._host_guard():
            # overlay: patch ONLY the slots that changed since this
            # sharded view last synced, with functional updates on the
            # device-RESIDENT per-shard copies — an O(write) scatter
            # instead of re-uploading the whole capacity-sized segment
            # on every write (the pre-patch behavior, which made each
            # mesh write pay O(capacity) host->device traffic).
            d_src, d_dst, d_exp, d_cav = cg._delta_host()
            n = len(d_exp)
            mirror = self._h_dexp
            mirror_c = self._h_dcav
            # appended slots (src/dst assigned once, at append)...
            app = np.arange(self._applied_delta,
                            min(cg.n_delta, n), dtype=np.int64)
            # ...plus expiration re-touches of EXISTING slots
            # (touch/delete reuse their pair's slot in place)
            changed = np.flatnonzero(mirror[:n] != d_exp)
            changed = np.union1d(changed, app)
            # ...and caveat-row re-touches (a touch may attach, replace,
            # or strip the condition without moving the expiration)
            changed_c = np.union1d(
                np.flatnonzero(mirror_c[:n] != d_cav), app)
            if len(changed):
                new._h_dexp = mirror.copy()
                new._h_dexp[changed] = d_exp[changed]
                if len(app):
                    new._dsrc = jax.device_put(
                        self._dsrc.at[app].set(d_src[app]),
                        self._edge_sh)
                    new._ddst = jax.device_put(
                        self._ddst.at[app].set(d_dst[app]),
                        self._edge_sh)
                new._dexp = jax.device_put(
                    self._dexp.at[changed].set(d_exp[changed]),
                    self._edge_sh)
            if len(changed_c):
                new._h_dcav = mirror_c.copy()
                new._h_dcav[changed_c] = d_cav[changed_c]
                new._dcav = jax.device_put(
                    self._dcav.at[changed_c].set(d_cav[changed_c]),
                    self._edge_sh)
            new._applied_delta = cg.n_delta
            # caveat instance appends: incremental_update placed new
            # (caveat, context) rows into the shared host tables' spare
            # rows (append-only per caveat) — patch exactly those column
            # ranges into the REPLICATED device tables, O(new rows)
            cavt = cg.caveats
            if cavt is not None and cavt.metas and self._cav_static:
                used = cavt.applied_rows()
                if used != self._applied_inst:
                    cs = list(self._cav_static)
                    for ci, (lo, hi) in enumerate(
                            zip(self._applied_inst, used)):
                        if hi <= lo:
                            continue
                        h = cavt.hosts[ci]
                        sl = slice(lo, hi)
                        ent = dict(cs[ci])
                        ent["ce"] = ent["ce"].at[:, sl].set(h.ctx_e[:, sl])
                        ent["cv"] = ent["cv"].at[:, sl].set(h.ctx_v[:, sl])
                        ent["ck"] = ent["ck"].at[:, sl].set(h.ctx_k[:, sl])
                        ent["loe"] = ent["loe"].at[:, :, sl].set(
                            h.lo_e[:, :, sl])
                        ent["lov"] = ent["lov"].at[:, :, sl].set(
                            h.lo_v[:, :, sl])
                        ent["hie"] = ent["hie"].at[:, :, sl].set(
                            h.hi_e[:, :, sl])
                        ent["hiv"] = ent["hiv"].at[:, :, sl].set(
                            h.hi_v[:, :, sl])
                        ent["lk"] = ent["lk"].at[:, sl].set(
                            h.list_k[:, sl])
                        ent["real"] = ent["real"].at[sl].set(h.real[sl])
                        # re-pin the replicated placement explicitly: the
                        # functional update must not leave a table with a
                        # committed single-device layout
                        cs[ci] = {k2: jax.device_put(v2, self._repl_sh)
                                  for k2, v2 in ent.items()}
                    new._cav_static = tuple(cs)
                    new._applied_inst = used
        return new

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, seeds_pad: np.ndarray, grid: np.ndarray,
                  now_abs: float, cav_req: tuple):
        now_rel = np.float32(now_abs - self.cg.base_time)
        # host numpy inputs stay UNCOMMITTED: jit shards them per the
        # in_specs, which works identically whether the mesh spans one
        # process or many (a committed local array would need a reshard
        # from a non-global placement under multi-controller)
        if self.cg.seed_edges():
            # the mesh walks the seeded parts (_host_level_edges)
            metrics.counter("engine_seed_walks_total").inc()
        with tracer.stage("engine_enqueue",
                          metrics.histogram("engine_enqueue_seconds"),
                          metrics.counter("engine_enqueue_cpu_seconds_total"),
                          rows=len(seeds_pad)):
            out, converged, iters, checks, cav_missing = self._run(
                self._level_edges, self._blocks,
                self._dsrc, self._ddst, self._dexp, self._dcav,
                self._cav_static, cav_req, seeds_pad, grid, now_rel)
        try:
            out.copy_to_host_async()
            converged.copy_to_host_async()
            iters.copy_to_host_async()
            checks.copy_to_host_async()
            cav_missing.copy_to_host_async()
        except AttributeError:  # non-jax backends in tests
            pass
        return out, converged, iters, checks, cav_missing

    def _request_arrays(self, context: Optional[dict],
                        cav_req: Optional[tuple], now_abs: float) -> tuple:
        """The per-caveat request-context arrays riding this dispatch
        (replicated); pre-encoded ``cav_req`` (chunked bulk callers)
        wins, else encode here — including the auto-injected ``now``."""
        cavt = self.cg.caveats
        if cavt is None or not cavt.metas:
            return ()
        if cav_req is not None:
            return cav_req
        req, _ = cavt.encode_request(context, now_abs)
        return req

    def _pad_rows(self, B: int) -> int:
        B_pad = max(_next_bucket(B, 1), self.nd)
        if B_pad % self.nd:
            B_pad = ((B_pad + self.nd - 1) // self.nd) * self.nd
        return B_pad

    def query_grid(
        self,
        seed_slots: np.ndarray,  # int32 [B, 2] (subject slot, wildcard slot)
        q_slots: np.ndarray,  # int32 [B, Q] result slots per subject
        now: Optional[float] = None,
        context: Optional[dict] = None,
    ) -> np.ndarray:
        """Run the sharded fixpoint; returns bool [B, Q]."""
        cg = self.cg
        B, Q = q_slots.shape
        B_pad = self._pad_rows(B)
        Q_pad = _next_bucket(Q, 8)
        seeds = np.full((B_pad, 2), cg.M, dtype=np.int32)
        seeds[:B] = seed_slots
        qs = np.full((B_pad, Q_pad), cg.M, dtype=np.int32)
        qs[:B, :Q] = q_slots
        now_abs = time.time() if now is None else now
        out, converged, iters, checks, cav_missing = self._dispatch(
            seeds, qs, now_abs, self._request_arrays(context, None, now_abs))
        fut = ShardedQueryFuture(out, converged, iters, None,
                                 self.max_iters, cav_missing, self.k_steps,
                                 checks=checks)
        return fut.result()[:B, :Q]

    def query_async(
        self,
        seed_slots: np.ndarray,  # int32 [B, 2]
        q_slots: np.ndarray,  # int32 [Q] flat result slots
        q_batch: np.ndarray,  # int32 [Q] batch row per query
        now: Optional[float] = None,
        q_cache_key: Optional[tuple] = None,
        q_contiguous: Optional[bool] = None,  # accepted for surface parity
        q_contig_grid: Optional[tuple] = None,  # (lo, L, R) promise: R rows
        # x one shared [lo, lo+L) window — skips the rank re-map entirely
        context: Optional[dict] = None,  # request caveat context: merged
        # under the tuple contexts ON the mesh (replicated request
        # arrays), exactly like the single-device dispatch
        cav_req: Optional[tuple] = None,  # pre-encoded request arrays
        # (CompiledCaveats.encode_request) — chunked bulk callers encode
        # ONCE for the whole logical call instead of per chunk
    ) -> ShardedQueryFuture:
        """Engine-compatible flat form (CompiledGraph.query_async surface):
        the flat (q_slots, q_batch) queries are packed into a [B, Qmax]
        grid (rank within row computed vectorized), dispatched, and the
        future re-maps the grid output back to flat [Q] order. The
        iteration budget is the construction-time ``max_iters`` (baked
        into the jitted shard_map). Homogeneous fused batches
        (``q_contig_grid``, engine/batcher.py) bypass the O(Q log Q)
        rank computation and the O(Q) fancy-index result re-map: their
        grid rows are the window itself and the row-major grid slice IS
        the flat order."""
        cg = self.cg
        B = seed_slots.shape[0]
        q_slots = np.asarray(q_slots, dtype=np.int32)
        q_batch = np.asarray(q_batch, dtype=np.int32)
        Q = len(q_slots)
        if (q_contig_grid is None and q_contiguous and Q and B == 1
                and not q_batch[0]):
            # the engine's single-window promise is the R=1 grid
            q_contig_grid = (int(q_slots[0]), Q, 1)
        contig = None
        if q_contig_grid is not None:
            lo, L, R = q_contig_grid
            if Q == L * R and 0 < L and 0 < R <= B and lo + L <= cg.M:
                contig = (lo, L, R)
        if contig is not None:
            lo, L, R = contig
            cols = None
            Qmax = L
        elif Q:
            # rank of each query within its batch row (stable)
            order = np.argsort(q_batch, kind="stable")
            sorted_qb = q_batch[order]
            starts = np.flatnonzero(
                np.r_[True, np.diff(sorted_qb) != 0])
            run_len = np.diff(np.r_[starts, Q])
            grp_start = np.repeat(starts, run_len)
            rank_sorted = np.arange(Q) - grp_start
            cols = np.empty(Q, dtype=np.int64)
            cols[order] = rank_sorted
            Qmax = int(rank_sorted.max()) + 1
        else:
            cols = np.empty(0, dtype=np.int64)
            Qmax = 1
        B_pad = self._pad_rows(B)
        Q_pad = _next_bucket(Qmax, 8)
        seeds = np.full((B_pad, 2), cg.M, dtype=np.int32)
        seeds[:B] = seed_slots
        grid = self._qgrid.get((q_cache_key, B_pad)) \
            if q_cache_key else None
        if grid is None:
            grid_np = np.full((B_pad, Q_pad), cg.M, dtype=np.int32)
            if contig is not None:
                grid_np[:R, :L] = lo + np.arange(L, dtype=np.int32)
            else:
                grid_np[q_batch, cols] = q_slots
            # a GLOBAL device array (not a process-local jnp.asarray):
            # identical on every process, sharded over the data axis —
            # valid on single-process and multi-host meshes alike
            grid = jax.device_put(
                grid_np, NamedSharding(self.mesh, P("data", None)))
            if q_cache_key:
                # bounded: grids pin device memory per distinct key
                if len(self._qgrid) >= 32:
                    self._qgrid.pop(next(iter(self._qgrid)), None)
                self._qgrid[(q_cache_key, B_pad)] = grid
        now_abs = time.time() if now is None else now
        out, converged, iters, checks, cav_missing = self._dispatch(
            seeds, grid, now_abs,
            self._request_arrays(context, cav_req, now_abs))
        sel = (("contig_grid", L, R) if contig is not None
               else (q_batch, cols))
        return ShardedQueryFuture(out, converged, iters, sel,
                                  max_iters=self.max_iters,
                                  cav_missing=cav_missing,
                                  k_steps=self.k_steps,
                                  checks=checks)

"""Masked boolean-semiring SpMM: the one propagation primitive.

One reachability hop is a sparse-matrix/dense-"vector" product over the
(OR, AND) boolean semiring, with a per-edge activation mask fused into the
multiply: ``prop[b, d] = OR_e [dst(e)=d] (V[b, src(e)] AND act(e))`` where
``act(e) = (exp(e) > now) AND cav_ok[cav(e)]``. Direct tuples, userset
tuples, and arrow-term edges all share this one form (they were lowered to
the uniform ``dst <- src`` edge set at compile time), so this module is
the single owner of propagation for BOTH the single-device fixpoint
(ops/reachability._run) and the shard_map body (parallel/sharded
._run_sharded) — there is no second propagate body to drift.

The multiply runs in one of two modes, switched PER ITERATION by a
``lax.cond`` on the traced frontier occupancy (so the choice never
re-specializes the trace):

- **push** — frontier-driven: the dense blocks' frontier columns are
  bit-packed (ops/bitprop.pack_frontier) and contracted by the bit-packed
  VPU kernel, streaming 8x less HBM per hop. Best while the frontier is
  sparse: the kernel's operand is 1 bit per potential edge and the work
  is proportional to reached sources, not the full block.

  (A literal COO gather/scatter push — touching only frontier edges —
  is the textbook formulation, but TPU gathers are scalar-bound: the
  measured 10M-edge bench block runs ~100x SLOWER on the gather path
  than on blocks (see reachability.DENSE_MIN_EDGES notes). The
  bit-packed contraction is the TPU-shaped spelling of "push".)

- **pull** — column-dense: every dst row pulls its full source range
  through an MXU matmul (``A[n_dst, n_src] @ frontier^T``), lowered to
  an MXU-tile-shaped Pallas kernel (ops/bitprop.dense_or_matmul) when
  eligible, with a ``lax.dot_general`` fallback otherwise. Best when
  the frontier saturates and the batch amortizes the A stream.

The crossover threshold is a TRACED scalar fed by the engine from its
``engine_frontier_occupancy`` histogram (EWMA of observed final-frontier
occupancy -> ``crossover_from_occupancy``), so tuning it costs zero
recompiles. Both modes compute the exact same boolean product — the
differential suite (tests/test_parallel.py / tests/test_semiring.py)
pins byte-identical verdicts across push, pull, Pallas, and the numpy
oracle.

Residual (expiring / caveated / sparse) edges and the incremental delta
overlay always ride the gather/segment-max path: their edge sets are
small by construction (compile_graph routes everything big and static
into dense blocks), so mode switching would only add latency there.
Residual edges that start in a subject's own ``__self`` range are the
exception: a dispatch sets at most two slots a row there, so
:func:`propagate_seeded` reads those slots' runs instead of walking the
slice (ops/reachability.compile_graph lays them out by source).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp

from . import bitprop

# resolved_mode() values: "auto" = per-iteration lax.cond on occupancy;
# "push"/"pull" force one branch (the differential tests' reference)
_MODES = ("auto", "push", "pull")
_FORCED: Optional[str] = None


def resolved_mode() -> str:
    """The propagation-mode policy baked into the next trace: a
    force_mode() override, else auto. Part of the jit-cache key
    (reachability._jit_run_for), so flipping it never reuses a stale
    trace."""
    return _FORCED if _FORCED is not None else "auto"


@contextmanager
def force_mode(mode: str):
    """Force push/pull/auto for the duration (chip_smoke.py and the
    push / pull / auto differential tests)."""
    global _FORCED
    if mode not in _MODES:
        raise ValueError(f"unknown semiring mode {mode!r}")
    prev = _FORCED
    _FORCED = mode
    try:
        yield
    finally:
        _FORCED = prev


def crossover_from_occupancy(ewma: Optional[float]) -> float:
    """Map the engine's frontier-occupancy EWMA (fraction of slots set in
    observed final frontiers, [0, 1]) to the push/pull crossover fed to
    :func:`propagate`: push while the traced per-iteration occupancy is
    <= the returned threshold. No signal yet (None) -> 1.0, i.e. always
    push where the bit path exists — the pre-semiring behavior. A hot
    (dense) workload shrinks the threshold so saturated iterations take
    the MXU pull path; the 0.05 floor keeps the cheap first hops (seeds
    only) on push even under a fully-dense steady state."""
    if ewma is None:
        return 1.0
    return float(min(1.0, max(0.05, 1.0 - ewma)))


def edge_activation(exp_rel: jax.Array, now_rel, cav: jax.Array,
                    cav_ok: Optional[jax.Array]) -> jax.Array:
    """The fused ``(exp > now) AND cav_ok[row]`` edge-activation mask,
    uint8 per edge. Computed ONCE per dispatch (callers hoist it outside
    their iteration/level loops — under K-step fusing that is once per
    fused window, not once per hop) and fed to the semiring multiply as
    its mask operand."""
    act = (exp_rel > now_rel).astype(jnp.uint8)
    if cav_ok is not None:
        act = act & cav_ok[cav]
    return act


def frontier_occupancy(Vflat: jax.Array) -> jax.Array:
    """Traced occupancy of the current frontier/state in [0, 1]: the
    mean of the uint8 0/1 state. Feeds the per-iteration push/pull
    ``lax.cond`` — a device-side scalar, never synced to the host."""
    return jnp.mean(Vflat.astype(jnp.float32))


def propagate_seeded(prop, start, length, dst, act, fanout: int):
    """The hop over edges that leave a dispatch's own seed slots, read
    and not walked: ``prop[b, dst[i]] |= act[i]`` for every ``i`` of the
    runs ``start[b, j] .. start[b, j] + length[b, j]`` (``[B, 2]``: row
    b's subject and wildcard seed) of a slice sorted by source.

    A ``__self`` range is written by nothing but the seeding, so of all
    its slots a dispatch of B rows sets at most 2 * B, each to 1: the
    product over the whole slice is these runs and nothing else.
    ``fanout`` (static) bounds a run's length; ``act`` is the slice of
    the one :func:`edge_activation` array the walk reads, so an expired,
    caveated or killed edge is off here by the same rule."""
    with jax.named_scope("seeded"):
        j = jnp.arange(fanout, dtype=jnp.int32)
        idx = jnp.minimum(start[..., None] + j, dst.shape[0] - 1)
        on = jnp.where(j < length[..., None], act[idx], 0)  # [B, 2, D]
        rows = jnp.arange(prop.shape[0], dtype=jnp.int32)[:, None, None]
        return prop.at[rows, dst[idx]].max(on)


def propagate(block_meta, blocks, blocks_bits, src, dst, act,
              dsrc, ddst, dact, Vflat, occ, crossover, *,
              level: Optional[int] = None, mode: str = "auto",
              shard: Optional[tuple] = None):
    """One masked-semiring hop: ``(prop [B, Mp] uint8, is_push int32)``.

    ``src``/``dst``/``act`` are the residual edge slice for this level
    (dst-sorted; ``act`` from :func:`edge_activation`); ``dsrc``/``ddst``/
    ``dact`` the incremental delta overlay (append order). ``block_meta``
    is the slim _BlockMeta tuple; ``blocks``/``blocks_bits`` the device
    matrices (bits entries may be None). Blocks are filtered here by
    ``level`` (None = all).

    ``occ``/``crossover`` are traced scalars: in auto mode the dense
    phase picks push (bit-packed) vs pull (dense matmul) via
    ``lax.cond(occ <= crossover, ...)`` — both branches are pure local
    compute (collective joins stay with the caller, so shard_map callers
    whose shards diverge on the branch cannot deadlock). ``mode``
    (static) forces one branch; when no selected block has a bit dual
    the branches are identical and the cond is elided (is_push = 0).

    ``shard``: ``(g_idx, ng)`` when the caller runs inside shard_map with
    block matrices sharded ``P(None, "graph")`` — the frontier slice then
    covers only this device's src-axis chunk.
    """
    B = Vflat.shape[0]
    Mp = Vflat.shape[1]
    # residual edges: gather + segment-max (boolean OR) over the slot
    # axis; trash padding lands in the trash row
    with jax.named_scope("residual"):
        if src.shape[0]:
            gathered = (Vflat[:, src] & act[None, :]).T  # [E_slice, B]
            prop = jax.ops.segment_max(
                gathered, dst, num_segments=Mp, indices_are_sorted=True
            ).T  # [B, Mp]
        else:
            prop = jnp.zeros((B, Mp), dtype=jnp.uint8)
        # delta overlay: applied at EVERY level (contributions outside
        # the level's ranges are dropped by the caller's range-scoped
        # merge)
        gathered_d = (Vflat[:, dsrc] & dact[None, :]).T  # [D_pad, B]
        prop = prop | jax.ops.segment_max(
            gathered_d, ddst, num_segments=Mp, indices_are_sorted=False
        ).T

    sel = [(bm, A, Ab)
           for bm, A, Ab in zip(block_meta, blocks, blocks_bits)
           if level is None or bm.level == level]
    if not sel:
        return prop, jnp.int32(0)

    def frontier_of(bm):
        if shard is None:
            return jax.lax.dynamic_slice(
                Vflat, (0, bm.src_off), (B, bm.n_src))
        g_idx, ng = shard
        w = bm.n_src // ng
        return jax.lax.dynamic_slice(
            Vflat, (0, bm.src_off + g_idx * w), (B, w))

    def pull_one(bm, A, frontier):
        # column-dense: MXU-tile Pallas kernel when the block's local
        # shard is tile-aligned and the kernel is enabled, else the XLA
        # dot_general (the lax fallback). Static choice — enablement is
        # part of the jit-cache key.
        if bitprop.dense_kernel_enabled() and bitprop.dense_eligible(
                A.shape[0], A.shape[1], B):
            return bitprop.dense_or_matmul(A, frontier)
        return (
            jax.lax.dot_general(
                frontier.astype(jnp.int8), A,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32) > 0
        ).astype(jnp.uint8)  # [B, n_dst]

    def push_one(bm, A, Ab, frontier):
        # frontier-driven: bit-packed contraction (8x smaller A stream);
        # blocks without a bit dual degrade to pull within the push pass
        if Ab is not None and B <= bitprop.BIT_B_MAX:
            vb = bitprop.pack_frontier(frontier, frontier.shape[1])
            return bitprop.bit_or_matmul(Ab, vb, B).T  # [B, n_dst]
        return pull_one(bm, A, frontier)

    def apply_blocks(p, use_push: bool):
        with jax.named_scope("dense/push" if use_push else "dense/pull"):
            for bm, A, Ab in sel:
                f = frontier_of(bm)
                contrib = (push_one(bm, A, Ab, f) if use_push
                           else pull_one(bm, A, f))
                cur = jax.lax.dynamic_slice(
                    p, (0, bm.dst_off), (B, bm.n_dst))
                p = jax.lax.dynamic_update_slice(
                    p, cur | contrib, (0, bm.dst_off))
            return p

    push_differs = any(Ab is not None and B <= bitprop.BIT_B_MAX
                       for _, _, Ab in sel)
    if mode == "push" and push_differs:
        return apply_blocks(prop, True), jnp.int32(1)
    if mode == "pull" or not push_differs:
        return apply_blocks(prop, False), jnp.int32(0)
    # auto: per-iteration branch on TRACED occupancy — a lax.cond, never
    # a Python branch (the jit-stability lint pins this), so the mode
    # flips at runtime without re-specializing
    is_push = (occ <= crossover).astype(jnp.int32)
    prop = jax.lax.cond(
        is_push > 0,
        lambda p: apply_blocks(p, True),
        lambda p: apply_blocks(p, False),
        prop)
    return prop, is_push

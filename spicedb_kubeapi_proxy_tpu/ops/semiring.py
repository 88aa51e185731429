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

A dense block's hop picks its kernel from the block and the batch, both
static, so the choice is made once per trace (:func:`propagate` states the
rule). A literal COO gather/scatter push, touching only frontier edges, is
the textbook formulation, but TPU gathers are scalar-bound: the 10M-edge
bench block ran ~100x slower on the gather path than on blocks (see
reachability.DENSE_MIN_EDGES notes). Every kernel computes the exact same
boolean product; the differential suite (tests/test_parallel.py /
tests/test_semiring.py) pins it against the numpy oracle.

Residual (expiring / caveated / sparse) edges and the incremental delta
overlay always ride the gather/segment-max path: their edge sets are
small by construction (compile_graph routes everything big and static
into dense blocks).
Residual edges that start in a subject's own ``__self`` range are the
exception: a dispatch sets at most two slots a row there, so
:func:`propagate_seeded` reads those slots' runs instead of walking the
slice (ops/reachability.compile_graph lays them out by source).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import bitprop


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
              dsrc, ddst, dact, Vflat, *, level: Optional[int] = None,
              shard: Optional[tuple] = None):
    """One masked-semiring hop: ``prop [B, Mp] uint8``.

    ``src``/``dst``/``act`` are the residual edge slice for this level
    (dst-sorted; ``act`` from :func:`edge_activation`); ``dsrc``/``ddst``/
    ``dact`` the incremental delta overlay (append order). ``block_meta``
    is the slim _BlockMeta tuple; ``blocks``/``blocks_bits`` the device
    matrices (bits entries may be None). Blocks are filtered here by
    ``level`` (None = all).

    A dense block hop uses the bit kernel (ops/bitprop.bit_or_matmul)
    when the block has a bit dual and ``B <= BIT_B_MAX``; otherwise the
    MXU dense kernel (ops/bitprop.dense_or_matmul) when
    ``dense_eligible(n_dst, n_src, B)`` holds and the kernel is enabled,
    and otherwise ``lax.dot_general``.

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

    def frontier_of(bm):
        if shard is None:
            return jax.lax.dynamic_slice(
                Vflat, (0, bm.src_off), (B, bm.n_src))
        g_idx, ng = shard
        w = bm.n_src // ng
        return jax.lax.dynamic_slice(
            Vflat, (0, bm.src_off + g_idx * w), (B, w))

    def hop(A, Ab, frontier):
        if Ab is not None and B <= bitprop.BIT_B_MAX:
            vb = bitprop.pack_frontier(frontier, frontier.shape[1])
            return bitprop.bit_or_matmul(Ab, vb, B).T  # [B, n_dst]
        if bitprop.dense_kernel_enabled() and bitprop.dense_eligible(
                A.shape[0], A.shape[1], B):
            return bitprop.dense_or_matmul(A, frontier)
        return (
            jax.lax.dot_general(
                frontier.astype(jnp.int8), A,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32) > 0
        ).astype(jnp.uint8)  # [B, n_dst]

    with jax.named_scope("dense"):
        for bm, A, Ab in zip(block_meta, blocks, blocks_bits):
            if level is not None and bm.level != level:
                continue
            contrib = hop(A, Ab, frontier_of(bm))
            cur = jax.lax.dynamic_slice(prop, (0, bm.dst_off), (B, bm.n_dst))
            prop = jax.lax.dynamic_update_slice(
                prop, cur | contrib, (0, bm.dst_off))
    return prop

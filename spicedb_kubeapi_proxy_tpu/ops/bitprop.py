"""Bit-packed block propagation: a Pallas TPU kernel for the latency path.

One hop over a dense relation block computes ``out[d, b] = OR_s A[d, s] &
V[s, b]`` (reached(dst) = any reached src with an edge). The int8 MXU
matmul used for large batches streams ``n_dst * n_src`` bytes of A from HBM
per hop; a single-subject query (B=1 — the reference's per-request
LookupResources, pkg/authz/lookups.go:49-65, which BASELINE.md turns into
the p50 list-filter target) is therefore HBM-bound on an operand that is
99.5% zeros at bench density.

Packing the src axis into uint32 words shrinks the streamed operand 8x
(one bit per potential edge) and turns the hop into an (AND, OR)-semiring
contraction the VPU executes directly:

    out[d, b] = (OR_k A_bits[d, k] & V_bits[b, k]) != 0

The kernel tiles dst over the grid, keeps the packed frontier resident in
VMEM, and OR-accumulates 128-word lanes; the lane reduction happens once
per (tile, b). Large batches (B > BIT_B_MAX) keep using the MXU matmul —
at B=1024 the systolic array amortizes the A stream across the batch and
wins; at B<=8 this kernel's 8x-smaller stream wins.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

BIT_B_MAX = 8  # batches up to this ride the bit kernel (beyond: MXU matmul)
TILE_D = 256  # dst rows per grid step
LANES = 128

# uint8 out tiles need sublane multiples of 32; uint32 A tiles need src
# words >= one lane row. Blocks smaller than this use the matmul path.
MIN_DST = 32
MIN_SRC = 32

# VMEM is ~16MiB/core; the kernel's resident set per grid step is the
# (double-buffered) A tile + the packed frontier + out/accumulators. Blocks
# whose packed rows blow this even at the smallest tile fall back to the MXU
# matmul, which XLA tiles itself — otherwise Mosaic fails AT RUNTIME on the
# first big-block query.
VMEM_BUDGET = 12 * 1024 * 1024


def _k_pad(n_src: int) -> int:
    return -(-((n_src + 31) // 32) // LANES) * LANES


def _vmem_bytes(tile_d: int, k: int) -> int:
    # 2x A tile (pipeline double-buffering), packed frontier, out tile and
    # two int32 accumulators
    return (2 * tile_d + BIT_B_MAX) * k * 4 + 3 * tile_d * LANES * 4


def _pick_tile_for_k(n_dst: int, k: int):
    for t in (TILE_D, 128, 64, 32):
        if n_dst % t == 0 and _vmem_bytes(t, k) <= VMEM_BUDGET:
            return t
    return None


def pick_tile(n_dst: int, n_src: int):
    """Largest dst tile that divides n_dst and fits VMEM, or None if even
    the smallest tile does not fit (matmul fallback)."""
    return _pick_tile_for_k(n_dst, _k_pad(n_src))


def eligible(n_dst: int, n_src: int) -> bool:
    return (n_dst % MIN_DST == 0 and n_src % MIN_SRC == 0
            and pick_tile(n_dst, n_src) is not None)


def kernel_enabled() -> bool:
    """Bit kernel runs on TPU; elsewhere only in the Pallas interpreter,
    which SDBKP_BITPROP=interpret selects (the CPU tests' seam)."""
    return os.environ.get("SDBKP_BITPROP") == "interpret" \
        or jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("SDBKP_BITPROP") == "interpret" \
        or jax.default_backend() != "tpu"


def pack_block_host(dst_local: np.ndarray, src_local: np.ndarray,
                    n_dst: int, n_src: int) -> np.ndarray:
    """Edges -> uint32 bit matrix [n_dst, K_pad]; bit w of word k set means
    an edge from src ``32k + w``. K padded to the 128-lane width."""
    k0 = (n_src + 31) // 32
    k_pad = -(-k0 // LANES) * LANES
    bits = np.zeros((n_dst, k_pad), dtype=np.uint32)
    word = src_local // 32
    bit = (src_local % 32).astype(np.uint32)
    np.bitwise_or.at(bits, (dst_local, word), np.uint32(1) << bit)
    return bits


def pack_frontier(frontier: jax.Array, n_src: int) -> jax.Array:
    """uint8 frontier [B, n_src] -> packed [8, K_pad] uint32 (B rows used).

    Device-side: a reshape + shift + sum over the 32-bit word axis. Cost
    is O(n_src * B) — negligible next to the hop.
    """
    b = frontier.shape[0]
    k0 = n_src // 32
    shifts = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    words = jnp.sum(
        frontier.astype(jnp.uint32).reshape(b, k0, 32)
        * shifts[None, None, :],
        axis=2,
    )  # [B, K0]
    k_pad = -(-k0 // LANES) * LANES
    out = jnp.zeros((BIT_B_MAX, k_pad), dtype=jnp.uint32)
    return jax.lax.dynamic_update_slice(out, words, (0, 0))


def _bit_kernel(n_b: int, a_ref, v_ref, out_ref):
    # int32 throughout: Mosaic has no unsigned reductions, and mixing i1
    # masks across int32/uint8 tilings forces unsupported relayouts
    tile_d = a_ref.shape[0]
    k = a_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_d, LANES), 1)
    out = jnp.zeros((tile_d, LANES), dtype=jnp.int32)
    for b in range(n_b):  # static: n_b <= BIT_B_MAX
        acc = jnp.zeros((tile_d, LANES), dtype=jnp.uint32)
        for kc in range(k // LANES):  # static unroll over lane chunks
            sl = slice(kc * LANES, (kc + 1) * LANES)
            acc = acc | (a_ref[:, sl] & v_ref[b, sl][None, :])
        hit = jnp.max((acc != 0).astype(jnp.int32), axis=1,
                      keepdims=True)  # [tile_d, 1] in {0, 1}
        out = out | jnp.where(lane == b, hit, 0)
    out_ref[:] = out


def bit_or_matmul(a_bits: jax.Array, v_bits: jax.Array, n_b: int) -> jax.Array:
    """(AND, OR) contraction: a_bits [n_dst, K] uint32, v_bits
    [BIT_B_MAX, K] uint32 -> reached [n_dst, n_b] uint8."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_dst, k = a_bits.shape
    # largest tile that divides n_dst exactly AND fits the VMEM budget
    # (eligible() guarantees one exists), so the grid covers every row
    tile_d = _pick_tile_for_k(n_dst, k) or MIN_DST
    out = pl.pallas_call(
        partial(_bit_kernel, n_b),
        grid=(n_dst // tile_d,),
        in_specs=[
            pl.BlockSpec((tile_d, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BIT_B_MAX, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_d, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_dst, LANES), jnp.int32),
        interpret=_interpret(),
        name="sdbkp_bit_hop",
    )(a_bits, v_bits)
    return out[:, :n_b].astype(jnp.uint8)


# ---------------------------------------------------------------------------
# MXU-shaped dense kernel: batches beyond BIT_B_MAX (ops/semiring.py)
# ---------------------------------------------------------------------------

# the MXU systolic array is 128x128; the dense kernel's grid tiles both
# block axes at exactly this, so every inner contraction is one MXU pass
MXU_TILE = 128
# int8 operands need sublane multiples of 32: batches are padded up to it
SUBLANE = 32
# frontier rows the dense kernel will pad/stream before the plain XLA
# matmul (which tiles the batch itself) is the better schedule
DENSE_B_MAX = 4096


def dense_kernel_enabled() -> bool:
    """Dense MXU Pallas kernel runs on TPU; elsewhere only in the Pallas
    interpreter, which SDBKP_SEMIRING=interpret selects (the CPU tests'
    seam; otherwise dot_general). Part of the jit-cache key
    (reachability._jit_run_for) — flipping it never reuses a stale
    trace."""
    return os.environ.get("SDBKP_SEMIRING") == "interpret" \
        or jax.default_backend() == "tpu"


def _dense_interpret() -> bool:
    return os.environ.get("SDBKP_SEMIRING") == "interpret" \
        or jax.default_backend() != "tpu"


def _dense_vmem_bytes(b32: int, n_dst: int) -> int:
    # double-buffered A tile + frontier tile + int32 out tile resident
    # per grid step
    return (2 * MXU_TILE * MXU_TILE + b32 * MXU_TILE
            + 4 * b32 * MXU_TILE)


def dense_eligible(n_dst: int, n_src: int, batch: int) -> bool:
    """Both block axes must be MXU-tile multiples (slot ranges are
    LANE=128-aligned by construction, so full blocks always qualify;
    sharded src chunks qualify when the per-device chunk stays
    tile-aligned) and the padded batch tile must fit VMEM."""
    if n_dst % MXU_TILE or n_src % MXU_TILE:
        return False
    if batch > DENSE_B_MAX:
        return False
    b32 = -(-batch // SUBLANE) * SUBLANE
    return _dense_vmem_bytes(b32, n_dst) <= VMEM_BUDGET


def _dense_kernel(f_ref, a_ref, out_ref):
    """One (dst-tile, src-tile) grid step of the masked boolean matmul:
    ``out[b, d] |= OR_s f[b, s] & a[d, s]`` via an int8 MXU contraction.
    The out tile is revisited across the src-tile grid axis (zeroed at
    the first step) — the standard Pallas accumulation pattern; the
    frontier-tile emptiness predicate skips the matmul for all-zero
    frontier chunks, which makes the kernel cheap on sparse iterations.
    The predicate reduces the tile widened to int32: Mosaic has no
    relayout for the i1 mask of an int8 compare ((32,128) tiling) into
    the reduction's (8,128) one."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(jnp.max(f_ref[:].astype(jnp.int32)) > 0)
    def _accum():
        part = jax.lax.dot_general(
            f_ref[:], a_ref[:],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)  # [b32, MXU_TILE]
        out_ref[:] = out_ref[:] | (part > 0).astype(jnp.int32)


def dense_or_matmul(A: jax.Array, frontier: jax.Array) -> jax.Array:
    """Masked boolean-semiring block hop on the MXU: ``A [n_dst, n_src]``
    int8, ``frontier [B, n_src]`` uint8 -> reached ``[B, n_dst]`` uint8.
    Grid = (dst tiles, src tiles), every tile exactly MXU-shaped;
    eligibility is the caller's job (:func:`dense_eligible`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_dst, n_src = A.shape
    b = frontier.shape[0]
    b32 = -(-b // SUBLANE) * SUBLANE
    f = jnp.zeros((b32, n_src), dtype=jnp.int8)
    f = jax.lax.dynamic_update_slice(f, frontier.astype(jnp.int8), (0, 0))
    out = pl.pallas_call(
        _dense_kernel,
        grid=(n_dst // MXU_TILE, n_src // MXU_TILE),
        in_specs=[
            pl.BlockSpec((b32, MXU_TILE), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((MXU_TILE, MXU_TILE), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b32, MXU_TILE), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b32, n_dst), jnp.int32),
        interpret=_dense_interpret(),
        name="sdbkp_dense_hop",
    )(f, A)
    return (out[:b] > 0).astype(jnp.uint8)


def dense_hop_reference(A: np.ndarray, frontier: np.ndarray) -> np.ndarray:
    """Pure-numpy oracle of one dense block hop (tests)."""
    return ((frontier.astype(np.int64) @ A.astype(np.int64).T) > 0
            ).astype(np.uint8)


def bit_hop_reference(a_bits: np.ndarray, frontier: np.ndarray) -> np.ndarray:
    """Pure-numpy oracle of one packed hop (tests)."""
    n_dst, k = a_bits.shape
    n_src, n_b = frontier.shape
    out = np.zeros((n_dst, n_b), dtype=np.uint8)
    for b in range(n_b):
        idx = np.flatnonzero(frontier[:, b])
        words = idx // 32
        bits = np.uint32(1) << (idx % 32).astype(np.uint32)
        v = np.zeros(k, dtype=np.uint32)
        np.bitwise_or.at(v, words, bits)
        out[:, b] = ((a_bits & v[None, :]).any(axis=1)).astype(np.uint8)
    return out

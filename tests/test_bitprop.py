"""Bit-packed propagation kernel: parity with the matmul path and the
numpy oracle (kernel runs in Pallas interpreter mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from spicedb_kubeapi_proxy_tpu.ops import bitprop


def _random_block(rng, n_dst, n_src, n_edges):
    dst = rng.integers(n_dst, size=n_edges).astype(np.int32)
    src = rng.integers(n_src, size=n_edges).astype(np.int32)
    return dst, src


def test_pack_block_host_sets_expected_bits():
    bits = bitprop.pack_block_host(
        np.asarray([0, 0, 2]), np.asarray([0, 33, 127]), n_dst=32, n_src=128)
    assert bits.shape == (32, 128)  # K padded to one lane row
    assert bits[0, 0] == 1  # src 0 -> word 0 bit 0
    assert bits[0, 1] == 2  # src 33 -> word 1 bit 1
    assert bits[2, 3] == np.uint32(1) << 31  # src 127 -> word 3 bit 31


@pytest.mark.parametrize("n_dst,n_src,n_b", [
    (32, 32, 1), (256, 128, 3), (512, 1024, 8), (288, 96, 2),
])
def test_kernel_matches_oracle(monkeypatch, n_dst, n_src, n_b):
    monkeypatch.setenv("SDBKP_BITPROP", "interpret")
    rng = np.random.default_rng(n_dst + n_src)
    dst, src = _random_block(rng, n_dst, n_src, n_edges=4 * n_dst)
    a_bits = bitprop.pack_block_host(dst, src, n_dst, n_src)
    frontier = (rng.random((n_src, n_b)) < 0.1).astype(np.uint8)

    # engine layout: frontier rows are batch lanes [B, n_src]
    vb = bitprop.pack_frontier(jnp.asarray(frontier.T.copy()), n_src)
    got = np.asarray(bitprop.bit_or_matmul(
        jnp.asarray(a_bits), vb, n_b))
    want = bitprop.bit_hop_reference(a_bits, frontier)
    np.testing.assert_array_equal(got, want)
    # cross-check the oracle against the dense matmul formulation
    dense = np.zeros((n_dst, n_src), dtype=np.int32)
    dense[dst, src] = 1
    np.testing.assert_array_equal(
        want, (dense @ frontier.astype(np.int32) > 0).astype(np.uint8))


def test_vmem_budget_bounds_eligibility(monkeypatch):
    """Blocks whose packed rows cannot fit VMEM at any tile fall back to
    the matmul path instead of failing Mosaic compilation at runtime."""
    # small blocks: eligible, and bigger dst picks a bigger tile
    assert bitprop.eligible(256, 4096)
    assert bitprop.pick_tile(256, 4096) == 256

    # 10M-src block: packed K ~ 312k words -> even a 32-row tile is
    # ~2*32*312k*4 + 8*312k*4 ≈ 90MB >> budget
    n_src_huge = 10_000_000 - (10_000_000 % 32)
    assert not bitprop.eligible(512, n_src_huge)
    assert bitprop.pick_tile(512, n_src_huge) is None

    # mid-size: full 256 tile busts the budget but a smaller one fits ->
    # still eligible, with a reduced tile
    monkeypatch.setattr(bitprop, "VMEM_BUDGET", 2 * 1024 * 1024)
    n_src_mid = 32 * 32 * bitprop.LANES  # K = 4096 words = 16KiB rows
    t = bitprop.pick_tile(256, n_src_mid)
    assert t is not None and t < 256
    assert bitprop.eligible(256, n_src_mid)
    # and the kernel actually runs with the reduced tile
    monkeypatch.setenv("SDBKP_BITPROP", "interpret")
    rng = np.random.default_rng(3)
    dst, src = _random_block(rng, 64, n_src_mid, n_edges=200)
    a_bits = bitprop.pack_block_host(dst, src, 64, n_src_mid)
    frontier = np.zeros((n_src_mid, 1), dtype=np.uint8)
    frontier[src[:5], 0] = 1
    vb = bitprop.pack_frontier(jnp.asarray(frontier.T.copy()), n_src_mid)
    got = np.asarray(bitprop.bit_or_matmul(jnp.asarray(a_bits), vb, 1))
    np.testing.assert_array_equal(
        got, bitprop.bit_hop_reference(a_bits, frontier))


def test_engine_query_parity_bit_vs_matmul(monkeypatch):
    """Same engine queries through both block representations."""
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.ops import reachability

    monkeypatch.setattr(reachability, "DENSE_MIN_EDGES", 4)
    schema = parse_schema("""
definition user {}
definition ns {
  relation viewer: user
  permission view = viewer
}
""")
    rng = np.random.default_rng(7)
    rels = [f"ns:n{rng.integers(40)}#viewer@user:u{rng.integers(30)}"
            for _ in range(300)]

    def run(mode):
        if mode is None:
            monkeypatch.delenv("SDBKP_BITPROP", raising=False)
        else:
            monkeypatch.setenv("SDBKP_BITPROP", mode)
        e = Engine(schema=schema)
        e.write_relationships(
            [WriteOp("touch", parse_relationship(r)) for r in set(rels)])
        items = [CheckItem("ns", f"n{i}", "view", "user", f"u{i % 30}")
                 for i in range(40)]
        # B=1 per check_bulk row grouping is engine-internal; both calls
        # use identical inputs either way
        return e.check_bulk(items)

    assert run("interpret") == run(None)

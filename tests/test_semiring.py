"""Masked boolean-semiring SpMM primitive (ops/semiring.py): the kernel
each batch size picks (bit kernel, Pallas dense kernel, both in
interpreter mode on CPU) and the numpy oracle must agree byte-identically
ON EVERY HOP — not just at the fixpoint — and the jitted fixpoint holds
no runtime branch between them."""

from functools import partial
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spicedb_kubeapi_proxy_tpu.engine import Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.ops import bitprop, reachability, semiring

SCHEMA = """
definition user {}
definition group {
  relation member: user
}
definition doc {
  relation viewer: user | group#member
  permission view = viewer
}
"""


def _block_engine(monkeypatch, n_docs=12, n_users=7):
    """A small engine whose graph really forms dense blocks WITH
    bit-packed duals on the CPU host (interpret-mode kernels + lowered
    dense threshold) — the bit and the dense kernel are both live here."""
    monkeypatch.setenv("SDBKP_BITPROP", "interpret")
    monkeypatch.setenv("SDBKP_SEMIRING", "interpret")
    monkeypatch.setattr(reachability, "DENSE_MIN_EDGES", 8)
    e = Engine(schema=parse_schema(SCHEMA))
    rels = [f"doc:d{i}#viewer@user:u{(i * 3 + j) % n_users}"
            for i in range(n_docs) for j in range(3)]
    rels += [f"group:g{i}#member@user:u{i % n_users}" for i in range(4)]
    rels += [f"doc:d{i}#viewer@group:g{i % 4}#member" for i in range(6)]
    e.write_relationships(
        [WriteOp("touch", parse_relationship(r)) for r in rels])
    cg = e.compiled()
    d = cg._dev()
    assert cg.blocks and any(b is not None for b in d["blocks_bits"])
    return e, cg, d


def _np_hop(Vf, src, dst, act, metas, blocks):
    """Numpy oracle for one masked-semiring hop (residual + blocks)."""
    B, Mp = Vf.shape
    prop = np.zeros((B, Mp), dtype=np.uint8)
    contrib = Vf[:, src] & act[None, :]
    np.maximum.at(prop.T, dst, contrib.T)
    for bm, A in zip(metas, blocks):
        f = Vf[:, bm.src_off:bm.src_off + bm.n_src].astype(np.int32)
        hit = (f @ np.asarray(A).astype(np.int32).T > 0).astype(np.uint8)
        win = prop[:, bm.dst_off:bm.dst_off + bm.n_dst]
        prop[:, bm.dst_off:bm.dst_off + bm.n_dst] = win | hit
    return prop


def test_propagate_modes_agree_every_hop(monkeypatch):
    """The kernel each batch picks (3 rows: the bit kernel; the same rows
    padded to BIT_B_MAX + 1: the dense kernel) and the numpy oracle
    produce the SAME propagation byte-for-byte at every hop of the
    closure."""
    e, cg, d = _block_engine(monkeypatch)
    meta = cg.run_meta()
    Mp = (cg.M // reachability.LANE + 1) * reachability.LANE
    src = np.asarray(d["src"])
    dst = np.asarray(d["dst"])
    act = np.asarray(
        semiring.edge_activation(d["exp"], np.float32(0.0), d["cav"], None))
    dsrc, ddst = d["dsrc"], d["ddst"]
    dact = semiring.edge_activation(d["dexp"], np.float32(0.0),
                                    d["dcav"], None)

    objs = e._objects_by_name()
    B = 3
    V = np.zeros((B, Mp), dtype=np.uint8)
    for b, u in enumerate(("u0", "u1", "u2")):
        # subject slot + wildcard slot, exactly like _seed_base: the
        # user -> group#member -> doc#viewer chain needs multiple hops
        for s in cg.encode_subject("user", u, None, objs):
            if 0 <= s < cg.M:
                V[b, s] = 1

    def one_hop(Vf):
        return np.asarray(semiring.propagate(
            meta.blocks, d["blocks"], d["blocks_bits"],
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(act),
            dsrc, ddst, dact, jnp.asarray(Vf), level=None))

    wide = bitprop.BIT_B_MAX + 1
    for hop in range(6):
        want = _np_hop(V, src, dst, act, meta.blocks, d["blocks"])
        padded = np.zeros((wide, Mp), dtype=np.uint8)
        padded[:B] = V
        np.testing.assert_array_equal(one_hop(V), want,
                                      err_msg=f"bit@{hop}")
        np.testing.assert_array_equal(one_hop(padded)[:B], want,
                                      err_msg=f"dense@{hop}")
        V2 = V | want
        if np.array_equal(V2, V):
            break
        V = V2
    else:
        pytest.fail("closure did not settle within the hop budget")
    assert hop >= 1, "graph must need multiple hops to exercise per-hop parity"


@pytest.mark.parametrize("B", [1, bitprop.BIT_B_MAX, bitprop.BIT_B_MAX + 1,
                               33])
def test_propagate_picks_kernel_by_batch(monkeypatch, B):
    """One dual-bearing block: up to BIT_B_MAX rows the hop is the bit
    kernel's, beyond it the dense kernel's, and either matches its numpy
    oracle. The block sits at [128, 384) fed by [0, 128)."""
    monkeypatch.setenv("SDBKP_BITPROP", "interpret")
    monkeypatch.setenv("SDBKP_SEMIRING", "interpret")
    n_dst, n_src, dst_off, Mp = 256, 128, 128, 512
    rng = np.random.default_rng(B)
    A = (rng.random((n_dst, n_src)) < 0.05).astype(np.int8)
    dl, sl = np.nonzero(A)
    bits = bitprop.pack_block_host(dl, sl, n_dst, n_src)
    bm = SimpleNamespace(level=0, src_off=0, n_src=n_src,
                         dst_off=dst_off, n_dst=n_dst)
    V = np.zeros((B, Mp), dtype=np.uint8)
    V[:, :n_src] = rng.random((B, n_src)) < 0.1
    none = jnp.zeros(0, dtype=jnp.int32)
    hop = partial(semiring.propagate, (bm,), (jnp.asarray(A),),
                  (jnp.asarray(bits),), none, none,
                  jnp.zeros(0, dtype=jnp.uint8), jnp.asarray([0]),
                  jnp.asarray([Mp - 1]), jnp.asarray([0], dtype=jnp.uint8))
    kernel = ("sdbkp_bit_hop" if B <= bitprop.BIT_B_MAX
              else "sdbkp_dense_hop")
    assert kernel in str(jax.make_jaxpr(hop)(jnp.asarray(V)))
    got = np.array(hop(jnp.asarray(V)))
    frontier = V[:, :n_src]
    want = bitprop.dense_hop_reference(A, frontier)
    if B <= bitprop.BIT_B_MAX:
        np.testing.assert_array_equal(
            want, bitprop.bit_hop_reference(bits, frontier.T.copy()).T)
    np.testing.assert_array_equal(got[:, dst_off:dst_off + n_dst], want)
    got[:, dst_off:dst_off + n_dst] = 0
    assert not got.any()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_fixpoint_holds_no_runtime_kernel_branch(monkeypatch):
    """A graph with a bit-dual block traces to a fixpoint with no cond
    anywhere in it, and four outputs: the answer, convergence, the
    iteration count and the missing-context count."""
    _, cg, d = _block_engine(monkeypatch)
    run = partial(reachability.fixpoint_program(cg.run_meta()),
                  max_iters=8)
    seeds = np.full((1, 2), cg.M, dtype=np.int32)
    qs = np.full(8, cg.M, dtype=np.int32)
    closed = jax.make_jaxpr(run)(
        d["blocks"], d["blocks_bits"], d["src"], d["dst"], d["exp"],
        d["cav"], d["ptr"], d["dsrc"], d["ddst"], d["dexp"], d["dcav"],
        d["cav_static"], (), seeds, qs, np.zeros(8, dtype=np.int32),
        np.float32(0.0))
    prims = {eqn.primitive.name for eqn in _eqns(closed.jaxpr)}
    assert "while" in prims and "cond" not in prims
    assert len(closed.out_avals) == 4


def test_edge_activation_fuses_expiry_and_caveat():
    exp = jnp.asarray([1.0, -1.0, 5.0, 5.0], dtype=jnp.float32)
    cav = jnp.asarray([0, 0, 1, 2], dtype=jnp.int32)
    cav_ok = jnp.asarray([1, 0, 1], dtype=jnp.uint8)
    act = np.asarray(semiring.edge_activation(exp, np.float32(0.0),
                                              cav, cav_ok))
    # row0: live + row ok; row1: expired; row2: live + caveat denied;
    # row3: live + caveat ok
    np.testing.assert_array_equal(act, [1, 0, 0, 1])
    # no caveat table: pure expiry mask
    np.testing.assert_array_equal(
        np.asarray(semiring.edge_activation(exp, np.float32(0.0), cav,
                                            None)),
        [1, 0, 1, 1])


@pytest.mark.parametrize("n_dst,n_src,n_b", [
    (128, 128, 1), (256, 128, 5), (128, 256, 32), (384, 128, 33),
])
def test_dense_pallas_kernel_matches_reference(monkeypatch, n_dst, n_src,
                                               n_b):
    """The MXU-tile dense kernel (interpreter mode on CPU) must match
    the numpy oracle and the dot_general fallback it replaces."""
    monkeypatch.setenv("SDBKP_SEMIRING", "interpret")
    assert bitprop.dense_kernel_enabled()
    assert bitprop.dense_eligible(n_dst, n_src, n_b)
    rng = np.random.default_rng(n_dst + n_src + n_b)
    A = (rng.random((n_dst, n_src)) < 0.05).astype(np.int8)
    frontier = (rng.random((n_b, n_src)) < 0.1).astype(np.uint8)
    got = np.asarray(bitprop.dense_or_matmul(jnp.asarray(A),
                                             jnp.asarray(frontier)))
    want = bitprop.dense_hop_reference(A, frontier)
    np.testing.assert_array_equal(got, want)
    # empty frontier: the @pl.when skip must still zero the output
    zero = np.zeros_like(frontier)
    np.testing.assert_array_equal(
        np.asarray(bitprop.dense_or_matmul(jnp.asarray(A),
                                           jnp.asarray(zero))),
        np.zeros((n_b, n_dst), dtype=np.uint8))


def test_dense_eligibility_matrix():
    """Pallas eligibility: MXU-tile-aligned axes and a VMEM-bounded
    batch only; everything else stays on the dot_general fallback."""
    assert bitprop.dense_eligible(128, 128, 1)
    assert bitprop.dense_eligible(256, 384, 64)
    assert not bitprop.dense_eligible(96, 128, 1)   # dst not tile-aligned
    assert not bitprop.dense_eligible(128, 100, 1)  # src not tile-aligned
    assert not bitprop.dense_eligible(
        128, 128, bitprop.DENSE_B_MAX + 1)          # batch cap

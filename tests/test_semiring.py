"""Masked boolean-semiring SpMM primitive (ops/semiring.py): push, pull,
the auto lax.cond, the Pallas dense kernel (interpreter mode on CPU), and
the numpy oracle must agree byte-identically ON EVERY HOP — not just at
the fixpoint — plus the mode-policy plumbing (force_mode, crossover
mapping, per-mode hop_bytes accounting)."""

import numpy as np
import pytest

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
    bit-packed duals on the CPU host (interpret-mode kernel + lowered
    dense threshold) — push and pull are distinct code paths here."""
    monkeypatch.setenv("SDBKP_BITPROP", "interpret")
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
    """Push, pull, both auto branches, and the numpy oracle produce the
    SAME propagation byte-for-byte at every hop of the closure, and the
    auto lax.cond reports the branch it took."""
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

    def one_hop(Vf, mode, crossover):
        prop, is_push = semiring.propagate(
            meta.blocks, d["blocks"], d["blocks_bits"],
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(act),
            dsrc, ddst, dact, jnp.asarray(Vf),
            semiring.frontier_occupancy(jnp.asarray(Vf)),
            jnp.float32(crossover), level=None, mode=mode)
        return np.asarray(prop), int(is_push)

    for hop in range(6):
        want = _np_hop(V, src, dst, act, meta.blocks, d["blocks"])
        got_push, p1 = one_hop(V, "push", 1.0)
        got_pull, p2 = one_hop(V, "pull", 1.0)
        got_auto_hi, p3 = one_hop(V, "auto", 1.0)   # occ <= 1 -> push
        got_auto_lo, p4 = one_hop(V, "auto", -1.0)  # occ > -1 -> pull
        assert (p1, p2, p3, p4) == (1, 0, 1, 0), hop
        for name, got in (("push", got_push), ("pull", got_pull),
                          ("auto/push", got_auto_hi),
                          ("auto/pull", got_auto_lo)):
            np.testing.assert_array_equal(got, want, err_msg=f"{name}@{hop}")
        V2 = V | want
        if np.array_equal(V2, V):
            break
        V = V2
    else:
        pytest.fail("closure did not settle within the hop budget")
    assert hop >= 1, "graph must need multiple hops to exercise per-hop parity"


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


def test_crossover_from_occupancy_mapping():
    assert semiring.crossover_from_occupancy(None) == 1.0
    assert semiring.crossover_from_occupancy(0.0) == 1.0
    assert semiring.crossover_from_occupancy(0.3) == pytest.approx(0.7)
    # floor keeps seed-only first hops on push under a dense steady state
    assert semiring.crossover_from_occupancy(1.0) == 0.05


def test_force_mode():
    assert semiring.resolved_mode() == "auto"
    with semiring.force_mode("push"):
        assert semiring.resolved_mode() == "push"
        with semiring.force_mode("pull"):
            assert semiring.resolved_mode() == "pull"
        assert semiring.resolved_mode() == "push"
    assert semiring.resolved_mode() == "auto"
    with pytest.raises(ValueError):
        with semiring.force_mode("sideways"):
            pass


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
    # the gate composes with the feature switch
    from spicedb_kubeapi_proxy_tpu.utils.features import features
    features.set("SemiringDenseKernel", False)
    try:
        assert not bitprop.dense_kernel_enabled()
    finally:
        features.reset()


def test_hop_bytes_reports_per_mode_traffic(monkeypatch):
    """hop_bytes() breaks the core dense-block bytes out PER SEMIRING
    MODE: push streams the bit-packed duals (8x smaller where they
    exist), pull the full int8 A, pallas adds the MXU kernel's frontier
    re-stream on eligible blocks."""
    _, cg, d = _block_engine(monkeypatch)
    hb = cg.hop_bytes(batch=1)
    modes = hb["modes"]
    assert set(modes) == {"push", "pull", "pallas"}
    core = [bm for bm in cg.run_meta().blocks if bm.level == 0]
    if core:
        assert modes["pull"] == sum(bm.n_dst * bm.n_src for bm in core)
        assert 0 < modes["push"] < modes["pull"]
        assert modes["pallas"] >= modes["pull"]
    # the pre-semiring keys survive for the roofline reports
    for k in ("residual", "blocks", "programs", "tail_once", "total"):
        assert k in hb

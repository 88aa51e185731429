"""Compile the main path's kernels for the chip, without the chip.

The TPU's compiler is installed in the CPU sandbox and compiles for a
described (not attached) ``v5e:2x2`` topology, so these tests raise here
what Mosaic/XLA would raise on the machine with the chip: an unsupported
relayout, a tile not aligned, too much VMEM. Interpret-mode tests cannot
see any of that (``_dense_kernel`` passed them all while Mosaic refused
it at every shape). A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — only the
worker that runs this file loads libtpu — and everything runs in this
process. The persistent compilation cache is turned off around the
module: an entry compiled for a described device cannot be read back.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from spicedb_kubeapi_proxy_tpu.ops import bitprop, reachability, semiring

# the headline deployment's big block (100k pods x 10k users, bucket-padded)
HEADLINE = (131072, 16384)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """What the chip sees: both kernels on, neither interpreted. Steered
    from the test — the program has no option for it."""
    monkeypatch.setattr(bitprop, "_interpret", lambda: False)
    monkeypatch.setattr(bitprop, "_dense_interpret", lambda: False)
    monkeypatch.setattr(bitprop, "kernel_enabled", lambda: True)
    monkeypatch.setattr(bitprop, "dense_kernel_enabled", lambda: True)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("n_dst,n_src,batch", [
    (128, 128, 1),  # one MXU tile: the smallest eligible block
    HEADLINE + (1,),  # a list filter in pull mode
    HEADLINE + (16,),  # one 16k chunk of the 65k bulk check
    (10240, 10240, bitprop.DENSE_B_MAX),  # largest batch admitted
    (128, 1024000, bitprop.DENSE_B_MAX),  # ... on a long src axis
])
def test_dense_kernel_compiles(one_chip, compiled_kernels, n_dst, n_src,
                               batch):
    assert bitprop.dense_eligible(n_dst, n_src, batch)
    text = _compile(
        bitprop.dense_or_matmul,
        jax.ShapeDtypeStruct((n_dst, n_src), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((batch, n_src), jnp.uint8, sharding=one_chip))
    assert "tpu_custom_call" in text


# the src widths at which pick_tile's estimate still admits a block: the
# widest at the 256-row tile, and the widest at all (32-row tile)
_WIDEST_256 = 5760 * 32
_WIDEST = 43520 * 32


@pytest.mark.parametrize("n_dst,n_src,batch", [
    HEADLINE + (1,),  # a list filter in push mode
    HEADLINE + (bitprop.BIT_B_MAX,),  # a fused batch of eight
    (102400, _WIDEST_256, bitprop.BIT_B_MAX),
    (1024, _WIDEST, bitprop.BIT_B_MAX),
])
def test_bit_kernel_and_pack_frontier_compile(one_chip, compiled_kernels,
                                              n_dst, n_src, batch):
    assert bitprop.eligible(n_dst, n_src)
    k = bitprop._k_pad(n_src)
    text = _compile(
        lambda a, v: bitprop.bit_or_matmul(a, v, batch),
        jax.ShapeDtypeStruct((n_dst, k), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((bitprop.BIT_B_MAX, k), jnp.uint32,
                             sharding=one_chip))
    assert "tpu_custom_call" in text
    _compile(
        lambda f: bitprop.pack_frontier(f, n_src),
        jax.ShapeDtypeStruct((batch, n_src), jnp.uint8, sharding=one_chip))


def test_eligibility_stops_where_the_estimate_says():
    """The widths above are the boundary, not a sample: one more lane of
    words and the estimate refuses the block (it then rides the matmul)."""
    assert bitprop.pick_tile(102400, _WIDEST_256) == bitprop.TILE_D
    assert bitprop.pick_tile(102400, _WIDEST_256 + 128 * 32) != bitprop.TILE_D
    assert bitprop.pick_tile(1024, _WIDEST) == bitprop.MIN_DST
    assert not bitprop.eligible(1024, _WIDEST + 128 * 32)
    assert not bitprop.dense_eligible(128, 128, bitprop.DENSE_B_MAX + 1)


@pytest.fixture(scope="module")
def graph():
    """A compiled graph with dense blocks, residual edges, a delta
    overlay and a caveat."""
    import bench
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship

    e, _ = bench.build_engine(2_000, 500, 50, 50, 50_000, cav_share=0.01,
                              schema=bench.MESH_SCHEMA)
    e.compiled()  # the base; the write after it lands in the overlay
    e.write_relationships([WriteOp("touch", Relationship(
        "pod", "ns/p1", "viewer", "user", "u1"))])
    cg = e.compiled()
    assert cg.n_delta == 1 and cg.caveats is not None and cg.caveats.metas
    assert cg.blocks and len(cg.res_src)
    return cg


def _compile_fixpoint(cg, one_chip, q_contig_len: int, rows: int = 1) -> str:
    """One whole jitted fixpoint (``_jit_run_for``'s program) over ``cg``,
    in ``auto``, compiled for the described chip: its HLO text. ``rows``
    subject rows, read back as the grid of that many rows over one
    window (a lookup alone, or the batcher's fused dispatch)."""

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(
            lambda a: S(np.shape(a), np.asarray(a).dtype), tree)

    blocks = tuple(S((b.n_dst, b.n_src), jnp.int8) for b in cg.blocks)
    bits = tuple(S((b.n_dst, bitprop._k_pad(b.n_src)), jnp.uint32)
                 if bitprop.eligible(b.n_dst, b.n_src) else None
                 for b in cg.blocks)
    cav_static = cav_req = ()
    if cg.caveats is not None and cg.caveats.metas:
        cav_static = cg.caveats.device_static()
        cav_req, _ = cg.caveats.encode_request({"ip": "10.1.2.3"},
                                               time.time())
    with semiring.force_mode("auto"):
        run = jax.jit(reachability.fixpoint_program(cg.run_meta()),
                      static_argnames=("max_iters", "q_contig_len",
                                       "q_contig_rows"))
        return run.lower(
            blocks, bits,
            *like((cg.res_src, cg.res_dst, cg.res_exp, cg.res_cav,
                   cg._res_ptr())),
            *like(cg._delta_host()),
            like(cav_static), like(cav_req),
            S((rows, 2), jnp.int32), S((), jnp.int32), S((), jnp.int32),
            S((), jnp.float32), S((), jnp.float32),
            max_iters=reachability.DEFAULT_MAX_ITERS,
            q_contig_len=q_contig_len, q_contig_rows=rows,
        ).compile().as_text()


def test_whole_fixpoint_compiles_in_auto(one_chip, compiled_kernels, graph):
    """Both kernels sit in the two branches of the per-iteration
    ``lax.cond``, so both must compile in one program."""
    cg = graph
    assert any(bitprop.eligible(b.n_dst, b.n_src) for b in cg.blocks)
    text = _compile_fixpoint(cg, one_chip, cg.type_sizes["pod"])
    # every block: its dense kernel in the pull branch, its bit kernel in
    # the push branch
    assert text.count("tpu_custom_call") >= 2 * len(cg.blocks)
    # the names a device trace is reduced by: the module line reads
    # jit_sdbkp_fixpoint, the kernels' operations sdbkp_*_hop
    assert text.startswith("HloModule jit_sdbkp_fixpoint")
    assert "sdbkp_bit_hop" in text and "sdbkp_dense_hop" in text


def test_fixpoint_with_feeder_levels_and_entry_edges_compiles(
        one_chip, compiled_kernels):
    """The schedule a graph with a cycle gets: a feeder level and an
    entry phase, each with residual edges and a dense block, a loop over
    the cycle, levels after it, the delta overlay riding every phase. The
    phases' named scopes are in the compiled program's metadata: a
    device trace's share by scope reads them."""
    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

    e = Engine(schema=parse_schema("""
definition user {}
definition group { relation member: user }
definition team { relation member: user | group#member | team#member }
definition namespace {
  relation parent: namespace
  relation viewer: team#member
  permission view = viewer + parent->view
}
definition pod {
  relation namespace: namespace
  permission view = namespace->view
}
"""))
    rng = np.random.default_rng(29)
    n_user, n_team = 4000, 2000  # buckets 4096 x 2048: a block
    rels = {f"group:g{u % 64}#member@user:u{u}" for u in range(n_user)}
    # user -> team#member: dense enough for an entry block
    rels |= {f"team:t{t}#member@user:u{u}" for t in range(n_team)
             for u in rng.integers(n_user, size=4).tolist()}
    # group#member -> team#member: too few for a block, residual entry
    rels |= {f"team:t{t}#member@group:g{t % 64}#member" for t in range(100)}
    # team#member -> team#member: under DENSE_MIN_EDGES, so the
    # self-pair is not closed on the host and stays a cycle
    rels |= {f"team:t{t // 2}#member@team:t{t}#member"
             for t in range(1, 1000)}
    rels |= {f"namespace:n{n}#viewer@team:t{n}#member" for n in range(200)}
    rels |= {f"namespace:n{n}#parent@namespace:n{n // 3}"
             for n in range(1, 200)}
    rels |= {f"pod:n{p % 200}/p{p}#namespace@namespace:n{p % 200}"
             for p in range(400)}
    e.write_relationships([WriteOp("touch", parse_relationship(r))
                           for r in sorted(rels)])
    e.compiled()  # the base; the write after it lands in the overlay
    e.write_relationships([WriteOp("touch", parse_relationship(
        "team:t7#member@user:u9"))])
    cg = e.compiled()
    assert cg.n_delta == 1 and cg.n_pre == 2 and cg.n_levels >= 1
    lo, hi = cg.run_meta().level_slice(-1)
    assert (cg.res_dst[lo:hi] != cg.M).sum() == 100
    # users into groups at the feeder level, users into teams at the entry
    assert sorted(b.level for b in cg.blocks) == [-2, -1]
    text = _compile_fixpoint(cg, one_chip, cg.type_sizes["pod"])
    assert text.startswith("HloModule jit_sdbkp_fixpoint")
    for scope in ("feed1", "entry", "core", "level1", "readout"):
        assert f"/{scope}/" in text, scope
    assert text.count("tpu_custom_call") >= 2  # both blocks' kernels


@pytest.mark.parametrize("rows", [8, 32])
def test_fused_lookup_program_of_the_tenant_schema_compiles(
        one_chip, compiled_kernels, rows):
    """A dispatch of several subject rows read as a grid, the shape of
    the batcher's fused dispatch (engine/batcher.py), on the
    ``multi-tenant-100k`` schema at its rehearsal size, dense blocks
    and seeded edges included: 8 rows ride the bit kernel, 32 the MXU
    kernel, and the grid of ``rows`` rows is one ``dynamic_slice`` of
    the state."""
    import importlib.util
    import os

    from spicedb_kubeapi_proxy_tpu.engine import Engine

    spec = importlib.util.spec_from_file_location(
        "bench_deployment", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "deployment.py"))
    deployment = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(deployment)
    dep = deployment.Deployment("multi-tenant-100k", 3200000029,
                                rehearse=True)
    e = Engine(dep.text("bootstrap.yaml"))
    e.bulk_load(dep.columns())
    cg = e.compiled()
    assert cg.blocks and cg.seed_edges()
    text = _compile_fixpoint(cg, one_chip, cg.type_sizes["namespace"], rows)
    assert text.startswith("HloModule jit_sdbkp_fixpoint")
    # past the bit kernel's rows the push branch is the pull branch
    assert "sdbkp_dense_hop" in text
    assert ("sdbkp_bit_hop" in text) == (rows <= bitprop.BIT_B_MAX)
    assert f"pred[{rows * cg.type_sizes['namespace']}]" in text


def test_mesh_fixpoint_compiles_and_joins_as_int32(topo, one_chip,
                                                   compiled_kernels, graph):
    """The ``shard_map`` program of ``ShardedGraph`` on the described 2x2
    mesh (data=2, graph=2), a 16-subject bulk check: the dense kernel and
    the collectives are in the compiled program, and the partial products
    are joined over ``graph`` as int32. A uint8 ``pmax`` drops set bytes
    on real v5e chips (grants denied; seen by ``chip_smoke.py --chips
    4``), while the CPU's virtual devices reduce byte by byte, so no
    other test would notice the dtype being narrowed again."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spicedb_kubeapi_proxy_tpu.parallel.sharded import ShardedGraph

    def mesh_of(devices):
        return Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "graph"))

    cg = graph
    sg = ShardedGraph(cg, mesh_of(jax.devices()))  # arrays on CPU devices
    described = mesh_of(topo.devices)

    def S(a, spec):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(described, spec))

    def placed(tree):  # same shapes and specs, on the described mesh
        return jax.tree.map(lambda a: S(a, a.sharding.spec), tree)

    cav_req, _ = cg.caveats.encode_request({"ip": "10.1.2.3"}, time.time())
    batch = 16
    with semiring.force_mode("auto"):
        text = sg._program(described).lower(
            placed(sg._level_edges), placed(sg._blocks),
            placed(sg._dsrc), placed(sg._ddst), placed(sg._dexp),
            placed(sg._dcav), placed(sg._cav_static),
            jax.tree.map(lambda a: S(a, P()), cav_req),
            S(np.zeros((batch, 2), np.int32), P("data", None)),
            S(np.zeros((batch, 1024), np.int32), P("data", None)),
            S(np.float32(0), P()), S(np.float32(0), P()),
        ).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    # shard_map keeps the partial's given name: what XLA Modules reads
    assert text.startswith("HloModule jit_sdbkp_fixpoint_sharded")
    joins = re.findall(r"= \(?(\w+)\[[^\]]*\][^=]* all-reduce(?:-start)?\(",
                       text)
    assert joins and set(joins) == {"s32"}, joins

"""Sharded-engine tests: the shard_map fixpoint over a virtual 8-device CPU
mesh must agree exactly with the single-device jitted path (which itself is
fuzzed against the recursive oracle in test_engine.py)."""

import numpy as np
import pytest

import jax

from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.parallel import ShardedGraph, make_mesh

SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition org {
  relation admin: user
  relation parent: org
  permission admin_rec = admin + parent->admin_rec
}
definition doc {
  relation org: org
  relation owner: user
  relation reader: user | group#member
  relation banned: user
  permission read = (reader + owner + org->admin_rec) - banned
}
"""


def touch(*rels):
    return [WriteOp("touch", parse_relationship(r)) for r in rels]


def build_engine(seed=7, n_users=8, n_groups=5, n_docs=12, n_orgs=3):
    rng = np.random.default_rng(seed)
    e = Engine(schema=parse_schema(SCHEMA))
    users = [f"u{i}" for i in range(n_users)]
    ops = set()
    for g in range(n_groups):
        for u in rng.choice(n_users, size=3, replace=False):
            ops.add(f"group:g{g}#member@user:u{u}")
        g2 = rng.integers(n_groups)
        if g2 != g:
            ops.add(f"group:g{g}#member@group:g{g2}#member")
    for o in range(n_orgs):
        ops.add(f"org:o{o}#admin@user:u{rng.integers(n_users)}")
        o2 = rng.integers(n_orgs)
        if o2 != o:
            ops.add(f"org:o{o}#parent@org:o{o2}")
    for d in range(n_docs):
        for u in rng.choice(n_users, size=2, replace=False):
            ops.add(f"doc:d{d}#reader@user:u{u}")
        if rng.random() < 0.5:
            ops.add(f"doc:d{d}#owner@user:u{rng.integers(n_users)}")
        if rng.random() < 0.5:
            ops.add(f"doc:d{d}#banned@user:u{rng.integers(n_users)}")
        if rng.random() < 0.6:
            ops.add(f"doc:d{d}#reader@group:g{rng.integers(n_groups)}#member")
        if rng.random() < 0.7:
            ops.add(f"doc:d{d}#org@org:o{rng.integers(n_orgs)}")
    e.write_relationships(touch(*ops))
    return e, users


def grid_for_lookup(cg, objs, subjects, resource_type, permission):
    """seeds [B,2] + q_slots [B,Q] reading every object's permission slot."""
    off = cg.offset_of(resource_type, permission)
    n = cg.type_sizes[resource_type]
    seeds = np.asarray(
        [cg.encode_subject(t, i, None, objs) for (t, i) in subjects],
        dtype=np.int32,
    )
    q = np.tile(off + np.arange(n, dtype=np.int32), (len(subjects), 1))
    return seeds, q, n


@pytest.mark.parametrize("data,graph", [(2, 4), (1, 8), (8, 1), (4, 2)])
def test_sharded_matches_unsharded(data, graph):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    e, users = build_engine()
    cg = e.compiled()
    objs = e._objects_by_name()
    mesh = make_mesh(8, data=data, graph=graph)
    sg = ShardedGraph(cg, mesh)

    subjects = [("user", u) for u in users] + [("user", "nobody")]
    seeds, q, n = grid_for_lookup(cg, objs, subjects, "doc", "read")
    got = sg.query_grid(seeds, q)

    interner = objs["doc"]
    for b, (_, u) in enumerate(subjects):
        want = set(e.lookup_resources("doc", "read", "user", u))
        got_ids = {
            interner.string(i)
            for i in np.flatnonzero(got[b]).tolist()
            if i >= 2 and i < len(interner)  # skip void/wildcard slots
        }
        assert got_ids == want, f"subject {u}: {got_ids} != {want}"


def test_sharded_contig_grid_promise_matches_flat():
    """The batcher's homogeneous-grid promise on the sharded backend must
    agree with the general flat path (which argsort-re-maps), including a
    malformed promise falling back rather than mis-slicing."""
    e, users = build_engine()
    cg = e.compiled()
    objs = e._objects_by_name()
    sg = ShardedGraph(cg, make_mesh(8, data=2, graph=4))
    off = cg.offset_of("doc", "read")
    n = cg.type_sizes["doc"]
    subs = [("user", users[0]), ("user", users[3]), ("user", "nobody")]
    seeds = np.asarray(
        [cg.encode_subject(t, i, None, objs) for (t, i) in subs],
        dtype=np.int32)
    qs = np.tile(off + np.arange(n, dtype=np.int32), len(subs))
    qb = np.repeat(np.arange(len(subs), dtype=np.int32), n)
    flat = sg.query_async(seeds, qs, qb).result()
    fast = sg.query_async(seeds, qs, qb,
                          q_contig_grid=(off, n, len(subs))).result()
    assert np.array_equal(flat, fast)
    assert flat[:n].any() and not flat[2 * n:].any()
    # wrong row count: promise declined, result still correct
    bad = sg.query_async(seeds, qs, qb,
                         q_contig_grid=(off, n, 2)).result()
    assert np.array_equal(bad, flat)


def test_sharded_check_grid_odd_shapes():
    e, users = build_engine(seed=11)
    cg = e.compiled()
    objs = e._objects_by_name()
    sg = ShardedGraph(cg, make_mesh(8, data=2, graph=4))

    # B=3 (not divisible by data axis), Q=5 (odd) — padding must handle it
    subjects = [("user", "u0"), ("user", "u3"), ("group", "g1")]
    checks = [("doc", f"d{i}", "read") for i in range(5)]
    seeds = np.asarray(
        [cg.encode_subject(t, i, "member" if t == "group" else None, objs)
         for (t, i) in subjects],
        dtype=np.int32,
    )
    q = np.asarray(
        [[cg.encode_target(rt, perm, rid, objs) for (rt, rid, perm) in checks]
         for _ in subjects],
        dtype=np.int32,
    )
    got = sg.query_grid(seeds, q)
    for b, (t, i) in enumerate(subjects):
        srel = "member" if t == "group" else None
        for qi, (rt, rid, perm) in enumerate(checks):
            want = e.check(CheckItem(rt, rid, perm, t, i, srel))
            assert bool(got[b, qi]) == want, (t, i, rt, rid)


def test_sharded_expiration_mask():
    import time

    now = time.time()
    e = Engine(schema=parse_schema(
        """
        definition user {}
        definition doc {
          relation reader: user with expiration
          permission read = reader
        }
        """
    ))
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship

    e.write_relationships([
        WriteOp("touch", Relationship("doc", "live", "reader", "user", "u",
                                      expiration=now + 3600)),
        WriteOp("touch", Relationship("doc", "dead", "reader", "user", "u",
                                      expiration=now - 5)),
    ])
    cg = e.compiled()
    objs = e._objects_by_name()
    sg = ShardedGraph(cg, make_mesh(8))
    seeds = np.asarray([cg.encode_subject("user", "u", None, objs)],
                       dtype=np.int32)
    q = np.asarray([[cg.encode_target("doc", "read", "live", objs),
                     cg.encode_target("doc", "read", "dead", objs)]],
                   dtype=np.int32)
    got = sg.query_grid(seeds, q, now=now)
    assert got.tolist() == [[True, False]]


def test_sharded_sees_incremental_updates():
    """A ShardedGraph built from an incrementally-updated CompiledGraph
    folds the delta segment and dead-pair kills into its edge shards."""
    from spicedb_kubeapi_proxy_tpu.engine.store import RelationshipFilter
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    e, users = build_engine(seed=23)
    e.compiled()
    c0 = metrics.counter("engine_graph_compiles_total").value

    # revoke one existing reader tuple and grant a new one — both must be
    # applied incrementally (no full recompile)
    existing = sorted(
        e.read_relationships(RelationshipFilter(
            resource_type="doc", relation="reader", subject_type="user")),
        key=str)[0]
    e.write_relationships([
        WriteOp("delete", existing),
        WriteOp("touch", parse_relationship("doc:d1#reader@user:u7")),
        WriteOp("touch", parse_relationship("group:g0#member@user:u6")),
    ])
    cg = e.compiled()
    assert metrics.counter("engine_graph_compiles_total").value == c0
    assert cg.n_delta >= 2 and len(cg.dead_pairs) >= 1

    objs = e._objects_by_name()
    sg = ShardedGraph(cg, make_mesh(8, data=2, graph=4))
    subjects = [("user", u) for u in users]
    seeds, q, _ = grid_for_lookup(cg, objs, subjects, "doc", "read")
    got = sg.query_grid(seeds, q)
    interner = objs["doc"]
    for b, (_, u) in enumerate(subjects):
        want = set(e.lookup_resources("doc", "read", "user", u))
        got_ids = {
            interner.string(i)
            for i in np.flatnonzero(got[b]).tolist()
            if i >= 2 and i < len(interner)
        }
        assert got_ids == want, f"subject {u}: {got_ids} != {want}"


def test_sharded_agrees_slot_for_slot_on_a_graph_with_a_seeded_part():
    """The single chip reads the edges out of ``user.__self`` from a
    dispatch's seeds (their part of a slice is sorted by source); the
    mesh merges that part back into dst order and walks it. Every slot
    of the state agrees, before and after a kill of one such edge
    through ``updated()``."""
    from spicedb_kubeapi_proxy_tpu.engine.store import RelationshipFilter

    e, users = build_engine(seed=31, n_users=40, n_groups=12, n_docs=30)
    cg = e.compiled()
    assert cg.seed_edges() > 40 and cg.seed_mode(1) == "lookup"
    sg = ShardedGraph(cg, make_mesh(8, data=2, graph=4))
    for _, h_dst, _, _ in sg._h_levels:
        assert np.all(np.diff(h_dst) >= 0)
    subjects = [("user", u) for u in users] + [("user", "nobody")]

    def agree(cg, sg):
        objs = e._objects_by_name()
        seeds = np.asarray([cg.encode_subject(t, i, None, objs)
                            for t, i in subjects], dtype=np.int32)
        every = np.arange(cg.M, dtype=np.int32)
        got = sg.query_grid(seeds, np.tile(every, (len(seeds), 1)))
        for b in range(len(seeds)):  # one row a dispatch: the lookup
            want = cg.query(seeds[b:b + 1], every, np.zeros_like(every))
            assert np.array_equal(got[b], want), (
                subjects[b], np.flatnonzero(got[b] != want)[:5])
        assert got.sum() > 2 * len(seeds)

    agree(cg, sg)
    gone = sorted(e.read_relationships(RelationshipFilter(
        resource_type="group", relation="member", subject_type="user")),
        key=str)[0]
    e.write_relationships([WriteOp("delete", gone)])
    cg2 = e.compiled()
    assert cg2.res_src is cg.res_src and len(cg2.dead_pairs) == 1
    sg2 = sg.updated(cg2)
    assert sg2._run is sg._run  # no rebuild: the kill found its edge
    agree(cg2, sg2)
    assert e.check_bulk([CheckItem(
        "group", gone.resource_id, "member", "user", gone.subject_id)]) \
        == [False]


def test_engine_mesh_routes_queries_through_sharded():
    """Engine(mesh=...) answers checks and lookups through the sharded
    backend — parity with a single-device engine over the same store,
    including dense MXU blocks inside the shard_map body and incremental
    writes after the first compile."""
    from spicedb_kubeapi_proxy_tpu.ops import reachability
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    old_min = reachability.DENSE_MIN_EDGES
    reachability.DENSE_MIN_EDGES = 4  # force dense blocks at test scale
    try:
        mesh = make_mesh(8, data=2, graph=4)
        em, users = build_engine(seed=5)
        em.mesh = mesh  # build_engine has no mesh param; attach before use
        e1, _ = build_engine(seed=5)

        cg = em.compiled()
        assert cg.blocks, "need dense blocks to exercise the MXU path"
        sg = em._backend(cg)
        assert sg is not cg and sg._blocks, \
            "mesh engine must route through ShardedGraph with kept blocks"

        def parity():
            items = [
                CheckItem("doc", f"d{d}", "read", "user", u)
                for d in range(12) for u in users
            ]
            assert em.check_bulk(items) == e1.check_bulk(items)
            for u in users:
                assert sorted(em.lookup_resources("doc", "read", "user", u)) \
                    == sorted(e1.lookup_resources("doc", "read", "user", u))

        parity()
        # incremental writes rebuild the sharded view and stay exact
        c0 = metrics.counter("engine_graph_compiles_total").value
        for eng in (em, e1):
            eng.write_relationships([
                WriteOp("delete", parse_relationship("doc:d0#reader@user:u1"))
                for _ in range(1)] + [
                WriteOp("touch", parse_relationship("doc:d2#banned@user:u0")),
            ])
        parity()
        assert metrics.counter("engine_graph_compiles_total").value == c0
        sg2 = em._sharded
        assert sg2.cg is em.compiled()
        # the incremental sharded view reuses the jitted shard_map and the
        # resident base edge shards — no rebuild per write (src/dst shards
        # are shared; only killed levels' exp and the delta re-upload)
        assert sg2 is not sg and sg2._run is sg._run
        assert all(a[0] is b[0] and a[1] is b[1]
                   for a, b in zip(sg2._level_edges, sg._level_edges))
    finally:
        reachability.DENSE_MIN_EDGES = old_min


def test_proxy_with_engine_mesh(tmp_path):
    """Full proxy (rules, dual-write, list filtering) with the in-process
    engine spread over the virtual 8-device mesh."""
    import asyncio
    import json

    from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    import os
    deploy = os.path.join(os.path.dirname(__file__), "..", "deploy")

    async def go():
        from fake_kube import FakeKube

        cfg = Options(
            rule_files=[os.path.join(deploy, "rules.yaml")],
            bootstrap_files=[os.path.join(deploy, "bootstrap.yaml")],
            upstream=FakeKube(),
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            engine_mesh="data=2,graph=4",
        ).complete()
        assert cfg.engine.mesh is not None
        await cfg.workflow.resume_pending()
        alice = InMemoryClient(cfg.server.handle, user="alice")
        bob = InMemoryClient(cfg.server.handle, user="bob")
        for ns in ("mesh-a", "mesh-b"):
            resp = await alice.post("/api/v1/namespaces", {
                "apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": ns}})
            assert resp.status == 201, resp.body
        resp = await alice.get("/api/v1/namespaces")
        assert sorted(o["metadata"]["name"]
                      for o in json.loads(resp.body)["items"]) \
            == ["mesh-a", "mesh-b"]
        resp = await bob.get("/api/v1/namespaces")
        assert json.loads(resp.body)["items"] == []
        resp = await alice.delete("/api/v1/namespaces/mesh-b")
        assert resp.status == 200
        resp = await alice.get("/api/v1/namespaces")
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["mesh-a"]
        await cfg.workflow.shutdown()
    asyncio.run(go())


CAVEAT_BOOTSTRAP = """
schema: |-
  use expiration
  caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }
  caveat win(now timestamp, until timestamp) { now < until }
  definition user {}
  definition doc {
    relation viewer: user | user with ip_allowlist
      | user with win | user with expiration
    permission view = viewer
  }
relationships: |-
  doc:readme#viewer@user:alice
  doc:readme#viewer@user:bob[ip_allowlist:{"allowed":["10.0.0.0/8"]}]
  doc:plan#viewer@user:bob[ip_allowlist:{"allowed":["192.168.0.0/16"]}]
"""


def test_sharded_caveated_matches_single_device_and_oracle():
    """Conditional grants evaluate ON the mesh: the caveat VM runs
    inside the shard_map body against replicated instance tables, so a
    caveated graph routes through ShardedGraph (no fallback) and its
    verdicts — satisfying context, non-matching context, and the
    fail-closed missing-context tri-state — are byte-identical to the
    single-device engine and the recursive oracle."""
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    mesh = make_mesh(8, data=2, graph=4)
    em = Engine(bootstrap=CAVEAT_BOOTSTRAP, mesh=mesh)
    e1 = Engine(bootstrap=CAVEAT_BOOTSTRAP)
    fb0 = metrics.counter("engine_caveat_mesh_fallback_total").value
    items = [CheckItem("doc", d, "view", "user", u)
             for d in ("readme", "plan") for u in ("alice", "bob")]
    for ctx in ({"ip": "10.0.0.5"}, {"ip": "192.168.1.1"},
                {"ip": "8.8.8.8"}, None):
        got = em.check_bulk(items, context=ctx)
        assert got == e1.check_bulk(items, context=ctx), ctx
        o = em.oracle(context=ctx)
        assert got == [o.check(i.resource_type, i.resource_id,
                               i.permission, i.subject_type, i.subject_id)
                       for i in items], ctx
    # the mesh really served these: ShardedGraph built, zero fallbacks
    assert em._sharded is not None
    assert metrics.counter(
        "engine_caveat_mesh_fallback_total").value == fb0
    # contexted lookups agree too
    for ctx in ({"ip": "10.0.0.5"}, None):
        assert sorted(em.lookup_resources("doc", "view", "user", "bob",
                                          context=ctx)) == \
            sorted(e1.lookup_resources("doc", "view", "user", "bob",
                                       context=ctx))
    # missing context fails closed AND counts through the mesh path
    c0 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    assert em.check_bulk(
        [CheckItem("doc", "readme", "view", "user", "bob")]) == [False]
    assert metrics.counter(
        "engine_caveat_denied_missing_context_total").value > c0


def test_sharded_caveated_incremental_churn_differential():
    """The ISSUE 15 parity bar: randomized caveated + expiring +
    plain-tuple churn (touches with reused AND new contexts, deletes,
    live/lapsed expirations) applied to a mesh engine and a
    single-device engine in lockstep on the forced 8-device host
    platform — after EVERY batch the verdicts are byte-identical to
    each other and to the recursive oracle, and steady churn stays on
    the incremental path (no per-write recompiles, resident shard
    reuse)."""
    import time as _time

    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    rng = np.random.default_rng(0xCAFE)
    mesh = make_mesh(8, data=2, graph=4)
    em = Engine(bootstrap=CAVEAT_BOOTSTRAP, mesh=mesh)
    e1 = Engine(bootstrap=CAVEAT_BOOTSTRAP)
    users = [f"u{i}" for i in range(6)]
    docs = [f"d{i}" for i in range(5)]
    ctxs = ['{"allowed":["10.0.0.0/8"]}',
            '{"allowed":["172.16.0.0/12"]}']
    req = {"ip": "10.5.5.5"}
    now_fixed = _time.time()

    def wr(op):
        for eng in (em, e1):
            eng.write_relationships([op])

    # warm both engines (first compile + first sharded build)
    em.check_bulk([CheckItem("doc", "readme", "view", "user", "alice")],
                  context=req, now=now_fixed)
    e1.check_bulk([CheckItem("doc", "readme", "view", "user", "alice")],
                  context=req, now=now_fixed)
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    fb0 = metrics.counter("engine_caveat_mesh_fallback_total").value
    live: list[Relationship] = []
    for step in range(16):
        kind = int(rng.integers(4))
        d = docs[int(rng.integers(len(docs)))]
        u = users[int(rng.integers(len(users)))]
        if kind == 0 and live:  # delete an existing churn tuple
            wr(WriteOp("delete", live.pop(int(rng.integers(len(live))))))
        elif kind == 1:  # caveated touch, contexts mostly reused
            ctx = ctxs[int(rng.integers(len(ctxs)))]
            rel = Relationship("doc", d, "viewer", "user", u, None, None,
                               "ip_allowlist", ctx)
            wr(WriteOp("touch", rel))
            live.append(rel)
        elif kind == 2:  # expiring grant, alive or lapsed at the clock
            exp = now_fixed + (300.0 if rng.random() < 0.5 else -300.0)
            rel = Relationship("doc", d, "viewer", "user", u,
                               expiration=exp)
            wr(WriteOp("touch", rel))
            live.append(rel)
        else:  # plain grant
            rel = Relationship("doc", d, "viewer", "user", u)
            wr(WriteOp("touch", rel))
            live.append(rel)
        items = [CheckItem("doc", dd, "view", "user", uu)
                 for dd in docs for uu in users]
        for ctx in (req, None):
            got = em.check_bulk(items, context=ctx, now=now_fixed)
            want = e1.check_bulk(items, context=ctx, now=now_fixed)
            assert got == want, (step, ctx)
            o = em.oracle(now=now_fixed, context=ctx)
            assert got == [o.check(i.resource_type, i.resource_id,
                                   i.permission, i.subject_type,
                                   i.subject_id) for i in items], \
                (step, ctx)
        assert sorted(em.lookup_resources(
            "doc", "view", "user", u, now=now_fixed, context=req)) == \
            sorted(e1.lookup_resources(
                "doc", "view", "user", u, now=now_fixed, context=req)), \
            step
    # reused contexts ride the overlay: no per-write full recompiles
    # (the churn's distinct contexts at most add instance rows once),
    # and a caveated graph NEVER fell back off the mesh
    assert metrics.counter("engine_graph_compiles_total").value \
        <= compiles0 + 2
    assert metrics.counter(
        "engine_caveat_mesh_fallback_total").value == fb0


def test_sharded_updated_carries_caveat_instance_append():
    """A caveated write with a NEW (caveat, context) pair rides the
    incremental path (spare instance row) and ShardedGraph.updated()
    patches the REPLICATED context tables in place: no recompile, no
    sharded rebuild, and the new conditional grant answers correctly
    under both polarities of request context."""
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    mesh = make_mesh(8, data=2, graph=4)
    em = Engine(bootstrap=CAVEAT_BOOTSTRAP, mesh=mesh)
    em.check_bulk([CheckItem("doc", "readme", "view", "user", "bob")],
                  context={"ip": "10.0.0.1"})
    sg0 = em._sharded
    assert sg0 is not None
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    upd0 = metrics.counter("engine_sharded_updates_total").value
    em.write_relationships([WriteOp("touch", parse_relationship(
        'doc:memo#viewer@user:carol'
        '[ip_allowlist:{"allowed":["172.16.0.0/12"]}]'))])
    item = CheckItem("doc", "memo", "view", "user", "carol")
    assert em.check_bulk([item], context={"ip": "172.16.9.9"}) == [True]
    assert em.check_bulk([item], context={"ip": "10.0.0.1"}) == [False]
    assert em.check_bulk([item]) == [False]  # missing ctx: fail closed
    assert metrics.counter("engine_graph_compiles_total").value \
        == compiles0, "instance append must not recompile"
    assert metrics.counter("engine_sharded_updates_total").value > upd0
    sg1 = em._sharded
    assert sg1 is not sg0 and sg1._run is sg0._run, \
        "updated() must reuse the jitted shard_map"
    assert sg1._applied_inst != sg0._applied_inst, \
        "the replicated instance tables must have advanced"
    assert em.oracle(context={"ip": "172.16.9.9"}).check(
        "doc", "memo", "view", "user", "carol")


def test_sharded_kstep_fuses_convergence_checks():
    """K-step fused fixpoint: the mesh runs K propagation steps per
    convergence collective, so a query pays ceil(iters/K)+<=1 checks
    instead of one per hop — counted via conv_checks() and compared
    against the single-device iteration count for the SAME query, with
    identical results. iterations() reports the TRUE converged-at step
    (the per-step change flags survive the fuse as a [K] pmax vector),
    not the K-quantized budget the pre-semiring future reported."""
    e, users = build_engine(seed=3)
    cg = e.compiled()
    objs = e._objects_by_name()
    sg = ShardedGraph(cg, make_mesh(8, data=2, graph=4))
    assert sg.k_steps >= 2
    off = cg.offset_of("doc", "read")
    n = cg.type_sizes["doc"]
    seeds = np.asarray([cg.encode_subject("user", users[0], None, objs)],
                       dtype=np.int32)
    qs = off + np.arange(n, dtype=np.int32)
    qb = np.zeros(n, dtype=np.int32)
    f1 = cg.query_async(seeds, qs, qb)
    want = f1.result()
    iters_single = f1.iterations()
    fm = sg.query_async(seeds, qs, qb)
    got = fm.result()
    assert np.array_equal(got, want)
    checks = fm.conv_checks()
    # the relative pin from ISSUE 15: at most one confirming block past
    # the single-device iteration count, and strictly fewer collectives
    # than one-per-hop whenever the query iterates past one block
    assert 1 <= checks <= -(-iters_single // sg.k_steps) + 1
    # the ISSUE 17 fix: no more "budget consumed, a multiple of K" —
    # the mesh future reports the same converged-at step the
    # single-device future does, and the checks stay fused
    assert fm.iterations() == iters_single
    assert fm.iterations() <= checks * sg.k_steps
    if iters_single > sg.k_steps:
        assert checks < iters_single
    # explicit K override is honored and stays exact
    sg4 = ShardedGraph(cg, make_mesh(8, data=2, graph=4), k_steps=4)
    f4 = sg4.query_async(seeds, qs, qb)
    assert np.array_equal(f4.result(), want)
    assert f4.conv_checks() <= -(-iters_single // 4) + 1
    assert f4.iterations() == iters_single


def test_sharded_refuses_unstratified_caveated_graph():
    """The one genuinely unsupported mesh shape: a caveated graph
    without per-edge caveat rows (hand-built unstratified layout) must
    be refused — serving it would drop the caveat mask (fail open) —
    and Engine._backend counts the fallback."""
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    em = Engine(bootstrap=CAVEAT_BOOTSTRAP)
    cg = em.compiled()
    assert ShardedGraph.unsupported_reason(cg) is None
    import dataclasses

    bare = dataclasses.replace(cg, res_src=None, res_dst=None,
                               res_exp=None, res_cav=None,
                               res_level_bounds=None, _device={})
    assert ShardedGraph.unsupported_reason(bare) is not None
    with pytest.raises(ValueError, match="cannot serve"):
        ShardedGraph(bare, make_mesh(8))
    # the engine routes it to the single-device path, counted
    em2 = Engine(bootstrap=CAVEAT_BOOTSTRAP, mesh=make_mesh(8))
    fb0 = metrics.counter("engine_caveat_mesh_fallback_total").value
    backend = em2._backend(bare)
    assert backend is bare
    assert metrics.counter(
        "engine_caveat_mesh_fallback_total").value == fb0 + 1


def test_mesh_topology_label():
    from spicedb_kubeapi_proxy_tpu.parallel.mesh import mesh_topology

    t = mesh_topology(make_mesh(8, data=2, graph=4))
    assert t == {"devices": 8, "data": 2, "graph": 4, "platform": "cpu"}


def test_mesh_spec_parsing():
    from spicedb_kubeapi_proxy_tpu.proxy.options import (
        Options, OptionsError, _parse_mesh_spec)

    assert _parse_mesh_spec("auto") == {}
    assert _parse_mesh_spec("data=2,graph=4") == {"data": 2, "graph": 4}
    assert _parse_mesh_spec("graph=8") == {"graph": 8}
    for bad in ("nope", "data=x", "data=0", "rows=2"):
        with pytest.raises(OptionsError):
            _parse_mesh_spec(bad)
    with pytest.raises(OptionsError, match="engine-mesh applies"):
        Options(engine_endpoint="tcp://h:1", engine_mesh="auto",
                rule_content="x", upstream_url="http://u").validate()


def test_sharded_update_after_recompile_with_equal_signature():
    """REGRESSION (found in round 4, present since round 3): a write that
    forces a FULL recompile can leave the new graph with a signature
    equal to the old one (bucket padding absorbs small edge-count
    changes) while folding the delta into NEW base arrays.
    ShardedGraph.updated() used to treat signature equality as
    incremental descent and kept the old resident shards — silently
    answering stale DENIALS for the new edge. The guard is base-array
    object identity."""
    import numpy as np

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.parallel import make_mesh
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    import jax

    mesh = make_mesh(4, devices=jax.devices()[:4])
    rng = np.random.default_rng(7)
    rels = [f"namespace:n{i}#creator@user:u{int(rng.integers(50))}"
            for i in range(300)]
    em = Engine(mesh=mesh)
    em.write_relationships(
        [WriteOp("touch", parse_relationship(r)) for r in rels])
    item = CheckItem("namespace", "n1", "view", "user", "u49")
    assert em.check_bulk([item]) == [False]
    upd0 = metrics.counter("engine_sharded_updates_total").value
    # first-ever viewer edge: incremental_update declines (layout), the
    # engine recompiles, and the recompiled graph's signature happens to
    # equal the old one
    em.write_relationships([WriteOp("touch", parse_relationship(
        "namespace:n1#viewer@user:u49"))])
    got = em.check_bulk([item])
    assert got == [True], \
        "stale sharded shards after an equal-signature recompile"
    assert em.oracle().check("namespace", "n1", "view", "user", "u49")
    assert metrics.counter("engine_sharded_updates_total").value > upd0


def test_sharded_incremental_interleaving_fuzz():
    """Adversarial fuzz over the incremental/recompile boundary the
    stale-shards regression lived on: random touches, deletes, NEW
    relations, NEW objects, and expiring grants interleaved with queries,
    asserting mesh-engine == single-device == oracle after every batch.
    Each step may take the incremental path, the equal-signature
    recompile path, or a layout-changing recompile — the engines must be
    indistinguishable through all of them."""
    import numpy as np

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import (
        Relationship,
        parse_relationship,
    )
    from spicedb_kubeapi_proxy_tpu.parallel import make_mesh

    import jax

    rng = np.random.default_rng(0xFADE)
    mesh = make_mesh(4, devices=jax.devices()[:4])
    bootstrap = """
schema: |-
  use expiration

  definition cluster {}
  definition user {}
  definition namespace {
    relation cluster: cluster
    relation creator: user
    relation viewer: user | user with expiration
    permission admin = creator
    permission view = viewer + creator
  }
  definition pod {
    relation namespace: namespace
    relation creator: user
    relation viewer: user
    permission view = viewer + creator + namespace->view
  }
relationships: ""
"""
    em = Engine(bootstrap=bootstrap, mesh=mesh)
    e1 = Engine(bootstrap=bootstrap)
    live: list[str] = []

    def wr(ops):
        for eng in (em, e1):
            eng.write_relationships(ops)

    # seed
    seed = [f"namespace:n{i}#creator@user:u{int(rng.integers(12))}"
            for i in range(40)]
    wr([WriteOp("touch", parse_relationship(r)) for r in seed])
    live += seed

    now_fixed = 1_700_000_000.0
    for step in range(14):
        kind = rng.integers(5)
        if kind == 0 and live:  # delete an existing edge
            r = live.pop(int(rng.integers(len(live))))
            wr([WriteOp("delete", parse_relationship(r))])
        elif kind == 1:  # touch within existing types/objects
            r = (f"namespace:n{int(rng.integers(40))}#viewer"
                 f"@user:u{int(rng.integers(12))}")
            wr([WriteOp("touch", parse_relationship(r))])
            live.append(r)
        elif kind == 2:  # NEW object id (bucket growth possible)
            r = (f"namespace:fresh-{step}#creator"
                 f"@user:new-u{step}")
            wr([WriteOp("touch", parse_relationship(r))])
            live.append(r)
        elif kind == 3:  # first-ever edges of a relation (layout change)
            r = (f"pod:n{int(rng.integers(40))}/p{step}#viewer"
                 f"@user:u{int(rng.integers(12))}")
            wr([WriteOp("touch", parse_relationship(r))])
            live.append(r)
        else:  # expiring grant, alive or lapsed at the query clock
            exp = now_fixed + (300.0 if rng.random() < 0.5 else -300.0)
            wr([WriteOp("touch", Relationship(
                "namespace", f"n{int(rng.integers(40))}", "viewer",
                "user", f"u{int(rng.integers(12))}", expiration=exp))])
        items = [
            CheckItem("namespace", f"n{int(i)}", "view", "user",
                      f"u{int(u)}")
            for i, u in zip(rng.integers(42, size=12),
                            rng.integers(12, size=12))
        ]
        got = em.check_bulk(items, now=now_fixed)
        want = e1.check_bulk(items, now=now_fixed)
        assert got == want, (step, got, want)
        oracle = em.oracle(now=now_fixed)
        for it, g in zip(items, got):
            assert g == oracle.check(it.resource_type, it.resource_id,
                                     it.permission, it.subject_type,
                                     it.subject_id), (step, it)
        u = f"u{int(rng.integers(12))}"
        assert sorted(em.lookup_resources(
            "namespace", "view", "user", u, now=now_fixed)) == \
            sorted(e1.lookup_resources(
                "namespace", "view", "user", u, now=now_fixed)), step


def test_watch_over_engine_mesh(tmp_path):
    """A live watch stream with the engine sharded over the virtual
    8-device mesh: grants flowing through dual-writes must reach the
    watcher via the hub's recompute path (which dispatches sharded grid
    queries), completing the mesh-engine coverage beyond list/get."""
    import asyncio
    import json
    import os

    from fake_kube import FakeKube, serve_upstream
    from test_proxy_server import HttpClient, RULES

    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            bind_port=0,
            engine_mesh="data=2,graph=4",
        ).complete()
        assert cfg.engine.mesh is not None
        await cfg.run()
        alice = HttpClient(cfg.server.port, "alice")
        status, _, _ = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "mw-a"}})
        assert status == 201
        status, headers, (reader, writer) = await alice.request(
            "GET", "/api/v1/namespaces?watch=true", stream=True)
        assert status == 200
        first = await asyncio.wait_for(alice.read_chunk(reader), timeout=15)
        ev = json.loads(first)
        assert (ev["type"], ev["object"]["metadata"]["name"]) \
            == ("ADDED", "mw-a")
        status, _, _ = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "mw-b"}})
        assert status == 201
        nxt = await asyncio.wait_for(alice.read_chunk(reader), timeout=15)
        assert json.loads(nxt)["object"]["metadata"]["name"] == "mw-b"
        writer.close()
        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_semiring_push_pull_differential_churn(monkeypatch):
    """The parity bar for the masked-semiring core: with the kernels on
    (interpret mode on the CPU host: real dense blocks with bit-packed
    duals, the MXU dense kernel) and off (dot_general), both backends
    agree byte-identically with the recursive oracle at EVERY churn step
    while expiring + caveated + plain tuples churn through the
    incremental overlay."""
    import time as _time

    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.ops import reachability

    def kernels(on: bool):
        # each engine builds, traces and serves under its own setting
        for var in ("SDBKP_BITPROP", "SDBKP_SEMIRING"):
            if on:
                monkeypatch.setenv(var, "interpret")
            else:
                monkeypatch.delenv(var, raising=False)

    # a low dense threshold: the small test graph forms real dense blocks
    monkeypatch.setattr(reachability, "DENSE_MIN_EDGES", 8)

    rng = np.random.default_rng(0x5E31)
    users = [f"u{i}" for i in range(7)]
    docs = [f"d{i}" for i in range(10)]
    seed_rels = [f"doc:{d}#viewer@user:{u}"
                 for d in docs for u in users if hash((d, u)) % 2]
    engines = {}
    for on in (True, False):
        kernels(on)
        for name, mesh in (("single", None),
                           ("mesh", make_mesh(8, data=2, graph=4))):
            e = Engine(bootstrap=CAVEAT_BOOTSTRAP, mesh=mesh)
            e.write_relationships(touch(*seed_rels))
            engines[(on, name)] = e
        cg = engines[(on, "single")].compiled()
        assert cg.blocks, "differential needs at least one dense block"
        assert any(b is not None for b in cg._dev()["blocks_bits"]) == on, \
            "a bit-packed dual exactly where the kernels are on"

    now_fixed = _time.time()
    req = {"ip": "10.5.5.5"}
    ctxs = ['{"allowed":["10.0.0.0/8"]}', '{"allowed":["172.16.0.0/12"]}']
    items = [CheckItem("doc", d, "view", "user", u)
             for d in docs for u in users]
    live: list[Relationship] = []
    for step in range(6):
        kind = int(rng.integers(4))
        d = docs[int(rng.integers(len(docs)))]
        u = users[int(rng.integers(len(users)))]
        if kind == 0 and live:
            op = WriteOp("delete", live.pop(int(rng.integers(len(live)))))
        elif kind == 1:
            rel = Relationship("doc", d, "viewer", "user", u, None, None,
                               "ip_allowlist",
                               ctxs[int(rng.integers(len(ctxs)))])
            live.append(rel)
            op = WriteOp("touch", rel)
        elif kind == 2:
            exp = now_fixed + (300.0 if rng.random() < 0.5 else -300.0)
            rel = Relationship("doc", d, "viewer", "user", u,
                               expiration=exp)
            live.append(rel)
            op = WriteOp("touch", rel)
        else:
            rel = Relationship("doc", d, "viewer", "user", u)
            live.append(rel)
            op = WriteOp("touch", rel)
        for (on, _), e in engines.items():
            kernels(on)
            e.write_relationships([op])
        for ctx in (req, None):
            o = engines[(True, "single")].oracle(now=now_fixed, context=ctx)
            want = [o.check(i.resource_type, i.resource_id, i.permission,
                            i.subject_type, i.subject_id) for i in items]
            for (on, name), e in engines.items():
                kernels(on)
                got = e.check_bulk(items, context=ctx, now=now_fixed)
                assert got == want, (step, ctx, on, name)

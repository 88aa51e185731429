"""Engine core tests: store semantics, oracle, and TPU-path equivalence.

The key gate: the jitted slot-space fixpoint (ops/reachability.py) must
agree with the recursive oracle evaluator on every (object, permission,
subject) combination, across schema features and randomized graphs.
"""

import time

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.engine import (
    CheckItem,
    Engine,
    Precondition,
    PreconditionFailed,
    RelationshipFilter,
    Store,
    WriteOp,
)
from spicedb_kubeapi_proxy_tpu.engine.store import AlreadyExists
from spicedb_kubeapi_proxy_tpu.engine.engine import SchemaViolation
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship, parse_relationship


def rel(s: str) -> Relationship:
    return parse_relationship(s)


def touch(*rels: str) -> list[WriteOp]:
    return [WriteOp("touch", rel(r)) for r in rels]


# ---------------------------------------------------------------------------
# Store semantics
# ---------------------------------------------------------------------------


def test_store_create_touch_delete():
    s = Store()
    s.write([WriteOp("create", rel("ns:a#viewer@user:alice"))])
    with pytest.raises(AlreadyExists):
        s.write([WriteOp("create", rel("ns:a#viewer@user:alice"))])
    # touch is an upsert
    s.write([WriteOp("touch", rel("ns:a#viewer@user:alice"))])
    assert len(s) == 1
    # delete is idempotent
    s.write([WriteOp("delete", rel("ns:a#viewer@user:alice"))])
    s.write([WriteOp("delete", rel("ns:a#viewer@user:alice"))])
    assert len(s) == 0


def test_store_preconditions():
    s = Store()
    s.write(touch("ns:a#viewer@user:alice"))
    # must-not-exist fails when it exists
    with pytest.raises(PreconditionFailed):
        s.write(
            touch("ns:b#viewer@user:bob"),
            [Precondition(RelationshipFilter("ns", "a", "viewer"), must_exist=False)],
        )
    # must-exist passes
    s.write(
        touch("ns:b#viewer@user:bob"),
        [Precondition(RelationshipFilter("ns", "a", "viewer"), must_exist=True)],
    )
    assert len(s) == 2
    # filter with subject fields
    assert s.exists(RelationshipFilter(subject_type="user", subject_id="bob"))
    assert not s.exists(RelationshipFilter(subject_type="user", subject_id="carol"))


def test_store_read_and_delete_by_filter():
    s = Store()
    s.write(touch(
        "pod:ns1/a#viewer@user:alice",
        "pod:ns1/b#viewer@user:alice",
        "pod:ns2/c#viewer@user:bob",
        "ns:ns1#viewer@user:alice",
    ))
    got = {str(r) for r in s.read(RelationshipFilter(resource_type="pod"))}
    assert got == {
        "pod:ns1/a#viewer@user:alice",
        "pod:ns1/b#viewer@user:alice",
        "pod:ns2/c#viewer@user:bob",
    }
    n = s.delete_by_filter(
        RelationshipFilter(resource_type="pod", subject_type="user",
                           subject_id="alice"))
    assert n == 2
    assert len(s) == 2


def test_store_expiration():
    s = Store()
    now = time.time()
    s.write([
        WriteOp("touch", Relationship("ns", "a", "viewer", "user", "x",
                                      expiration=now - 10)),
        WriteOp("touch", Relationship("ns", "b", "viewer", "user", "x",
                                      expiration=now + 1000)),
    ])
    live = {r.resource_id for r in s.read(RelationshipFilter(resource_type="ns"))}
    assert live == {"b"}
    # an expired tuple does not block CREATE
    s.write([WriteOp("create", Relationship("ns", "a", "viewer", "user", "x"))])


def test_store_watch_log():
    s = Store()
    r0 = s.revision
    s.write(touch("ns:a#viewer@user:alice"))
    s.write([WriteOp("delete", rel("ns:a#viewer@user:alice"))])
    recs = s.watch_since(r0)
    assert [(r.op, str(r.rel)) for r in recs] == [
        (2, "ns:a#viewer@user:alice"),
        (3, "ns:a#viewer@user:alice"),
    ]


def test_store_bulk_load_and_snapshot():
    s = Store()
    n = 1000
    s.bulk_load({
        "resource_type": ["pod"] * n,
        "resource_id": [f"p{i}" for i in range(n)],
        "relation": ["viewer"] * n,
        "subject_type": ["user"] * n,
        "subject_id": [f"u{i % 7}" for i in range(n)],
    })
    assert len(s) == n
    snap = s.snapshot()
    assert len(snap.cols) == n
    # single-row ops still work after bulk load (lazy index build)
    s.write([WriteOp("delete", rel("pod:p0#viewer@user:u0"))])
    assert len(s) == n - 1


def test_store_bulk_load_prebuilds_index_in_background():
    """bulk_load kicks off the big-chunk index build on a background
    thread, so the first write joins it instead of paying the full
    hash+sort inline (bench first_write_after_bulk target <1s @ 10M)."""
    from spicedb_kubeapi_proxy_tpu.engine.store import INDEX_SMALL_CHUNK

    s = Store()
    n = INDEX_SMALL_CHUNK + 100  # above the sorted-index threshold
    s.bulk_load({
        "resource_type": ["pod"] * n,
        "resource_id": [f"p{i}" for i in range(n)],
        "relation": ["viewer"] * n,
        "subject_type": ["user"] * n,
        "subject_id": [f"u{i % 7}" for i in range(n)],
    })
    t = s._prebuild_thread
    assert t is not None
    t.join(timeout=30)
    assert not t.is_alive()
    # the prebuilt index is stashed, keyed by chunk identity
    assert id(s._chunks[0]) in s._index._prebuilt
    # first write consumes it (no rebuild) and behaves correctly
    s.write([WriteOp("delete", rel("pod:p3#viewer@user:u3"))])
    assert s._index._prebuilt == {}
    assert s._prebuild_thread is None
    assert len(s) == n - 1
    # touch of an existing key replaces, not duplicates (index finds rows)
    before = len(s)
    s.write([WriteOp("touch", rel("pod:p4#viewer@user:u4"))])
    assert len(s) == before


def test_store_back_to_back_bulk_loads_leak_no_prebuilt_entries():
    """A second bulk_load joins the first load's prebuild thread before
    spawning its own, so no abandoned thread can publish a stale sorted
    index after sync() has passed its chunk."""
    from spicedb_kubeapi_proxy_tpu.engine.store import INDEX_SMALL_CHUNK

    s = Store()
    n = INDEX_SMALL_CHUNK + 10
    for batch in range(2):
        s.bulk_load({
            "resource_type": ["pod"] * n,
            "resource_id": [f"b{batch}/p{i}" for i in range(n)],
            "relation": ["viewer"] * n,
            "subject_type": ["user"] * n,
            "subject_id": [f"u{i % 5}" for i in range(n)],
        })
    s.write([WriteOp("delete", rel("pod:b0/p0#viewer@user:u0"))])
    assert s._index._prebuilt == {}
    assert len(s._index._sorted) == 2  # both big chunks indexed exactly once
    assert len(s) == 2 * n - 1


# ---------------------------------------------------------------------------
# Engine write validation
# ---------------------------------------------------------------------------

SCHEMA = """
use expiration

definition user {}
definition group {
  relation member: user | group#member
}
definition namespace {
  relation creator: user
  relation viewer: user | group#member | user:*
  permission edit = creator
  permission view = viewer + creator
}
definition pod {
  relation namespace: namespace
  relation creator: user
  relation viewer: user with expiration
  permission edit = creator
  permission view = viewer + creator + namespace->view
}
"""


def make_engine(*rels_: str) -> Engine:
    e = Engine(schema=parse_schema(SCHEMA))
    if rels_:
        e.write_relationships(touch(*rels_))
    return e


def test_engine_write_validation():
    e = make_engine()
    with pytest.raises(SchemaViolation, match="no relation"):
        e.write_relationships(touch("namespace:a#nope@user:x"))
    with pytest.raises(SchemaViolation, match="not writable"):
        e.write_relationships(touch("namespace:a#view@user:x"))
    with pytest.raises(SchemaViolation, match="not allowed"):
        e.write_relationships(touch("namespace:a#creator@group:g#member"))
    with pytest.raises(SchemaViolation, match="unknown resource type"):
        e.write_relationships(touch("zebra:a#creator@user:x"))
    with pytest.raises(SchemaViolation, match="not allowed"):
        e.write_relationships(touch("namespace:a#creator@user:*"))
    with pytest.raises(SchemaViolation, match="expiring"):
        e.write_relationships([WriteOp("touch", Relationship(
            "namespace", "a", "creator", "user", "x",
            expiration=time.time() + 60))])
    # allowed cases
    e.write_relationships(touch(
        "namespace:a#viewer@user:*",
        "namespace:a#viewer@group:g#member",
        "pod:a/p#namespace@namespace:a",
    ))


# ---------------------------------------------------------------------------
# Oracle sanity (hand-computed expectations)
# ---------------------------------------------------------------------------


def test_oracle_basics():
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns1#viewer@user:bob",
        "pod:ns1/p1#namespace@namespace:ns1",
        "pod:ns1/p1#creator@user:carol",
    )
    o = e.oracle()
    assert o.check("namespace", "ns1", "view", "user", "alice")  # creator
    assert o.check("namespace", "ns1", "view", "user", "bob")  # viewer
    assert not o.check("namespace", "ns1", "edit", "user", "bob")
    # arrow: pod view via namespace->view
    assert o.check("pod", "ns1/p1", "view", "user", "alice")
    assert o.check("pod", "ns1/p1", "view", "user", "bob")
    assert o.check("pod", "ns1/p1", "view", "user", "carol")
    assert not o.check("pod", "ns1/p1", "edit", "user", "bob")
    assert o.lookup_resources("pod", "view", "user", "bob") == {"ns1/p1"}


def test_oracle_nested_groups_and_wildcard():
    e = make_engine(
        "group:eng#member@user:dev1",
        "group:all#member@group:eng#member",
        "namespace:ns#viewer@group:all#member",
        "namespace:open#viewer@user:*",
    )
    o = e.oracle()
    assert o.check("namespace", "ns", "view", "user", "dev1")
    assert not o.check("namespace", "ns", "view", "user", "outsider")
    assert o.check("namespace", "open", "view", "user", "anyone")


def test_oracle_cycle_terminates():
    e = make_engine(
        "group:a#member@group:b#member",
        "group:b#member@group:a#member",
        "group:b#member@user:u",
        "namespace:ns#viewer@group:a#member",
    )
    o = e.oracle()
    assert o.check("namespace", "ns", "view", "user", "u")
    assert not o.check("namespace", "ns", "view", "user", "v")


# ---------------------------------------------------------------------------
# TPU path vs oracle equivalence
# ---------------------------------------------------------------------------


def assert_engine_matches_oracle(e: Engine, subjects=None):
    """Exhaustively compare engine.check_bulk and lookup_resources against
    the oracle for every (type, object, permission) x subject."""
    o = e.oracle()
    snap = e.store.snapshot()
    if subjects is None:
        uid = snap.types.lookup("user")
        subjects = [
            ("user", snap.objects[uid].string(i))
            for i in range(2, len(snap.objects[uid]))
        ] if uid is not None and uid in snap.objects else []
        subjects.append(("user", "zz-unknown"))
    items, expect = [], []
    for tname, d in e.schema.definitions.items():
        tid = snap.types.lookup(tname)
        if tid is None or tid not in snap.objects:
            continue
        ids = [snap.objects[tid].string(i)
               for i in range(2, len(snap.objects[tid]))]
        for perm in list(d.permissions) + list(d.relations):
            for oid in ids:
                for st, sid in subjects:
                    items.append(CheckItem(tname, oid, perm, st, sid))
                    expect.append(o.check(tname, oid, perm, st, sid))
    got = e.check_bulk(items)
    bad = [
        (items[i], expect[i], got[i])
        for i in range(len(items)) if expect[i] != got[i]
    ]
    assert not bad, f"{len(bad)}/{len(items)} mismatches; first 5: {bad[:5]}"

    # lookup_resources equivalence on permissions
    for tname, d in e.schema.definitions.items():
        for perm in d.permissions:
            for st, sid in subjects:
                got_ids = set(e.lookup_resources(tname, perm, st, sid))
                want = o.lookup_resources(tname, perm, st, sid)
                assert got_ids == want, (tname, perm, st, sid, got_ids, want)


def test_tpu_matches_oracle_reference_style():
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns1#viewer@user:bob",
        "namespace:ns2#creator@user:bob",
        "pod:ns1/p1#namespace@namespace:ns1",
        "pod:ns1/p2#namespace@namespace:ns1",
        "pod:ns2/q#namespace@namespace:ns2",
        "pod:ns2/q#viewer@user:alice",
        "pod:ns1/p1#creator@user:carol",
        "group:eng#member@user:dev1",
        "group:all#member@group:eng#member",
        "namespace:ns2#viewer@group:all#member",
        "namespace:open#viewer@user:*",
    )
    assert_engine_matches_oracle(e)


INTERSECT_SCHEMA = """
definition user {}
definition group {
  relation member: user | group#member
}
definition doc {
  relation owner: user
  relation reader: user | group#member
  relation banned: user
  relation org: org
  permission read = (reader + owner) - banned
  permission audit = reader & owner
  permission admin = org->admin
  permission super = org->admin & owner
}
definition org {
  relation admin: user
  relation parent: org
  permission all_admin = admin + parent->all_admin
}
"""


def test_tpu_matches_oracle_intersect_exclude_arrows():
    e = Engine(schema=parse_schema(INTERSECT_SCHEMA))
    e.write_relationships(touch(
        "doc:d1#owner@user:o",
        "doc:d1#reader@user:r",
        "doc:d1#reader@user:o",
        "doc:d1#banned@user:o",
        "doc:d2#reader@group:g#member",
        "doc:d2#banned@user:m1",
        "group:g#member@user:m1",
        "group:g#member@user:m2",
        "doc:d3#org@org:acme",
        "org:acme#admin@user:boss",
        "org:acme#parent@org:parent",
        "org:parent#admin@user:grandboss",
        "doc:d3#owner@user:boss",
    ))
    o = e.oracle()
    # sanity: exclusion beats union
    assert not o.check("doc", "d1", "read", "user", "o")
    assert o.check("doc", "d1", "read", "user", "r")
    assert o.check("doc", "d2", "read", "user", "m2")
    assert not o.check("doc", "d2", "read", "user", "m1")
    # multi-hop arrow recursion
    assert o.check("org", "acme", "all_admin", "user", "grandboss")
    assert o.check("doc", "d3", "admin", "user", "boss")
    assert o.check("doc", "d3", "super", "user", "boss")
    assert not o.check("doc", "d3", "super", "user", "grandboss")
    assert_engine_matches_oracle(e)


def test_tpu_deep_chain_10_hops():
    # BASELINE config 4 shape: 10-hop org->team->user chains
    chain = ["group:g%d#member@group:g%d#member" % (i, i + 1) for i in range(10)]
    e = make_engine(
        *chain,
        "group:g10#member@user:deep",
        "namespace:ns#viewer@group:g0#member",
    )
    o = e.oracle()
    assert o.check("namespace", "ns", "view", "user", "deep")
    assert_engine_matches_oracle(e)


def test_tpu_expiration_mask():
    now = time.time()
    e = make_engine()
    e.write_relationships([
        WriteOp("touch", Relationship("pod", "a/p", "viewer", "user", "u1",
                                      expiration=now + 3600)),
        WriteOp("touch", Relationship("pod", "a/q", "viewer", "user", "u1",
                                      expiration=now - 5)),
    ])
    assert e.check(CheckItem("pod", "a/p", "view", "user", "u1"))
    assert not e.check(CheckItem("pod", "a/q", "view", "user", "u1"))
    # lookup sees only the unexpired one
    assert e.lookup_resources("pod", "view", "user", "u1") == ["a/p"]


def test_tpu_matches_oracle_fuzz():
    rng = np.random.default_rng(42)
    for trial in range(6):
        e = Engine(schema=parse_schema(INTERSECT_SCHEMA))
        users = [f"u{i}" for i in range(6)]
        groups = [f"g{i}" for i in range(4)]
        docs = [f"d{i}" for i in range(8)]
        orgs = [f"o{i}" for i in range(4)]
        ops = []
        for g in groups:
            for u in rng.choice(users, size=2, replace=False):
                ops.append(f"group:{g}#member@user:{u}")
            if rng.random() < 0.5:
                g2 = rng.choice(groups)
                if g2 != g:
                    ops.append(f"group:{g}#member@group:{g2}#member")
        for d in docs:
            for u in rng.choice(users, size=2, replace=False):
                ops.append(f"doc:{d}#reader@user:{u}")
            if rng.random() < 0.6:
                ops.append(f"doc:{d}#owner@user:{rng.choice(users)}")
            if rng.random() < 0.4:
                ops.append(f"doc:{d}#banned@user:{rng.choice(users)}")
            if rng.random() < 0.6:
                ops.append(f"doc:{d}#reader@group:{rng.choice(groups)}#member")
            if rng.random() < 0.6:
                ops.append(f"doc:{d}#org@org:{rng.choice(orgs)}")
        for o_ in orgs:
            ops.append(f"org:{o_}#admin@user:{rng.choice(users)}")
            o2 = rng.choice(orgs)
            if o2 != o_:
                ops.append(f"org:{o_}#parent@org:{o2}")
        e.write_relationships(touch(*set(ops)))
        assert_engine_matches_oracle(
            e, subjects=[("user", u) for u in users] + [("user", "nobody")]
        )


def test_closured_block_fuzz_matches_oracle(monkeypatch):
    """Randomized recursive-group graphs with the dense threshold forced
    low, fuzzing the closure machinery: random group→group edges (chains,
    diamonds, cycles), wildcard grants, and — in half the trials —
    expiring group→group edges, which must disqualify the self-pair from
    closure (expiring edges ride the residual path) without losing oracle
    parity either way."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    rng = np.random.default_rng(1234)
    schema = parse_schema("""
use expiration

definition user {}
definition group { relation member: user | group#member with expiration }
definition namespace {
  relation viewer: group#member | user:*
  permission view = viewer
}
""")
    saw_closured = saw_unclosured = False
    for trial in range(8):
        e = Engine(schema=schema)
        users = [f"u{i}" for i in range(5)]
        groups = [f"g{i}" for i in range(7)]
        ops = []
        for g in groups:
            for u in rng.choice(users, size=2, replace=False):
                ops.append(WriteOp("touch", rel(f"group:{g}#member@user:{u}")))
        n_gg = int(rng.integers(3, 9))
        expiring_trial = trial % 2 == 0
        seen_gg = set()
        for _ in range(n_gg):
            a, b = rng.choice(groups, size=2, replace=False)
            if (a, b) in seen_gg:
                continue
            seen_gg.add((a, b))
            exp_ = (time.time() + 1000
                    if expiring_trial and rng.random() < 0.4 else None)
            ops.append(WriteOp("touch", Relationship(
                "group", a, "member", "group", b,
                subject_relation="member", expiration=exp_)))
        for i in range(4):
            g = rng.choice(groups)
            ops.append(WriteOp("touch", rel(
                f"namespace:ns{i}#viewer@group:{g}#member")))
        if rng.random() < 0.3:
            ops.append(WriteOp("touch", rel("namespace:ns0#viewer@user:*")))
        e.write_relationships(ops)
        cg = e.compiled()
        has_closured = any(b.closured for b in cg.blocks)
        if expiring_trial and any(
                op.rel.expiration is not None for op in ops):
            assert not has_closured, \
                "expiring self-edges must disqualify closure"
            saw_unclosured = True
        saw_closured = saw_closured or has_closured
        subjects = ([("user", u) for u in users]
                    + [("group", g) for g in groups[:2]]
                    + [("user", "nobody")])
        assert_engine_matches_oracle(e, subjects=subjects)
        # random delete batch (the re-close path: cycle edges, leaf
        # edges, already-deleted idempotence), then parity again
        del_ops = []
        for op in rng.choice(len(ops), size=min(4, len(ops)),
                             replace=False).tolist():
            if ops[op].rel.expiration is None:
                del_ops.append(WriteOp("delete", ops[op].rel))
        if del_ops:
            e.write_relationships(del_ops)
            e.write_relationships(del_ops)  # idempotent re-delete
            assert_engine_matches_oracle(e, subjects=subjects)
    assert saw_closured and saw_unclosured, "fuzz must cover both paths"


def test_dense_block_path_matches_oracle(monkeypatch):
    """Force the dense MXU block path (normally >=1024 edges per block) on
    the fuzz graphs and assert oracle parity — covers block splitting,
    local-coordinate construction, and the matmul hop (review finding:
    blocks path untested at default thresholds)."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    rng = np.random.default_rng(7)
    e = Engine(schema=parse_schema(INTERSECT_SCHEMA))
    users = [f"u{i}" for i in range(6)]
    ops = set()
    for g in range(4):
        for u in rng.choice(users, size=2, replace=False):
            ops.add(f"group:g{g}#member@user:{u}")
    for d in range(10):
        for u in rng.choice(users, size=2, replace=False):
            ops.add(f"doc:d{d}#reader@user:{u}")
        ops.add(f"doc:d{d}#owner@user:{rng.choice(users)}")
        if rng.random() < 0.5:
            ops.add(f"doc:d{d}#banned@user:{rng.choice(users)}")
        ops.add(f"doc:d{d}#reader@group:g{rng.integers(4)}#member")
        ops.add(f"doc:d{d}#org@org:o{rng.integers(3)}")
    for o_ in range(3):
        ops.add(f"org:o{o_}#admin@user:{rng.choice(users)}")
    e.write_relationships(touch(*ops))
    cg = e.compiled()
    assert cg.blocks, "expected dense blocks with DENSE_MIN_EDGES=1"
    assert len(cg.res_idx) < cg.n_edges, "some edges must leave the residual"
    assert_engine_matches_oracle(
        e, subjects=[("user", u) for u in users] + [("user", "nobody")]
    )


def test_closure_pairs_helper():
    """Reflexive-transitive closure of a COO self-block: chains complete,
    cycles converge, the diagonal is always present, and oversized
    closures bail to None."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    # chain 0 -> 1 -> 2 (edge src->dst flows src's value to dst)
    dl, sl = R._closure_pairs(np.array([1, 2], dtype=np.int32),
                              np.array([0, 1], dtype=np.int32), 4)
    pairs = set(zip(sl.tolist(), dl.tolist()))
    assert {(0, 1), (1, 2), (0, 2)} <= pairs  # incl. the composed hop
    assert {(i, i) for i in range(4)} <= pairs  # diagonal
    # 2-cycle converges, reaching each other and themselves
    dl, sl = R._closure_pairs(np.array([1, 0], dtype=np.int32),
                              np.array([0, 1], dtype=np.int32), 2)
    assert set(zip(sl.tolist(), dl.tolist())) == {
        (0, 1), (1, 0), (0, 0), (1, 1)}
    # cap: a complete bipartite-ish blowup past the limit returns None
    old = R.CLOSURE_MAX_PAIRS
    try:
        R.CLOSURE_MAX_PAIRS = 4
        big_d = np.arange(1, 9, dtype=np.int32)
        big_s = np.zeros(8, dtype=np.int32)
        assert R._closure_pairs(big_d, big_s, 16) is None
    finally:
        R.CLOSURE_MAX_PAIRS = old


NESTED_GROUP_SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition namespace {
  relation viewer: group#member
  permission view = viewer
}
"""


def _nested_group_engine(depth: int = 6, fan: int = 3) -> Engine:
    """A strictly layered group tree: users in leaf groups, each layer a
    member of the next, namespaces viewing the root groups."""
    e = Engine(schema=parse_schema(NESTED_GROUP_SCHEMA))
    ops = []
    for g in range(fan):
        ops.append(f"group:l0-{g}#member@user:u{g}")
        for d in range(1, depth):
            ops.append(f"group:l{d}-{g}#member@group:l{d - 1}-{g}#member")
        ops.append(f"namespace:ns{g}#viewer@group:l{depth - 1}-{g}#member")
    e.write_relationships(touch(*ops))
    return e


def test_closured_self_block_peels_nested_groups(monkeypatch):
    """With the group#member self-pair densified, its block holds the
    closure and the range PEELS: deep nested-group membership resolves
    without core iterations (BASELINE config 3's shape)."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = _nested_group_engine()
    cg = e.compiled()
    closured = [b for b in cg.blocks if b.closured]
    assert closured, "group#member self-pair must be closured"
    assert all(b.level % 2 == 0 or b.level == 0 for b in closured)
    assert_engine_matches_oracle(e)
    # u0 sees ns0 through 6 membership hops in ONE core iteration (the
    # convergence probe): closure + peel, no per-hop iteration
    fut = e.check_bulk_async(
        [CheckItem("namespace", "ns0", "view", "user", "u0"),
         CheckItem("namespace", "ns1", "view", "user", "u0")])
    assert fut.result() == [True, False]
    assert fut.iterations() <= 1


def test_closured_block_recursive_group_cycle(monkeypatch):
    """Instance CYCLES inside the closured self-pair (mutually recursive
    groups) stay correct — closure covers them without iteration."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = Engine(schema=parse_schema(NESTED_GROUP_SCHEMA))
    e.write_relationships(touch(
        "group:a#member@user:alice",
        "group:a#member@group:b#member",
        "group:b#member@group:a#member",  # a <-> b cycle
        "group:c#member@group:b#member",
        "namespace:ns#viewer@group:c#member",
    ))
    cg = e.compiled()
    assert any(b.closured for b in cg.blocks)
    assert_engine_matches_oracle(e)
    assert e.check_bulk([
        CheckItem("namespace", "ns", "view", "user", "alice")]) == [True]


def test_closured_block_write_paths(monkeypatch):
    """Writes against a closured self-pair stay fully consistent: adds
    and deletes of member edges are visible on the next read (closure
    cells are derived, so deletes force the re-closing recompile)."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = _nested_group_engine(depth=4)
    assert any(b.closured for b in e.compiled().blocks)
    chk = lambda u, ns: e.check_bulk(  # noqa: E731
        [CheckItem("namespace", ns, "view", "user", u)])[0]
    assert chk("u1", "ns1")
    # delete a mid-chain membership edge: the chain must break
    e.write_relationships([WriteOp("delete", rel(
        "group:l2-1#member@group:l1-1#member"))])
    assert not chk("u1", "ns1")
    # re-add it: the chain must re-form
    e.write_relationships(touch("group:l2-1#member@group:l1-1#member"))
    assert chk("u1", "ns1")
    assert_engine_matches_oracle(e)


def test_closured_core_block_expiring_touch_recompiles(monkeypatch):
    """Review regression: a TOUCH attaching an expiration to an edge of a
    CORE-level closured block (self-pair kept in the core by a
    cross-range cycle, so _level_order_ok passes) must force a recompile
    — otherwise multi-hop closure cells derived through the edge outlive
    its expiration (permanent over-allow)."""
    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = Engine(schema=parse_schema("""
use expiration

definition user {}
definition team { relation member: group#member }
definition group { relation member: user | group#member with expiration | team#member }
definition namespace {
  relation viewer: group#member
  permission view = viewer
}
"""))
    now = time.time()
    e.write_relationships(touch(
        "group:a#member@user:alice",
        "group:b#member@group:a#member",
        "group:c#member@group:b#member",
        # cross-range cycle keeps group#member in the iterated core
        "team:t#member@group:c#member",
        "group:d#member@team:t#member",
        "namespace:ns#viewer@group:c#member",
    ))
    cg = e.compiled()
    core_closured = [b for b in cg.blocks if b.closured and b.level == 0]
    assert core_closured, "self-pair must be closured inside the core"
    item = CheckItem("namespace", "ns", "view", "user", "alice")
    assert e.check_bulk([item], now=now) == [True]
    # touch the mid-chain edge with an expiration 50s out
    e.write_relationships([WriteOp("touch", Relationship(
        "group", "b", "member", "group", "a",
        subject_relation="member", expiration=now + 50))])
    assert e.check_bulk([item], now=now + 10) == [True]  # still valid
    assert e.check_bulk([item], now=now + 100) == [False]  # expired


def test_closured_block_delete_recloses_incrementally(monkeypatch):
    """A membership delete inside a closured block re-closes that block
    from its base edges in O(block) — no full graph recompile — and a
    surviving alternative path keeps derived reachability alive (the
    dead-cell approach would have under-allowed it)."""
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    import spicedb_kubeapi_proxy_tpu.ops.reachability as R

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = Engine(schema=parse_schema(NESTED_GROUP_SCHEMA))
    # two paths a->b: direct, and via c (a -> c -> b)
    e.write_relationships(touch(
        "group:a#member@user:alice",
        "group:b#member@group:a#member",   # direct
        "group:c#member@group:a#member",
        "group:b#member@group:c#member",   # alternative
        "group:z#member@user:zed",
        "namespace:ns#viewer@group:b#member",
    ))
    assert any(b.closured for b in e.compiled().blocks)
    item = CheckItem("namespace", "ns", "view", "user", "alice")
    assert e.check_bulk([item]) == [True]
    compiles = metrics.counter("engine_graph_compiles_total").value
    inc = metrics.counter("engine_graph_incremental_updates_total").value
    # delete the direct edge: reachability survives via c
    e.write_relationships([WriteOp("delete", rel(
        "group:b#member@group:a#member"))])
    assert e.check_bulk([item]) == [True]
    # delete the alternative too: now revoked
    e.write_relationships([WriteOp("delete", rel(
        "group:b#member@group:c#member"))])
    assert e.check_bulk([item]) == [False]
    assert metrics.counter("engine_graph_compiles_total").value == compiles, \
        "closured deletes must not trigger a full recompile"
    assert metrics.counter(
        "engine_graph_incremental_updates_total").value >= inc + 2
    assert_engine_matches_oracle(e)


def test_closured_block_sharded_parity(monkeypatch):
    """The closured block rides the sharded path too (kept on the MXU
    when the graph axis divides its src range, folded to closure edges
    when it does not) — parity against the single-chip engine."""
    import jax

    import spicedb_kubeapi_proxy_tpu.ops.reachability as R
    from spicedb_kubeapi_proxy_tpu.parallel import make_mesh

    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = make_mesh(4, devices=devs[:4])
    e1 = _nested_group_engine()
    em = Engine(schema=parse_schema(NESTED_GROUP_SCHEMA), mesh=mesh)
    ops = [str(r) for r in e1.read_relationships(RelationshipFilter())]
    em.write_relationships(touch(*ops))
    assert any(b.closured for b in em.compiled().blocks)
    items = [CheckItem("namespace", f"ns{g}", "view", "user", f"u{u}")
             for g in range(3) for u in range(3)]
    assert em.check_bulk(items) == e1.check_bulk(items)
    for g in range(3):
        assert em.lookup_resources("namespace", "view", "user", f"u{g}") \
            == e1.lookup_resources("namespace", "view", "user", f"u{g}")
    # a closured-block delete must stay consistent on the sharded path
    # (re-closed matrices re-uploaded without a full sharded rebuild)
    run_before = em._sharded._run
    for eng in (em, e1):
        eng.write_relationships([WriteOp("delete", rel(
            "group:l3-1#member@group:l2-1#member"))])
    assert em.check_bulk(items) == e1.check_bulk(items)
    assert em._sharded._run is run_before, \
        "re-closed delete must reuse the jitted shard_map (fast path)"
    assert em.lookup_resources("namespace", "view", "user", "u1") \
        == e1.lookup_resources("namespace", "view", "user", "u1")


def test_check_bulk_mixed_subjects_and_unknowns():
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns1#viewer@user:bob",
    )
    got = e.check_bulk([
        CheckItem("namespace", "ns1", "view", "user", "alice"),
        CheckItem("namespace", "ns1", "view", "user", "bob"),
        CheckItem("namespace", "ns1", "edit", "user", "bob"),
        CheckItem("namespace", "nsX", "view", "user", "alice"),  # unknown obj
        CheckItem("wat", "x", "view", "user", "alice"),  # unknown type
        CheckItem("namespace", "ns1", "view", "robot", "r2"),  # unknown subj type
    ])
    assert got == [True, True, False, False, False, False]


def test_check_bulk_fast_encode_matches_reference_encode():
    """The inlined-cache batch encoder must agree exactly with per-item
    encode_target/encode_subject across every edge case: unknown types /
    permissions / object ids / subject ids, userset subjects, wildcard
    grants, duplicated subjects, and '' vs None subject relations."""
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns1#viewer@group:eng#member",
        "group:eng#member@user:carol",
        "namespace:open#viewer@user:*",
        "pod:ns1/api#namespace@namespace:ns1",
        "pod:ns1/api#viewer@user:dave",
    )
    base = [
        CheckItem("namespace", "ns1", "view", "user", "alice"),
        CheckItem("namespace", "ns1", "view", "user", "carol"),  # via group
        CheckItem("namespace", "ns1", "view", "group", "eng", "member"),
        CheckItem("namespace", "open", "view", "user", "anyone"),  # wildcard
        CheckItem("namespace", "ns1", "edit", "user", "carol"),
        CheckItem("pod", "ns1/api", "view", "user", "alice"),  # via arrow
        CheckItem("pod", "ns1/api", "view", "user", "dave"),
        CheckItem("namespace", "nsX", "view", "user", "alice"),  # unknown obj
        CheckItem("wat", "x", "view", "user", "alice"),  # unknown type
        CheckItem("namespace", "ns1", "wat", "user", "alice"),  # unknown perm
        CheckItem("namespace", "ns1", "view", "robot", "r2"),  # unknown stype
        CheckItem("namespace", "ns1", "view", "user", "nobody"),  # unknown sid
        CheckItem("namespace", "ns1", "view", "group", "eng", ""),  # ''==None
        CheckItem("namespace", "ns1", "view", "group", "eng"),
    ]
    items = base * 64  # 896 items: subject/offset caches get real reuse
    want_one = e.check_bulk(base)
    got = e.check_bulk(items)
    assert got == want_one * 64
    # the encoded arrays themselves must match per-item reference encoding
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds, q_slots, q_batch = e._encode_checks(cg, objs, items)
    for i, it in enumerate(items):
        assert q_slots[i] == cg.encode_target(
            it.resource_type, it.permission, it.resource_id, objs), i
        assert tuple(seeds[q_batch[i]].tolist()) == cg.encode_subject(
            it.subject_type, it.subject_id, it.subject_relation, objs), i


def test_check_bulk_fast_encode_randomized_parity():
    """Randomized parity fuzz (advisor r3): _encode_checks hand-inlines
    encode_target/encode_subject/Interner.lookup semantics for speed; a
    future change to the canonical encoders must not silently diverge from
    this hot path. 500 random items over known/unknown types, permissions,
    relations, object ids, subject relations, and wildcards."""
    import random

    rng = random.Random(0xC0FFEE)
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns2#viewer@group:eng#member",
        "group:eng#member@user:carol",
        "namespace:open#viewer@user:*",
        "pod:ns1/api#namespace@namespace:ns1",
    )
    types = ["namespace", "pod", "group", "user", "ghost-type"]
    perms = ["view", "edit", "member", "wat", "creator", "viewer"]
    ids = ["ns1", "ns2", "open", "eng", "alice", "carol", "ns1/api",
           "missing", "*", ""]
    srels = [None, "", "member", "ghost-rel"]
    items = [
        CheckItem(rng.choice(types), rng.choice(ids), rng.choice(perms),
                  rng.choice(types), rng.choice(ids), rng.choice(srels))
        for _ in range(500)
    ]
    cg = e.compiled()
    # post-compile interned ids: a write between compiled() and
    # _objects_by_name() interns ids past the compiled type size — both
    # encoders must agree on the size-overflow (treat-as-void) rule
    e.store.write([WriteOp("touch", parse_relationship(
        "namespace:late-ns#creator@user:late-user"))], [])
    objs = e._objects_by_name()
    items += [
        CheckItem("namespace", "late-ns", "view", "user", "alice"),
        CheckItem("namespace", "ns1", "view", "user", "late-user"),
    ]
    seeds, q_slots, q_batch = e._encode_checks(cg, objs, items)
    for i, it in enumerate(items):
        assert q_slots[i] == cg.encode_target(
            it.resource_type, it.permission, it.resource_id, objs), (i, it)
        assert tuple(seeds[q_batch[i]].tolist()) == cg.encode_subject(
            it.subject_type, it.subject_id, it.subject_relation, objs), \
            (i, it)


def test_check_bulk_chunked_pipeline_preserves_order(monkeypatch):
    """Bulk checks split into pipelined dispatch chunks must return the
    same per-item results in the same order, including a remainder chunk
    and subjects spanning chunk boundaries."""
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "namespace:ns2#viewer@user:bob",
    )
    items = [
        CheckItem("namespace", f"ns{1 + (i % 3)}", "view", "user",
                  ["alice", "bob", "zed"][i % 3])
        for i in range(25)
    ]
    want = e.check_bulk(items)  # single dispatch
    monkeypatch.setattr(Engine, "CHECK_PIPELINE_CHUNK", 7)  # 4 chunks, rem 4
    assert e.check_bulk(items) == want
    assert want.count(True) > 0 and want.count(False) > 0


# ---------------------------------------------------------------------------
# Review-finding regressions (engine core)
# ---------------------------------------------------------------------------


def test_write_atomicity_on_midbatch_conflict():
    s = Store()
    s.write(touch("ns:a#viewer@user:x", "ns:b#viewer@user:x"))
    rev = s.revision
    with pytest.raises(AlreadyExists):
        s.write([
            WriteOp("touch", rel("ns:a#viewer@user:x")),
            WriteOp("create", rel("ns:b#viewer@user:x")),
        ])
    # nothing applied, revision unchanged, no bogus watch events
    assert len(s) == 2
    assert s.exists(RelationshipFilter("ns", "a", "viewer"))
    assert s.revision == rev
    assert s.watch_since(rev) == []


def test_duplicate_update_in_one_write_rejected():
    from spicedb_kubeapi_proxy_tpu.engine import StoreError
    s = Store()
    with pytest.raises(StoreError, match="duplicate"):
        s.write(touch("ns:a#viewer@user:x", "ns:a#viewer@user:x"))


def test_userset_subject_does_not_match_wildcard():
    schema = parse_schema("""
    definition user {}
    definition group {
      relation member: user
    }
    definition ns {
      relation viewer: group#member | group:*
      permission view = viewer
    }
    """)
    e = Engine(schema=schema)
    e.write_relationships(touch("ns:x#viewer@group:*", "group:g#member@user:u"))
    o = e.oracle()
    assert not o.check("ns", "x", "view", "group", "g", "member")
    assert not e.check(CheckItem("ns", "x", "view", "group", "g", "member"))
    # but a concrete group subject does match the wildcard
    assert o.check("ns", "x", "view", "group", "anything")
    assert e.check(CheckItem("ns", "x", "view", "group", "anything"))


def test_contiguous_query_window_matches_gather():
    """The list-filter shape (one type's full permission range) takes a
    dynamic_slice fast path instead of the general fancy-index gather
    (ops/reachability.py query_async q_contiguous). Both extractions must
    agree bit-for-bit, auto-detection must engage on a contiguous window,
    and windows whose padded tail would clamp past the state tensor must
    fall back to the gather rather than shift."""
    rels = ["namespace:ns%d#viewer@user:alice" % i for i in range(0, 40, 3)]
    rels += ["namespace:ns%d#creator@user:alice" % i for i in range(1, 40, 7)]
    # >1024 pods: the pod window crosses the auto-detect gate (small
    # windows decline to the gather to bound per-length recompiles)
    rels += ["pod:p%d#namespace@namespace:ns%d" % (i, i % 40)
             for i in range(1100)]
    e = make_engine(*rels)
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds = np.asarray([cg.encode_subject("user", "alice", None, objs)],
                       dtype=np.int32)

    for tname, perm in [("pod", "view"), ("namespace", "view")]:
        off = cg.offset_of(tname, perm)
        n = cg.type_sizes[tname]
        qs = off + np.arange(n, dtype=np.int32)
        qb = np.zeros(n, dtype=np.int32)
        general = cg.query_async(seeds, qs, qb, q_contiguous=False).result()
        fast = cg.query_async(seeds, qs, qb, q_contiguous=True).result()
        auto = cg.query_async(seeds, qs, qb).result()
        assert np.array_equal(general, fast), (tname, perm)
        assert np.array_equal(general, auto), (tname, perm)
        assert general.any(), "fixture should grant something"
        assert not general.all(), "fixture should deny something"

    # non-contiguous queries must not be misdetected
    off = cg.offset_of("namespace", "view")
    qs = off + np.asarray([0, 2, 5], dtype=np.int32)
    qb = np.zeros(3, dtype=np.int32)
    got = cg.query_async(seeds, qs, qb).result()
    want = cg.query_async(seeds, qs, qb, q_contiguous=False).result()
    assert np.array_equal(got, want)

    # a window ending at the very top of the slot space must still match
    # (exact slice lengths: no clamp is possible, but keep the guard honest)
    lo = max(0, cg.M - 5)
    qs = lo + np.arange(5, dtype=np.int32)
    qb = np.zeros(5, dtype=np.int32)
    tail_fast = cg.query_async(seeds, qs, qb, q_contiguous=True).result()
    tail_gen = cg.query_async(seeds, qs, qb, q_contiguous=False).result()
    assert np.array_equal(tail_fast, tail_gen)

    # the fused-batch grid form (batcher shape): R rows x one shared window
    subjects = ["alice", "bob", "carol"]
    rels2 = ["namespace:ns0#viewer@user:bob", "namespace:ns7#viewer@user:bob"]
    e.write_relationships(touch(*rels2))
    cg = e.compiled()
    objs = e._objects_by_name()
    off = cg.offset_of("namespace", "view")
    n = cg.type_sizes["namespace"]
    seeds3 = np.asarray(
        [cg.encode_subject("user", s, None, objs) for s in subjects],
        dtype=np.int32)
    qs = np.tile(off + np.arange(n, dtype=np.int32), 3)
    qb = np.repeat(np.arange(3, dtype=np.int32), n)
    grid = cg.query_async(seeds3, qs, qb,
                          q_contig_grid=(off, n, 3)).result()
    gen = cg.query_async(seeds3, qs, qb).result()
    assert np.array_equal(grid, gen)
    assert grid[:n].any() and grid[n:2 * n].any(), "alice+bob see something"
    assert not grid[2 * n:].any(), "carol has no grants"

    # malformed grid promises (wrong total, zero rows) must fall back, not
    # mis-slice
    bad = cg.query_async(seeds3, qs, qb,
                         q_contig_grid=(off, n, 2)).result()
    assert np.array_equal(bad, gen)


def test_nonconvergence_raises_not_denies():
    from spicedb_kubeapi_proxy_tpu.ops.reachability import ConvergenceError
    chain = ["group:g%d#member@group:g%d#member" % (i, i + 1) for i in range(40)]
    e = make_engine(*chain, "group:g40#member@user:deep",
                    "namespace:ns#viewer@group:g0#member")
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds = np.asarray([cg.encode_subject("user", "deep", None, objs)],
                       dtype=np.int32)
    q = np.asarray([cg.encode_target("namespace", "view", "ns", objs)],
                   dtype=np.int32)
    with pytest.raises(ConvergenceError):
        cg.query(seeds, q, np.zeros(1, dtype=np.int32), max_iters=8)
    # with budget it converges and grants
    assert cg.query(seeds, q, np.zeros(1, dtype=np.int32), max_iters=128)[0]


def test_wildcard_expiration_validation():
    schema = parse_schema("""
    definition user {}
    definition ns {
      relation viewer: user:*
      permission view = viewer
    }
    """)
    e = Engine(schema=schema)
    with pytest.raises(SchemaViolation, match="expiring"):
        e.write_relationships([WriteOp("touch", Relationship(
            "ns", "a", "viewer", "user", "*", expiration=time.time() + 60))])


def test_noop_deletes_do_not_bump_revision():
    s = Store()
    s.write(touch("ns:a#viewer@user:x"))
    rev = s.revision
    s.delete_by_filter(RelationshipFilter(resource_type="pod"))
    assert s.revision == rev
    s.write([WriteOp("delete", rel("ns:zz#viewer@user:x"))])
    assert s.revision == rev


def test_delete_by_filter_preconditions_atomic():
    s = Store()
    s.write(touch("lock:l1#workflow@workflow:w1"))
    with pytest.raises(PreconditionFailed):
        s.delete_by_filter(
            RelationshipFilter(resource_type="lock"),
            [Precondition(RelationshipFilter("lock", "l1", "workflow",
                                             subject_id="other"),
                          must_exist=True)],
        )
    assert len(s) == 1


def test_jit_cache_shared_across_revisions():
    """Steady-state writes (same bucket layout) must not recompile
    (review finding: jit-per-CompiledGraph)."""
    e = make_engine("namespace:ns1#creator@user:alice")
    e.check(CheckItem("namespace", "ns1", "view", "user", "alice"))
    cg1 = e.compiled()
    # touch/delete an existing tuple: same interners, same buckets
    e.write_relationships(touch("namespace:ns1#viewer@user:bob"))
    e.check(CheckItem("namespace", "ns1", "view", "user", "bob"))
    cg2 = e.compiled()
    assert cg1 is not cg2
    assert cg1.signature() == cg2.signature()
    assert cg1._device["run"] is cg2._device["run"]


def test_reflexive_userset_identity_both_paths():
    e = make_engine("group:eng#member@user:u",
                    "namespace:ns#viewer@group:eng#member")
    o = e.oracle()
    assert o.check("group", "eng", "member", "group", "eng", "member")
    assert e.check(CheckItem("group", "eng", "member", "group", "eng", "member"))


def test_wildcard_resource_id_rejected():
    e = make_engine()
    with pytest.raises(SchemaViolation, match="wildcard"):
        e.write_relationships(touch("namespace:*#viewer@user:x"))


def test_store_read_does_not_hold_lock():
    s = Store()
    s.write(touch("ns:a#viewer@user:x", "ns:b#viewer@user:x"))
    rels = s.read(RelationshipFilter(resource_type="ns"))
    # read returns a list; a concurrent write must not deadlock
    s.write(touch("ns:c#viewer@user:x"))
    assert len(rels) == 2


def test_watch_trim_and_bisect():
    from spicedb_kubeapi_proxy_tpu.engine import StoreError
    s = Store()
    s.watch_retention = 10
    for i in range(20):
        s.write(touch(f"ns:n{i}#viewer@user:x"))
    recs = s.watch_since(s.revision - 1)
    assert len(recs) == 1
    with pytest.raises(StoreError, match="trimmed"):
        s.watch_since(0)


def test_schema_mixed_operators_require_parens():
    from spicedb_kubeapi_proxy_tpu.models import SchemaError
    with pytest.raises(SchemaError, match="parentheses"):
        parse_schema("""
        definition user {}
        definition d {
          relation a: user
          relation b: user
          relation c: user
          permission p = a + b & c
        }
        """)
    # same-operator chains still fine
    parse_schema("""
    definition user {}
    definition d {
      relation a: user
      relation b: user
      relation c: user
      permission p = a - b - c
      permission q = a + b + c
    }
    """)


# ---------------------------------------------------------------------------
# Incremental graph updates (engine write path without full recompiles)
# ---------------------------------------------------------------------------


def _compiles():
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    return metrics.counter("engine_graph_compiles_total").value


def _incrementals():
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    return metrics.counter("engine_graph_incremental_updates_total").value


def test_incremental_write_avoids_recompile():
    """Small writes after the first compile ride the delta segment: no
    full recompile, answers stay oracle-exact."""
    e = make_engine(
        "namespace:ns1#creator@user:alice",
        "pod:ns1/p1#namespace@namespace:ns1",
        "group:eng#member@user:dev",
        "namespace:ns2#viewer@group:eng#member",
    )
    e.compiled()
    c0, i0 = _compiles(), _incrementals()

    # create: new viewer tuple visible in a fully-consistent read
    e.write_relationships(touch("namespace:ns1#viewer@user:bob"))
    assert e.check(CheckItem("namespace", "ns1", "view", "user", "bob"))
    assert _compiles() == c0 and _incrementals() == i0 + 1
    assert_engine_matches_oracle(e)

    # delete: revoked immediately (base-edge invalidation)
    e.write_relationships(
        [WriteOp("delete", rel("namespace:ns1#creator@user:alice"))])
    assert not e.check(CheckItem("namespace", "ns1", "view", "user", "alice"))
    # arrow edge through the deleted namespace tuple is also gone
    assert not e.check(CheckItem("pod", "ns1/p1", "view", "user", "alice"))
    assert _compiles() == c0
    assert_engine_matches_oracle(e)

    # new subject AND new resource interned within their buckets
    e.write_relationships(touch("namespace:ns-new#viewer@user:carol"))
    assert e.check(CheckItem("namespace", "ns-new", "view", "user", "carol"))
    assert not e.check(CheckItem("namespace", "ns-new", "view", "user", "bob"))
    assert _compiles() == c0
    assert_engine_matches_oracle(e)

    # userset + arrow edges created incrementally
    e.write_relationships(touch(
        "group:eng#member@user:newdev",
        "pod:ns2/px#namespace@namespace:ns2",
    ))
    assert e.check(CheckItem("pod", "ns2/px", "view", "user", "newdev"))
    assert _compiles() == c0
    assert_engine_matches_oracle(e)


def test_incremental_expiration_retouch():
    """TOUCH refreshing a tuple's expiration invalidates the old edge and
    re-adds it with the new clock mask."""
    e = make_engine("pod:a/p#viewer@user:u")
    e.compiled()
    c0 = _compiles()
    now = time.time()
    # retouch with an already-expired timestamp: view revoked
    e.write_relationships([WriteOp("touch", Relationship(
        "pod", "a/p", "viewer", "user", "u", None, now - 10))])
    assert not e.check(CheckItem("pod", "a/p", "view", "user", "u"))
    # retouch back to never-expiring: restored
    e.write_relationships(touch("pod:a/p#viewer@user:u"))
    assert e.check(CheckItem("pod", "a/p", "view", "user", "u"))
    # future expiration honored at query time
    e.write_relationships([WriteOp("touch", Relationship(
        "pod", "a/p", "viewer", "user", "u", None, now + 3600))])
    assert e.check(CheckItem("pod", "a/p", "view", "user", "u"))
    assert not e.check(
        CheckItem("pod", "a/p", "view", "user", "u"), now=now + 7200)
    assert _compiles() == c0


def test_incremental_bucket_overflow_falls_back():
    """Interning objects past the padded bucket forces a full recompile —
    and the answers stay right."""
    e = make_engine("namespace:ns#viewer@user:u0")
    e.compiled()
    c0 = _compiles()
    # LANE-padded bucket is 128; blow past it
    e.write_relationships(touch(*[
        f"namespace:ns#viewer@user:u{i}" for i in range(1, 200)]))
    assert e.check(CheckItem("namespace", "ns", "view", "user", "u150"))
    assert _compiles() > c0
    assert_engine_matches_oracle(e, subjects=[("user", f"u{i}")
                                              for i in (0, 5, 150, 199)])


def test_incremental_after_bulk_load_falls_back():
    """bulk_load bypasses the watch log, so the next read recompiles
    rather than applying an impossible delta."""
    e = make_engine("namespace:a#viewer@user:u")
    e.compiled()
    c0 = _compiles()
    e.bulk_load({
        "resource_type": ["namespace"] * 2,
        "resource_id": ["b", "c"],
        "relation": ["viewer"] * 2,
        "subject_type": ["user"] * 2,
        "subject_id": ["u", "v"],
    })
    assert e.check(CheckItem("namespace", "b", "view", "user", "u"))
    assert e.check(CheckItem("namespace", "c", "view", "user", "v"))
    assert _compiles() > c0
    # and incremental service resumes afterwards
    c1 = _compiles()
    e.write_relationships(touch("namespace:d#viewer@user:w"))
    assert e.check(CheckItem("namespace", "d", "view", "user", "w"))
    assert _compiles() == c1


def test_incremental_dense_block_clear(monkeypatch):
    """Deleting an edge that lives in a dense MXU block clears the block
    cell (not just the residual)."""
    from spicedb_kubeapi_proxy_tpu.ops import reachability

    monkeypatch.setattr(reachability, "DENSE_MIN_EDGES", 4)
    e = make_engine(*[
        f"namespace:n{i}#viewer@user:u{i % 7}" for i in range(40)])
    cg = e.compiled()
    assert cg.blocks, "test needs a dense block to exist"
    c0 = _compiles()
    e.write_relationships(
        [WriteOp("delete", rel("namespace:n3#viewer@user:u3"))])
    assert not e.check(CheckItem("namespace", "n3", "view", "user", "u3"))
    # re-touch restores it through the delta segment
    e.write_relationships(touch("namespace:n3#viewer@user:u3"))
    assert e.check(CheckItem("namespace", "n3", "view", "user", "u3"))
    assert _compiles() == c0
    assert_engine_matches_oracle(e, subjects=[("user", f"u{i}")
                                              for i in range(7)])


def test_incremental_fuzz_against_oracle():
    """Randomized interleaving of creates/touches/deletes applied
    incrementally stays oracle-exact at every step."""
    rng = np.random.default_rng(42)
    e = Engine(schema=parse_schema(INTERSECT_SCHEMA))
    # seed one tuple per relation so every relation id is interned before
    # the first compile — a relation's FIRST-ever tuple is a (one-time)
    # full-recompile event by design, which would muddy the no-recompile
    # assertion below
    e.write_relationships(touch(
        "doc:d0#owner@user:u0",
        "group:g0#member@user:u1",
        "group:g1#member@user:u0",
        "doc:d0#reader@group:g0#member",
        "doc:d0#org@org:o0",
        "org:o0#admin@user:u2",
        "org:o1#parent@org:o0",
        "doc:d9#banned@user:u5",
    ))
    e.compiled()
    c0 = _compiles()
    live = set()
    users = [f"u{i}" for i in range(6)]
    for step in range(12):
        n_ops = int(rng.integers(1, 4))
        ops = []
        seen = set()
        for _ in range(n_ops):
            kind = rng.choice(["reader", "banned", "owner", "member", "org"])
            if kind == "member":
                s = f"group:g{rng.integers(2)}#member@user:{rng.choice(users)}"
            elif kind == "org":
                s = f"doc:d{rng.integers(3)}#org@org:o{rng.integers(2)}"
            else:
                s = f"doc:d{rng.integers(3)}#{kind}@user:{rng.choice(users)}"
            if s in seen:
                continue
            seen.add(s)
            if s in live and rng.random() < 0.5:
                ops.append(WriteOp("delete", rel(s)))
                live.discard(s)
            else:
                ops.append(WriteOp("touch", rel(s)))
                live.add(s)
        if ops:
            e.write_relationships(ops)
        assert_engine_matches_oracle(
            e, subjects=[("user", u) for u in users])
    assert _compiles() == c0, "fuzz writes must all apply incrementally"


# ---------------------------------------------------------------------------
# Vectorized store index
# ---------------------------------------------------------------------------


def test_store_index_big_chunk_paths(monkeypatch):
    """Chunks at/above the threshold use the sorted-hash index; lookups,
    overwrites, and deletes through it behave identically to the dict."""
    from spicedb_kubeapi_proxy_tpu.engine import store as store_mod

    monkeypatch.setattr(store_mod, "INDEX_SMALL_CHUNK", 8)
    s = Store()
    n = 500
    s.bulk_load({
        "resource_type": ["ns"] * n,
        "resource_id": [f"o{i}" for i in range(n)],
        "relation": ["viewer"] * n,
        "subject_type": ["user"] * n,
        "subject_id": [f"u{i % 37}" for i in range(n)],
    })
    # touch-refresh an existing tuple found via the sorted index
    s.write([WriteOp("touch", Relationship("ns", "o42", "viewer", "user",
                                           "u5", None, time.time() + 99))])
    assert len(s) == n  # replaced, not duplicated
    # create of an existing live tuple errors (found via sorted index)
    with pytest.raises(AlreadyExists):
        s.write([WriteOp("create", Relationship("ns", "o7", "viewer",
                                                "user", "u7"))])
    # delete via the index; subsequent create succeeds
    s.write([WriteOp("delete", Relationship("ns", "o7", "viewer", "user",
                                            "u7"))])
    assert len(s) == n - 1
    s.write([WriteOp("create", Relationship("ns", "o7", "viewer", "user",
                                            "u7"))])
    assert len(s) == n


def test_store_index_hash_collision_verified(monkeypatch):
    """Colliding hashes must not alias rows: lookups verify the actual
    key columns."""
    from spicedb_kubeapi_proxy_tpu.engine import store as store_mod

    monkeypatch.setattr(store_mod, "INDEX_SMALL_CHUNK", 2)
    # force EVERY hash equal: all rows land in one searchsorted run
    monkeypatch.setattr(
        store_mod, "_hash_key_cols",
        lambda *cols: np.zeros(np.broadcast(*cols).size or 1,
                               dtype=np.uint64).reshape(
            np.asarray(cols[0]).shape if np.asarray(cols[0]).shape else ()))
    monkeypatch.setattr(store_mod.native, "index_build",
                        lambda *a: None)  # force the python hash path
    s = Store()
    s.bulk_load({
        "resource_type": ["ns"] * 4,
        "resource_id": ["a", "b", "c", "d"],
        "relation": ["viewer"] * 4,
        "subject_type": ["user"] * 4,
        "subject_id": ["u1", "u2", "u3", "u4"],
    })
    with pytest.raises(AlreadyExists):
        s.write([WriteOp("create", Relationship("ns", "c", "viewer",
                                                "user", "u3"))])
    s.write([WriteOp("delete", Relationship("ns", "b", "viewer", "user",
                                            "u2"))])
    live = {r.resource_id for r in s.read(
        RelationshipFilter(resource_type="ns"))}
    assert live == {"a", "c", "d"}


# ---------------------------------------------------------------------------
# Stratified fixpoint (acyclic levels applied once; only cycles iterate)
# ---------------------------------------------------------------------------


def test_stratification_splits_kube_shaped_graph():
    """In the kube-shaped schema only the recursive group-membership
    ranges iterate; pod/namespace ranges are acyclic tail levels applied
    once — the dominant per-pod blocks stay out of the fixpoint loop."""
    e = make_engine(
        "group:a#member@group:b#member",   # recursion -> core
        "group:b#member@group:a#member",
        "group:b#member@user:u",
        "namespace:ns#viewer@group:a#member",
        "pod:ns/p#namespace@namespace:ns",
    )
    cg = e.compiled()
    assert cg.n_levels > 0
    offs = cg.range_offs
    lvl = {  # (type, rel) -> level
        k: int(cg.range_levels[int(np.searchsorted(offs, v, "right")) - 1])
        for k, v in cg.slot_offset.items()
    }
    assert lvl[("group", "member")] == 0  # recursive: iterated core
    # the value-dependency chain ns#view -> pod arrow term -> pod#view is
    # strictly layered tail (pod#namespace itself is a value sink: its
    # TUPLES define arrow edges, its slots feed nothing)
    assert 0 < lvl[("namespace", "view")] < lvl[("pod", "view")]
    assert lvl[("pod", "namespace")] > 0
    # and answers stay oracle-exact (core + levels compose correctly)
    assert_engine_matches_oracle(e)


def test_stratified_deep_acyclic_chain_converges_in_one_core_iter():
    """A 10-hop ACYCLIC chain needs zero core iterations of work — every
    hop is a one-shot level — so the iteration counter stays at the
    convergence-check minimum instead of growing with depth."""
    # (a recursive schema like org->parent->can_admin would stay core;
    # this chain uses 10 DISTINCT types so every hop is acyclic)
    schema = ["definition user {}"]
    for i in range(10):
        sub = "user" if i == 0 else f"t{i - 1}"
        schema.append(f"""
definition t{i} {{
  relation up: {sub}
  permission view = {'up' if i == 0 else 'up->view'}
}}""")
    e = Engine(schema=parse_schema("\n".join(schema)))
    ops = ["t0:x0#up@user:alice"]
    ops += [f"t{i}:x{i}#up@t{i - 1}:x{i - 1}" for i in range(1, 10)]
    e.write_relationships(touch(*ops))
    fut = e.check_bulk_async(
        [CheckItem("t9", "x9", "view", "user", "alice")])
    assert fut.result() == [True]
    # acyclic: the core loop only runs its convergence check
    assert fut.iterations() <= 2
    cg = e.compiled()
    assert cg.n_levels >= 10


def test_incremental_level_violation_forces_recompile():
    """A delta edge inverting the frozen stratification (a first-ever
    dependency direction) must fall back to a full recompile — applying
    it at the wrong phase would read a stale source."""
    e = Engine(schema=parse_schema("""
definition user {}
definition a {
  relation m: user | b#p
  permission p = m
}
definition b {
  relation m: user | a#p
  permission p = m
}
"""))
    # only a->b edges exist: acyclic, b depends on a
    e.write_relationships(touch("a:x#m@user:u", "b:y#m@a:x#p"))
    e.compiled()
    c0 = _compiles()
    # new edge b->a inverts the order (creates a cross-type cycle)
    e.write_relationships(touch("a:z#m@b:y#p"))
    assert e.check(CheckItem("a", "z", "p", "user", "u"))
    assert _compiles() == c0 + 1  # re-stratified via full recompile
    assert_engine_matches_oracle(e, subjects=[("user", "u")])

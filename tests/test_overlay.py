"""Delta-overlay write path (ISSUE 8): differential oracle parity across
interleaved write/delete/expiry churn with queries between every
mutation, the fallback edge cases that force a counted recompile
(closured-block expiration-attach, overlay overflow), the
compaction-swap-under-concurrent-dispatch race, overlay-full write
back-pressure, the mirror apply path (a replicated frame must never
shed), and decision-cache retirement at fold cadence."""

import threading
import time

import numpy as np
import pytest

import spicedb_kubeapi_proxy_tpu.ops.reachability as R
from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
from spicedb_kubeapi_proxy_tpu.engine.compaction import (
    MAX_RETRY_AFTER,
    MIN_RETRY_AFTER,
    OverlayBackpressure,
    validate_overlay_config,
)
from spicedb_kubeapi_proxy_tpu.engine.decision_cache import (
    DecisionCache,
    check_key,
)
from spicedb_kubeapi_proxy_tpu.engine.store import (
    RelationshipFilter,
    WriteOp,
)
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import (
    Relationship,
    parse_relationship as rel,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

SCHEMA = """
use expiration

definition user {}
definition group { relation member: user | group#member with expiration }
definition namespace {
  relation viewer: group#member | user | user with expiration
  permission view = viewer
}
"""


def build(delta_capacity: int = 256, n_users: int = 6, n_groups: int = 5,
          n_ns: int = 6) -> Engine:
    """Engine with every object pre-seeded into the slot layout (the
    overlay absorbs edges between EXISTING objects; a brand-new object
    is a layout fallback by design) and a compiled base."""
    e = Engine(schema=parse_schema(SCHEMA), delta_capacity=delta_capacity)
    ops = []
    for i in range(n_users):
        ops.append(WriteOp("touch", rel(f"group:g{i % n_groups}#member"
                                        f"@user:u{i}")))
    for i in range(n_ns):
        ops.append(WriteOp("touch", rel(f"namespace:ns{i}#viewer"
                                        f"@user:u{i % n_users}")))
        ops.append(WriteOp("touch", rel(
            f"namespace:ns{i}#viewer@group:g{i % n_groups}#member")))
    e.write_relationships(ops)
    e.compiled()
    # warm the device path so churn tests measure steady state
    e.check_bulk([CheckItem("namespace", "ns0", "view", "user", "u0")])
    return e


def fallback_value(reason: str) -> float:
    return metrics.counter("engine_graph_incremental_fallback_total",
                           reason=reason).value


def assert_oracle_parity(e: Engine, n_users=6, n_ns=6, n_groups=5):
    """Exhaustive namespace#view grid + a spot lookup, twice (the second
    round re-reads the same compiled graph)."""
    for _ in range(2):
        o = e.oracle()
        items, want = [], []
        for i in range(n_ns):
            for u in range(n_users):
                items.append(CheckItem("namespace", f"ns{i}", "view",
                                       "user", f"u{u}"))
                want.append(o.check("namespace", f"ns{i}", "view",
                                    "user", f"u{u}"))
        got = e.check_bulk(items)
        if got != want:
            # an expiration boundary may have passed between oracle and
            # engine reads; a real overlay bug reproduces fresh
            o = e.oracle()
            want = [o.check(it.resource_type, it.resource_id,
                            it.permission, it.subject_type, it.subject_id)
                    for it in items]
            got = e.check_bulk(items)
        bad = [(items[i], got[i], want[i])
               for i in range(len(items)) if got[i] != want[i]]
        assert not bad, bad[:5]
        u = f"u{n_users // 2}"
        got_l = set(e.lookup_resources("namespace", "view", "user", u))
        want_l = e.oracle().lookup_resources("namespace", "view",
                                             "user", u)
        assert got_l == want_l, (u, got_l, want_l)


def test_overlay_differential_randomized_churn():
    """Randomized interleaved write/delete/expiry churn with oracle
    parity after EVERY mutation, and ZERO full recompiles: every
    mutation between pre-seeded objects must ride the overlay."""
    e = build()
    rng = np.random.default_rng(7)
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    live: list[Relationship] = []
    exp_at = None
    for step in range(40):
        r = rng.random()
        if r < 0.35 or not live:
            rl = Relationship("namespace", f"ns{rng.integers(6)}",
                              "viewer", "user", f"u{rng.integers(6)}")
            e.write_relationships([WriteOp("touch", rl)])
            live.append(rl)
        elif r < 0.50:
            # expiring grant: dies while the test still queries
            exp_at = time.time() + 1.2
            rl = Relationship("namespace", f"ns{rng.integers(6)}",
                              "viewer", "user", f"u{rng.integers(6)}",
                              expiration=exp_at)
            e.write_relationships([WriteOp("touch", rl)])
            live.append(rl)
        elif r < 0.70:
            rl = live.pop(int(rng.integers(len(live))))
            e.write_relationships([WriteOp("delete", rl)])
        elif r < 0.85:
            # group membership churn (dense-block territory)
            rl = Relationship("group", f"g{rng.integers(5)}", "member",
                              "user", f"u{rng.integers(6)}")
            e.write_relationships([WriteOp("touch", rl)])
            live.append(rl)
        else:
            # re-touch an existing edge (overlay slot update, not a
            # second slot)
            rl = live[int(rng.integers(len(live)))]
            e.write_relationships([WriteOp("touch", rl)])
        assert_oracle_parity(e)
    if exp_at is not None:
        time.sleep(max(0.0, exp_at + 0.05 - time.time()))
        assert_oracle_parity(e)  # expired overlay edges are invisible
    assert metrics.counter("engine_graph_compiles_total").value \
        == compiles0, "steady-state churn must not recompile"
    assert e.compiled().n_delta > 0


def test_overlay_filter_delete_and_idempotent_redelete():
    e = build()
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    e.write_relationships([WriteOp(
        "touch", rel("namespace:ns1#viewer@user:u4"))])
    n = e.delete_relationships(RelationshipFilter(
        resource_type="namespace", resource_id="ns1"))
    assert n >= 1
    assert_oracle_parity(e)
    # idempotent re-delete of an already-dead base pair: no new dead-
    # ledger growth, still parity
    cg1 = e.compiled()
    e.write_relationships([WriteOp(
        "delete", rel("namespace:ns2#viewer@user:u2"))])
    cg2 = e.compiled()
    e.write_relationships([WriteOp(
        "delete", rel("namespace:ns2#viewer@user:u2"))])
    cg3 = e.compiled()
    assert cg3.n_dead == cg2.n_dead >= cg1.n_dead
    assert_oracle_parity(e)
    assert metrics.counter("engine_graph_compiles_total").value \
        == compiles0


def test_seeded_edge_killed_added_killed_and_folded_without_recompile():
    """A membership out of ``user.__self`` lives in a seeded part (sorted
    by source under a row pointer): a delete kills it where it lies
    (found by its source), a re-add rides the overlay, a second delete
    kills that, and a fold lays the base out again: no graph compile
    before the fold, no fresh trace at any point, parity after each."""
    e = build(n_users=40)
    base = e.compiled()
    src, _ = base.encode_subject("user", "u7", None, e._objects_by_name())
    dst = base.encode_target("group", "member", "g2",
                             e._objects_by_name())
    (pos,) = R._res_positions(base, src, dst)
    phase = int(np.searchsorted(base.res_level_bounds, pos, "right")) - 1
    assert base.seed.starts[phase] <= pos  # in the seeded part
    assert base.seed_mode(1) == "lookup"
    item = [CheckItem("group", "g2", "member", "user", "u7")]
    assert e.check_bulk(item) == [True]
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    lookups0 = metrics.counter("engine_seed_lookups_total").value
    traces0 = R._TRACE_BUILDS
    for op, want, n_delta in (("delete", False, 0), ("touch", True, 1),
                              ("delete", False, 1)):
        e.write_relationships([WriteOp(op, rel("group:g2#member@user:u7"))])
        cg = e.compiled()
        assert cg.res_src is base.res_src and cg.n_delta == n_delta
        assert cg.res_exp[pos] == -np.inf
        assert e.check_bulk(item) == [want]
        assert_oracle_parity(e, n_users=40)
    assert metrics.counter("engine_graph_compiles_total").value == compiles0
    c = e.enable_compaction(1.0)
    assert c.compact() is True
    e.close_compaction()
    folded = e.compiled()
    assert folded.res_src is not base.res_src and folded.n_delta == 0
    assert R._res_positions(folded, src, dst) == []
    assert folded.seed_edges() == base.seed_edges() - 1
    assert e.check_bulk(item) == [False]
    assert_oracle_parity(e, n_users=40)
    assert folded.signature() == base.signature()
    assert R._TRACE_BUILDS == traces0
    assert metrics.counter("engine_seed_lookups_total").value > lookups0


def test_closured_block_delete_recloses_and_expiry_attach_falls_back(
        monkeypatch):
    """The two fallback edge cases of the closured dense block: deleting
    a base group->group edge re-closes the block in place (NO recompile,
    parity held — derived multi-hop cells must die with it), while
    attaching an expiration to a closured pair cannot be expressed
    against the block and must take the counted closured-expiry
    fallback recompile."""
    monkeypatch.setattr(R, "DENSE_MIN_EDGES", 1)
    e = Engine(schema=parse_schema(SCHEMA), delta_capacity=256)
    ops = [WriteOp("touch", rel(f"group:g{i}#member@user:u{i}"))
           for i in range(4)]
    # membership chain g0 <- g1 <- g2 (g2's members reach g0)
    ops += [WriteOp("touch", rel("group:g0#member@group:g1#member")),
            WriteOp("touch", rel("group:g1#member@group:g2#member")),
            WriteOp("touch", rel("namespace:ns0#viewer@group:g0#member"))]
    e.write_relationships(ops)
    cg = e.compiled()
    assert any(b.closured for b in cg.blocks), \
        "test precondition: the group self-block must be closured"
    assert e.check_bulk([CheckItem("namespace", "ns0", "view",
                                   "user", "u2")])[0]  # via g2->g1->g0

    compiles0 = metrics.counter("engine_graph_compiles_total").value
    # delete the middle chain edge: the DERIVED g2->g0 reachability must
    # die with it (a naive single-cell clear would leave it alive)
    e.write_relationships([WriteOp(
        "delete", rel("group:g0#member@group:g1#member"))])
    assert not e.check_bulk([CheckItem("namespace", "ns0", "view",
                                       "user", "u2")])[0]
    assert not e.check_bulk([CheckItem("namespace", "ns0", "view",
                                       "user", "u1")])[0]
    assert e.check_bulk([CheckItem("namespace", "ns0", "view",
                                   "user", "u0")])[0]
    assert metrics.counter("engine_graph_compiles_total").value \
        == compiles0, "closured delete must re-close, not recompile"

    # expiration-attach onto a closured pair: counted fallback recompile
    fb0 = fallback_value("closured-expiry")
    e.write_relationships([WriteOp("touch", Relationship(
        "group", "g1", "member", "group", "g2",
        subject_relation="member", expiration=time.time() + 500))])
    assert e.check_bulk([CheckItem("namespace", "ns0", "view",
                                   "user", "u1") ])[0] is False
    assert fallback_value("closured-expiry") == fb0 + 1
    assert metrics.counter("engine_graph_compiles_total").value \
        == compiles0 + 1

    # a NEW dependency direction (plain add into closured-block
    # territory) is the stratification-inversion fallback — counted
    # under its own reason
    si0 = fallback_value("stratification-inversion")
    e.write_relationships([WriteOp(
        "touch", rel("group:g3#member@group:g0#member"))])
    assert e.check_bulk([CheckItem("group", "g3", "member",
                                   "user", "u0")])[0]
    assert fallback_value("stratification-inversion") >= si0


def test_first_dual_write_after_a_load_rides_the_overlay():
    """The first dual-write after a load writes types and relations no
    loaded tuple used (lock, workflow, activity, namespace#creator). The
    compiled id tables and levels cover whatever the schema admits, so
    those writes are overlay appends: no declined update, no recompile
    (at 10M relationships a recompile answers 401 for ~12 s meanwhile)."""
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "deploy", "bootstrap.yaml")) as f:
        e = Engine(f.read())
    e.write_relationships([WriteOp("touch", rel(r)) for r in (
        "pod:ns/p1#viewer@user:u1", "pod:ns/p1#namespace@namespace:ns",
        "namespace:ns#viewer@group:g1#member", "group:g1#member@user:u2")])
    assert e.check_bulk([CheckItem("pod", "ns/p1", "view", "user", "u2")])[0]

    def declined() -> float:
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in metrics.render().splitlines()
                   if line.startswith(
                       "engine_graph_incremental_fallback_total"))

    compiles0 = metrics.counter("engine_graph_compiles_total").value
    declined0 = declined()
    lock = rel("lock:l1#workflow@workflow:w1")
    e.write_relationships([WriteOp("create", lock)])
    assert e.lookup_resources("namespace", "view", "user", "u2") == ["ns"]
    e.write_relationships([
        WriteOp("touch", rel("namespace:new#creator@user:u2")),
        WriteOp("touch", Relationship(
            "workflow", "w1", "idempotency_key", "activity", "a1",
            expiration=time.time() + 500))])
    e.write_relationships([WriteOp("delete", lock)])
    assert set(e.lookup_resources("namespace", "view", "user", "u2")) \
        == {"ns", "new"}
    assert e.check_bulk([
        CheckItem("namespace", "new", "admin", "user", "u2"),
        CheckItem("namespace", "new", "view", "user", "u1"),
        CheckItem("workflow", "w1", "idempotency_key", "activity", "a1"),
        CheckItem("lock", "l1", "workflow", "workflow", "w1"),
    ]) == [True, False, True, False]
    assert declined() == declined0
    assert metrics.counter("engine_graph_compiles_total").value == compiles0


def test_overlay_overflow_counted_fallback_without_compactor():
    """Without a compactor, overflowing the fixed-capacity overlay is a
    COUNTED fallback to one full recompile (which empties the overlay) —
    correctness never depends on capacity."""
    e = build(delta_capacity=64, n_users=12, n_ns=12)
    fb0 = fallback_value("overflow")
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    for i in range(100):  # > capacity DISTINCT pairs (12x12 pair space)
        e.write_relationships([WriteOp("touch", Relationship(
            "namespace", f"ns{i % 12}", "viewer", "user",
            f"u{(i * 5 + i // 12) % 12}"))])
    assert_oracle_parity(e, n_users=12, n_ns=12)
    assert fallback_value("overflow") > fb0
    assert metrics.counter("engine_graph_compiles_total").value \
        > compiles0
    assert e.compiled().revision == e.store.revision


def test_overlay_full_sheds_bounded_retry_after_nothing_applied():
    """With compaction enabled, overlay-full is admission back-pressure:
    the write sheds BEFORE any store mutation with a bounded
    Retry-After, and a later fold restores write headroom."""
    e = build(delta_capacity=64, n_users=12, n_ns=12)
    c = e.enable_compaction(1.0)
    real_compact, c.compact = c.compact, lambda: False  # freeze the fold
    shed = None
    rev_before = None
    for i in range(200):
        try:
            e.write_relationships([WriteOp("touch", Relationship(
                "namespace", f"ns{i % 12}", "viewer", "user",
                f"u{(i * 5 + i // 12) % 12}"))])
        except OverlayBackpressure as ex:
            rev_before = e.store.revision
            shed = ex
            break
    assert shed is not None, "overlay never filled"
    assert MIN_RETRY_AFTER <= shed.retry_after <= MAX_RETRY_AFTER
    assert shed.capacity == 64 and shed.occupancy <= 64
    # a shed write left no trace: revision unchanged, retrying the same
    # write sheds again identically
    with pytest.raises(OverlayBackpressure):
        e.write_relationships([WriteOp("touch", Relationship(
            "namespace", "ns0", "viewer", "user", "u11"))])
    assert e.store.revision == rev_before
    assert metrics.counter("engine_overlay_backpressure_total").value > 0
    assert_oracle_parity(e, n_users=12, n_ns=12)  # reads keep serving
    # one fold restores headroom
    c.compact = real_compact
    assert c.compact() is True
    e.write_relationships([WriteOp("touch", Relationship(
        "namespace", "ns0", "viewer", "user", "u11"))])
    assert e.store.revision == rev_before + 1
    e.close_compaction()
    assert_oracle_parity(e, n_users=12, n_ns=12)


def test_compaction_swap_under_concurrent_dispatch():
    """Folds swapping the compiled base while reader threads dispatch
    continuously: no errors, every read sees a consistent graph, parity
    at the end, and the swap preserves the revision (decision-cache
    keys stay exactly valid)."""
    e = build(delta_capacity=512)
    e.enable_decision_cache()
    c = e.enable_compaction(1.0)  # manual folds only
    stop = threading.Event()
    errors: list = []

    def reader(k: int):
        i = 0
        while not stop.is_set():
            try:
                got = e.check_bulk([CheckItem(
                    "namespace", f"ns{(i + k) % 6}", "view",
                    "user", f"u{i % 6}")])
                assert isinstance(got[0], bool)
                e.lookup_resources_mask("namespace", "view", "user",
                                        f"u{(i + k) % 6}")
                i += 1
            except Exception as ex:  # noqa: BLE001 - the assertion
                errors.append(ex)
                return

    threads = [threading.Thread(target=reader, args=(k,))
               for k in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(30):
            e.write_relationships([WriteOp("touch", Relationship(
                "namespace", f"ns{i % 6}", "viewer", "user",
                f"u{(i * 5) % 6}"))])
            if i % 5 == 4:
                rev = e.store.revision
                assert c.compact() is True
                assert e.compiled().revision == rev, \
                    "the swap must preserve the revision"
                assert e.compiled().n_delta == 0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        e.close_compaction()
    assert not errors, errors[:3]
    assert metrics.counter("engine_compactions_total").value >= 6
    e.disable_decision_cache()
    assert_oracle_parity(e)


def test_mirror_shed_before_publish_and_apply_never_sheds():
    """Replication safety: the leader's overlay back-pressure runs
    BEFORE the frame is published (a post-publish shed would fork the
    lineages), and a follower applying a replicated frame NEVER sheds —
    overflow there falls back to a counted recompile instead."""
    from spicedb_kubeapi_proxy_tpu.engine.remote import _rel_to_dict
    from spicedb_kubeapi_proxy_tpu.parallel.multihost import (
        MirroredEngine,
        apply_mirror_frame,
    )

    def fill(e: Engine) -> None:
        for i in range(200):
            try:
                e.write_relationships([WriteOp("touch", Relationship(
                    "namespace", f"ns{i % 12}", "viewer", "user",
                    f"u{(i * 5 + i // 12) % 12}"))])
            except OverlayBackpressure:
                return
        raise AssertionError("overlay never filled")

    leader = build(delta_capacity=64, n_users=12, n_ns=12)
    lc = leader.enable_compaction(1.0)
    lc.compact = lambda: False  # freeze: stays full
    fill(leader)
    m = MirroredEngine(leader, mirror_queries=False)
    published = []
    m._publish = lambda *a, **kw: published.append(a) or None
    with pytest.raises(OverlayBackpressure):
        m.write_relationships([WriteOp("touch", Relationship(
            "namespace", "ns0", "viewer", "user", "u11"))])
    assert not published, "a shed write must never reach followers"
    leader.close_compaction()

    follower = build(delta_capacity=64, n_users=12, n_ns=12)
    fc = follower.enable_compaction(1.0)
    fc.compact = lambda: False
    fill(follower)
    rev = follower.store.revision
    frame = {"method": "write_relationships", "ops": [
        {"op": "touch", "rel": _rel_to_dict(Relationship(
            "namespace", "ns0", "viewer", "user", "u11"))}]}
    apply_mirror_frame(follower, frame)  # must NOT raise
    assert follower.store.revision == rev + 1
    assert follower.check_bulk([CheckItem("namespace", "ns0", "view",
                                          "user", "u11")])[0]
    follower.close_compaction()


def test_decision_cache_retire_below():
    dc = DecisionCache(max_entries=128)
    now = time.time()
    it = CheckItem("ns", "n0", "view", "user", "u0")
    for rev in (3, 4, 5):
        dc.put(check_key(rev, it), True, now + 60, 0, now)
    assert dc.retire_below(5) == 2
    assert dc.get(check_key(5, it), now) is True
    assert dc.stats()["entries"] == 1
    assert dc.retire_below(5) == 0  # idempotent


def test_validate_overlay_config_bounds():
    validate_overlay_config(64, 0.0)
    validate_overlay_config(4096, 1.0)
    with pytest.raises(ValueError):
        validate_overlay_config(63, 0.5)
    with pytest.raises(ValueError):
        validate_overlay_config(1024, 1.5)
    with pytest.raises(ValueError):
        validate_overlay_config(1024, -0.1)

"""Online shard rebalancing: the live tuple mover (ISSUE 14).

Covers the acceptance surface:

- ring-diff planning (moving slice set; a pure version bump moves
  nothing; every key whose owner changed falls in exactly one slice);
- the versioned RevisionVector satellite (encode/parse carry the
  shard-map version; cross-version tokens are rejected, translated
  only through a recorded transition — never misindexed);
- end-to-end live moves, in-process and over loopback TCP engine
  groups: zero acked writes lost, never fail-open, watch streams gap-
  and duplicate-free across cutover, goodput on non-moving slices
  held during the move;
- the dual-write window mirroring through the split journal (entries
  tagged with both versions; a mid-window planner crash replays to
  completion);
- the crash matrix: no slice cut -> clean abort (copies dropped,
  routing never left V); >= 1 slice cut -> resume to completion;
  committed-but-uncleared -> finish at boot (chaos-invariant checked);
- mover traffic admission-classed `rebalance` and shed-aware;
- /readyz's `rebalance:` line and --rebalance-to options validation.
"""

import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from spicedb_kubeapi_proxy_tpu.admission import (  # noqa: E402
    REBALANCE,
    AdmissionRejected,
    classify_op,
)
from spicedb_kubeapi_proxy_tpu.chaos.invariants import (  # noqa: E402
    check_rebalance_converged,
)
from spicedb_kubeapi_proxy_tpu.engine import Engine  # noqa: E402
from spicedb_kubeapi_proxy_tpu.engine.engine import CheckItem  # noqa: E402
from spicedb_kubeapi_proxy_tpu.engine.store import (  # noqa: E402
    RelationshipFilter,
    WriteOp,
)
from spicedb_kubeapi_proxy_tpu.models.tuples import (  # noqa: E402
    Relationship,
)
from spicedb_kubeapi_proxy_tpu.scaleout import (  # noqa: E402
    MapTransition,
    RebalanceCoordinator,
    RevisionVector,
    ShardedEngine,
    ShardMap,
    ShardMapError,
    SplitJournal,
    hash_key,
    plan_moves,
)
from spicedb_kubeapi_proxy_tpu.scaleout.rebalance import (  # noqa: E402
    CATCHUP,
    COPYING,
    CUT,
    DUAL,
    abort_transition,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics  # noqa: E402

SCHEMA_YAML = """\
schema: |-
  use expiration

  definition user {}

  definition group {
    relation member: user
  }

  definition namespace {
    relation creator: user
    relation viewer: user | group#member
    permission admin = creator
    permission view = viewer + creator
  }

  definition pod {
    relation namespace: namespace
    relation creator: user
    relation viewer: user
    permission edit = creator
    permission view = viewer + creator + namespace->view
  }
relationships: ""
"""


def _engine() -> Engine:
    return Engine(bootstrap=SCHEMA_YAML)


def _map(n: int, version: int = 1, vnodes: int = 64) -> ShardMap:
    return ShardMap(version=version,
                    groups=tuple((("127.0.0.1", 0),) for _ in range(n)),
                    virtual_nodes=vnodes)


def rel(rt, rid, rl, st, sid, srl=None) -> Relationship:
    return Relationship(rt, rid, rl, st, sid, srl)


def _seed_writes(n_ns: int, users: int = 4) -> list:
    out = []
    for i in range(n_ns):
        out.append(WriteOp("create", rel(
            "namespace", f"ns{i}", "viewer", "user", f"u{i % users}")))
        out.append(WriteOp("create", rel(
            "pod", f"ns{i}/p0", "namespace", "namespace", f"ns{i}")))
        out.append(WriteOp("create", rel(
            "pod", f"ns{i}/p0", "viewer", "user", f"u{i % users}")))
    return out


def _moving_split(t: MapTransition, n_ns: int):
    """(moving, staying) namespace name lists under transition ``t``."""
    moving, staying = [], []
    for i in range(n_ns):
        (moving if t.slice_for_key(f"ns{i}", "pod") is not None
         else staying).append(f"ns{i}")
    return moving, staying


# -- planning ----------------------------------------------------------------


def test_plan_moves_version_bump_moves_nothing():
    assert plan_moves(_map(2, 1), _map(2, 2)) == []


def test_plan_moves_covers_exactly_the_changed_keys():
    old, new = _map(2, 1, vnodes=64), _map(2, 2, vnodes=96)
    moves = plan_moves(old, new)
    assert moves, "a vnode change must move slices"
    t = MapTransition(old, new, moves)
    for i in range(400):
        ns = f"ns{i}"
        sl = t.slice_for_key(ns, "pod")
        src = old.shard_for(ns, "pod")
        dst = new.shard_for(ns, "pod")
        if src == dst:
            assert sl is None, (ns, "unchanged key inside a slice")
        else:
            assert sl is not None, (ns, "changed key outside all slices")
            assert (sl.src, sl.dst) == (src, dst)
    # grow: adding a group produces slices INTO the new group only
    grown = _map(3, 2)
    for sl in plan_moves(_map(2, 1), grown):
        assert sl.dst == 2 and sl.src in (0, 1)


# -- revision-vector map-version satellite -----------------------------------


def test_revision_vector_encode_parse_carry_map_version():
    v = RevisionVector((3, 5))
    assert v.encode() == "v3.5"
    tagged = v.encode(map_version=2)
    assert tagged == "v3.5@m2"
    assert RevisionVector.parse(tagged) == (3, 5)
    assert RevisionVector.parse(tagged, map_version=2) == (3, 5)
    assert RevisionVector.parse_versioned(tagged) == ((3, 5), 2)
    assert RevisionVector.parse_versioned("v3.5") == ((3, 5), None)
    # a vector minted under ANOTHER map version is rejected, not bound
    # to whatever groups now sit at those indices
    with pytest.raises(ShardMapError, match="minted under"):
        RevisionVector.parse(tagged, map_version=3)
    with pytest.raises(ShardMapError):
        RevisionVector.parse("v3.5@mX")
    assert RevisionVector((1, 2)).extend(4) == (1, 2, 0, 0)


def test_planner_rejects_wrong_size_or_unknown_version_tokens():
    engines = [_engine(), _engine(), _engine()]
    p = ShardedEngine(_map(3), engines)
    # a 2-component vector against a 3-group planner used to misindex;
    # now it is rejected (no recorded transition explains the growth)
    with pytest.raises(ShardMapError):
        p.watch_since(RevisionVector((1, 2)))
    with pytest.raises(ShardMapError, match="no transition"):
        p.watch_since("v1.2.3@m99")
    assert p.watch_since("v0.0.0@m1") == []  # current version: fine
    p.close()


# -- live move, in process ---------------------------------------------------


def test_inproc_rebalance_end_to_end(tmp_path):
    n_ns = 24
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    journal = SplitJournal(str(tmp_path / "sj.sqlite"))
    p = ShardedEngine(old, engines, journal=journal)
    p.write_relationships(_seed_writes(n_ns))
    users = [f"u{i}" for i in range(4)]
    before = {u: sorted(p.lookup_resources("pod", "view", "user", u))
              for u in users}

    coord = p.begin_rebalance(new)
    assert coord.wait(90), "mover never finished"
    assert coord.error is None, coord.error
    assert p.map.version == 2

    # zero acked writes lost; lookups byte-identical
    after = {u: sorted(p.lookup_resources("pod", "view", "user", u))
             for u in users}
    assert before == after
    for i in range(n_ns):
        assert p.check(CheckItem("pod", f"ns{i}/p0", "view", "user",
                                 f"u{i % 4}"))
        # never fail-open for a never-granted subject
        assert not p.check(CheckItem("pod", f"ns{i}/p0", "view",
                                     "user", "intruder"))
    # GC: each namespaced tuple lives on exactly its NEW owner
    for i in range(n_ns):
        f = RelationshipFilter(resource_type="pod",
                               resource_id=f"ns{i}/p0")
        holders = [gi for gi, e in enumerate(engines)
                   if e.store.exists(f)]
        assert holders == [new.shard_for(f"ns{i}", "pod")], (i, holders)
    # the durable completion marker (phase "done") persists so a
    # stale-flag restart cannot re-run the move against the GC'd
    # source; the converged invariant treats it as completed
    assert journal.load_transition()["phase"] == "done"
    assert journal.pending_count() == 0
    assert check_rebalance_converged(journal.load_transition()) == []
    p.close()


def test_rebalance_grow_one_to_two_groups_translates_tokens():
    old = _map(1, 1)
    new = _map(2, 2)
    engines = [_engine()]
    extra = _engine()
    p = ShardedEngine(old, engines)
    n_ns = 16
    p.write_relationships(_seed_writes(n_ns))
    # a V-minted resumption token (1 component, tagged)
    token = p.revision_vector().encode(map_version=1)

    coord = p.begin_rebalance(new, new_clients={1: extra})
    assert coord.wait(90) and coord.error is None, coord.error
    assert p.map.version == 2 and len(p.groups) == 2

    # the new group holds its slices AND the replicated globals
    moved = [f"ns{i}" for i in range(n_ns)
             if new.shard_for(f"ns{i}", "pod") == 1]
    assert moved, "fixture must move something to the new group"
    for ns in moved:
        assert extra.store.exists(RelationshipFilter(
            resource_type="pod", resource_id=f"{ns}/p0"))
        assert extra.store.exists(RelationshipFilter(
            resource_type="namespace", resource_id=ns))
    # the 1-component V token translates (new component from zero) and
    # replays NO mover echoes: every tuple it replays was already
    # acked before the token was minted -> zero events expected
    replay = p.watch_since(token)
    assert replay == [], [
        (e.relationship.resource_id, e.operation) for e in replay]
    # lookups still exact across the grown placement
    for u in (f"u{i}" for i in range(4)):
        got = sorted(p.lookup_resources("pod", "view", "user", u))
        want = sorted(f"ns{i}/p0" for i in range(n_ns)
                      if f"u{i % 4}" == u)
        assert got == want
    p.close()


def test_watch_stream_gap_and_duplicate_free_across_cutover():
    """The tentpole's watch-continuity core: a stream opened before the
    move sees every acked write exactly once — none of the mover's
    copy/catch-up/dual/GC echoes, no gap at the flip."""
    n_ns = 16
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    p = ShardedEngine(old, engines)
    p.write_relationships(_seed_writes(n_ns))
    t = MapTransition(old, new, plan_moves(old, new))
    moving, staying = _moving_split(t, n_ns)
    assert moving and staying

    stream = p.watch_push_stream(p.revision_vector())
    acked = []
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            ns = (moving + staying)[i % n_ns]
            name = f"w{i}"
            p.write_relationships([WriteOp("touch", rel(
                "pod", f"{ns}/p0", "viewer", "user", name))])
            acked.append(name)
            i += 1
            time.sleep(0.005)

    wt = threading.Thread(target=writer, daemon=True)
    wt.start()
    try:
        coord = p.begin_rebalance(new, pace_seconds=0.002,
                                  batch_rows=16)
        assert coord.wait(120) and coord.error is None, coord.error
    finally:
        stop.set()
        wt.join(10)
    # drain the stream until every acked write's event arrived
    want = set(acked)
    seen = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for e in stream.next_batch():
            if e.relationship.subject_id.startswith("w"):
                seen.append(e.relationship.subject_id)
        if want <= set(seen):
            break
    stream.close()
    missing = want - set(seen)
    assert not missing, f"gap across cutover: {sorted(missing)[:5]}"
    dups = {n for n in seen if seen.count(n) > 1}
    assert not dups, f"duplicates across cutover: {sorted(dups)[:5]}"
    p.close()


# -- dual-write window -------------------------------------------------------


def test_dual_write_window_mirrors_and_tags_journal(tmp_path):
    n_ns = 12
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    journal = SplitJournal(str(tmp_path / "sj.sqlite"))
    p = ShardedEngine(old, engines, journal=journal)
    p.write_relationships(_seed_writes(n_ns))
    t = MapTransition(old, new, plan_moves(old, new))
    moving, _ = _moving_split(t, n_ns)
    ns = moving[0]
    sl = t.slice_for_key(ns, "pod")
    # open the window by hand: copy, then DUAL (the coordinator's own
    # sequencing is covered by the end-to-end tests)
    p._install_transition(t)
    coord = RebalanceCoordinator(p, t)
    copy_rev, rows = coord._slice_read(sl.src, sl.ranges)
    coord._slice_load(sl.dst, rows)
    t.set_state(sl, "catchup", copy_rev=copy_rev, replayed=copy_rev)
    while coord._catch_up_once(sl) > 0:
        pass
    t.set_state(sl, DUAL)

    before = metrics.counter(
        "scaleout_rebalance_dual_writes_total").value
    p.write_relationships([WriteOp("touch", rel(
        "pod", f"{ns}/p0", "viewer", "user", "mirrored"))])
    assert metrics.counter(
        "scaleout_rebalance_dual_writes_total").value > before
    # the write landed on BOTH owners
    f = RelationshipFilter(resource_type="pod", resource_id=f"{ns}/p0",
                           subject_id="mirrored")
    assert engines[sl.src].store.exists(f)
    assert engines[sl.dst].store.exists(f)
    assert journal.pending_count() == 0
    # reads still route at V (src)
    s_before = metrics.counter("scaleout_ops_total", group=str(sl.src),
                               op="check_bulk", mode="single").value
    assert p.check(CheckItem("pod", f"{ns}/p0", "view", "user",
                             "mirrored"))
    assert metrics.counter("scaleout_ops_total", group=str(sl.src),
                           op="check_bulk", mode="single"
                           ).value == s_before + 1
    p.close(close_journal=False)

    # a mid-window planner crash: the mirrored split stays replayable
    # (tagged with BOTH versions -> NOT re-routed by recovery)
    engines2 = [_engine(), _engine()]
    p2 = ShardedEngine(old, engines2, journal=journal, recover=False)
    p2._install_transition(MapTransition.from_doc(t.to_doc(), old))
    sl2 = p2._active_transition.slices[sl.sid]
    p2._active_transition.set_state(sl2, DUAL)

    class _Dying:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def write_relationships(self, ops, preconditions=()):
            self._inner.write_relationships(ops, preconditions)
            raise ConnectionResetError("crash after first owner")

    p2.groups[max(sl.src, sl.dst)] = _Dying(
        p2.groups[max(sl.src, sl.dst)])
    with pytest.raises(ConnectionResetError):
        p2.write_relationships([WriteOp("touch", rel(
            "pod", f"{ns}/p0", "viewer", "user", "window-crash"))])
    ent = journal.pending()[0]
    assert ent["map_version"] == 1 and ent["map_version_to"] == 2
    p2.close(close_journal=False)
    # "restart" mid-window with NO slice cut: the pending dual-write
    # split replays FIRST (the entry names both versions, so the
    # recorded owners route as-is), then the transition aborts cleanly
    # — source keeps every acked write, destination copies are dropped
    p3 = ShardedEngine(old, engines2, journal=journal)
    assert journal.pending_count() == 0
    assert journal.load_transition() is None
    wc = RelationshipFilter(resource_type="pod",
                            resource_id=f"{ns}/p0",
                            subject_id="window-crash")
    assert engines2[sl.src].store.exists(wc)
    assert not engines2[sl.dst].store.exists(wc), \
        "aborted transition left a stale destination copy"
    # and the planner (routing at V) serves it
    assert p3.exists(wc)
    p3.close()


# -- crash matrix ------------------------------------------------------------


def _persisted_transition(tmp_path, n_ns=12, cut_first=False):
    """Build engines + journal holding a mid-flight transition record;
    returns (old, new, engines, journal, transition)."""
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    journal = SplitJournal(str(tmp_path / "sj.sqlite"))
    p = ShardedEngine(old, engines, journal=journal)
    p.write_relationships(_seed_writes(n_ns))
    t = MapTransition(old, new, plan_moves(old, new))
    p._install_transition(t)
    coord = RebalanceCoordinator(p, t)
    for i, sl in enumerate(t.slices):
        copy_rev, rows = coord._slice_read(sl.src, sl.ranges)
        coord._slice_load(sl.dst, rows)
        t.set_state(sl, "catchup", copy_rev=copy_rev,
                    replayed=copy_rev)
        while coord._catch_up_once(sl) > 0:
            pass
        if cut_first and i == 0:
            src_cut = coord._src_revision(sl.src)
            dst_cut = coord._src_revision(sl.dst)
            t.set_state(sl, CUT, src_cut=src_cut, dst_cut=dst_cut)
    coord._persist()
    p.close(close_journal=False)  # the "SIGKILL": record stays
    return old, new, engines, journal, t


def test_crash_before_any_cut_aborts_cleanly(tmp_path):
    old, new, engines, journal, t = _persisted_transition(tmp_path)
    assert journal.load_transition() is not None
    # invariant checker: a still-persisted record is a violation...
    assert check_rebalance_converged(journal.load_transition())
    p2 = ShardedEngine(old, engines, journal=journal)
    # ...and recovery resolves it: clean abort — record cleared,
    # routing still at V, the destination copies dropped
    assert journal.load_transition() is None
    assert check_rebalance_converged(journal.load_transition()) == []
    assert p2.map.version == 1
    for i in range(12):
        ns = f"ns{i}"
        f = RelationshipFilter(resource_type="pod",
                               resource_id=f"{ns}/p0")
        holders = [gi for gi, e in enumerate(engines)
                   if e.store.exists(f)]
        assert holders == [old.shard_for(ns, "pod")], (ns, holders)
        assert p2.check(CheckItem("pod", f"{ns}/p0", "view", "user",
                                  f"u{i % 4}"))
    p2.close()


def test_crash_after_first_cut_resumes_to_completion(tmp_path):
    old, new, engines, journal, t = _persisted_transition(
        tmp_path, cut_first=True)
    p2 = ShardedEngine(old, engines, journal=journal)
    # past the point of no return: a coordinator auto-resumed at boot
    assert p2._coordinator is not None
    assert p2._coordinator.wait(90)
    assert p2._coordinator.error is None, p2._coordinator.error
    assert p2.map.version == 2
    assert journal.load_transition()["phase"] == "done"
    assert check_rebalance_converged(journal.load_transition()) == []
    for i in range(12):
        ns = f"ns{i}"
        assert p2.check(CheckItem("pod", f"{ns}/p0", "view", "user",
                                  f"u{i % 4}"))
        f = RelationshipFilter(resource_type="pod",
                               resource_id=f"{ns}/p0")
        holders = [gi for gi, e in enumerate(engines)
                   if e.store.exists(f)]
        assert holders == [new.shard_for(ns, "pod")], (ns, holders)
    p2.close()


def test_committed_but_uncleared_record_finishes_at_boot(tmp_path):
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    journal = SplitJournal(str(tmp_path / "sj.sqlite"))
    p = ShardedEngine(old, engines, journal=journal)
    p.write_relationships(_seed_writes(8))
    coord = p.begin_rebalance(new)
    assert coord.wait(90) and coord.error is None
    # re-persist the committed record as if the crash hit before clear
    t = p._archived_transitions[0]
    journal.save_transition(t.to_doc("committed"))
    p.close(close_journal=False)
    p2 = ShardedEngine(old, engines, journal=journal)
    assert p2.map.version == 2
    # the recovered GC runs OFF the boot path; the record flips to the
    # "done" marker when it lands
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        doc = journal.load_transition()
        if doc is not None and doc.get("phase") == "done":
            break
        time.sleep(0.05)
    assert journal.load_transition()["phase"] == "done"
    p2.close()


def test_abort_requires_no_cut_slice():
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    t = MapTransition(old, new, plan_moves(old, new))
    t.set_state(t.slices[0], CUT, src_cut=1, dst_cut=1)
    p, _ = ShardedEngine(old, [_engine(), _engine()]), None
    from spicedb_kubeapi_proxy_tpu.scaleout import RebalanceError

    with pytest.raises(RebalanceError, match="point of no return"):
        abort_transition(p, t)
    p.close()


# -- admission classing (mover traffic is sheddable) -------------------------


def test_slice_ops_are_rebalance_classed_and_mover_backs_off():
    for op in ("slice_read", "slice_load", "slice_apply",
               "slice_drop"):
        assert classify_op(op) is REBALANCE
    # lowest shed priority: migration yields to every serving class
    from spicedb_kubeapi_proxy_tpu.admission import CLASSES

    assert all(REBALANCE.priority < c.priority
               for n, c in CLASSES.items() if n != "rebalance")
    # a shedding host backs the mover off by Retry-After, then it
    # proceeds — a shed never fails the transition
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    t = MapTransition(old, new, plan_moves(old, new))
    p = ShardedEngine(old, [_engine(), _engine()])
    coord = RebalanceCoordinator(p, t)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise AdmissionRejected("rebalance", "host full",
                                    retry_after=0.01,
                                    dependency="engine-admission")
        return "ok"

    before = metrics.counter(
        "scaleout_rebalance_shed_backoff_total").value
    assert coord._call_shed_aware(flaky) == "ok"
    assert metrics.counter(
        "scaleout_rebalance_shed_backoff_total").value == before + 2
    p.close()


# -- /readyz + options -------------------------------------------------------


def test_sharding_status_and_readyz_report_rebalance(tmp_path):
    import asyncio

    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.engine.remote import EngineServer
    from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    RULES = open(os.path.join(os.path.dirname(__file__), "..",
                              "deploy", "rules.yaml")).read()

    async def go():
        srvs = [EngineServer(_engine()), EngineServer(_engine())]
        ports = [await s.start() for s in srvs]
        smap = ('{"version": 1, "groups": [["127.0.0.1:%d"], '
                '["127.0.0.1:%d"]]}' % (ports[0], ports[1]))
        cfg = Options(
            shard_map=smap,
            shard_journal_path=str(tmp_path / "sj.sqlite"),
            engine_insecure=True,
            rule_content=RULES,
            upstream=FakeKube(),
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
        ).complete()
        await cfg.workflow.resume_pending()
        # install a mid-flight transition white-box (deterministic:
        # no racing mover) and read /readyz
        old = cfg.engine.map
        new = ShardMap(version=2, groups=old.groups, virtual_nodes=96)
        t = MapTransition(old, new, plan_moves(old, new))
        cfg.engine._install_transition(t)
        st = cfg.engine.sharding_status()
        assert st["rebalance"] == {
            "to_version": 2, "moving": len(t.slices),
            "copied": 0, "cut": 0, "lag": 0}
        alice = InMemoryClient(cfg.server.handle, user="alice")
        resp = await alice.get("/readyz")
        assert resp.status == 200, resp.body
        body = resp.body.decode()
        assert "[+]rebalance: to_version=2 moving=" in body
        assert "cut=0 lag=0" in body
        cfg.engine._active_transition = None
        cfg.engine.journal.clear_transition()
        await cfg.workflow.shutdown()
        cfg.engine.close()
        for s in srvs:
            await s.stop()

    asyncio.run(go())


def test_options_validation_rebalance_to():
    from spicedb_kubeapi_proxy_tpu.proxy.options import (
        Options,
        OptionsError,
    )

    good = '{"version": 1, "groups": [["127.0.0.1:1"], ["127.0.0.1:2"]]}'
    with pytest.raises(OptionsError, match="requires --shard-map"):
        Options(rebalance_to=good, rule_content="x",
                upstream=object()).validate()
    with pytest.raises(OptionsError, match="must exceed"):
        Options(shard_map=good, rebalance_to=good, rule_content="x",
                upstream=object()).validate()
    good3 = ('{"version": 1, "groups": [["127.0.0.1:1"], '
             '["127.0.0.1:2"], ["127.0.0.1:3"]]}')
    with pytest.raises(OptionsError, match="at most ONE group"):
        Options(shard_map=good3,
                rebalance_to='{"version": 2, '
                             '"groups": [["127.0.0.1:1"]]}',
                rule_content="x", upstream=object()).validate()
    with pytest.raises(OptionsError, match="LAST group"):
        Options(shard_map=good,
                rebalance_to='{"version": 2, '
                             '"groups": [["127.0.0.1:2"]]}',
                rule_content="x", upstream=object()).validate()
    # a valid transition map validates; so does a tail-group shrink
    Options(shard_map=good,
            rebalance_to='{"version": 2, "groups": [["127.0.0.1:1"], '
                         '["127.0.0.1:2"]], "virtual_nodes": 96}',
            rule_content="x", upstream=object()).validate()
    Options(shard_map=good,
            rebalance_to='{"version": 2, "groups": [["127.0.0.1:1"]]}',
            rule_content="x", upstream=object()).validate()


# -- the live-move acceptance run (loopback TCP groups) ----------------------


def test_live_move_acceptance_over_tcp(tmp_path, monkeypatch):
    """ISSUE 14 acceptance: under sustained load, a live move between
    two loopback engine groups loses zero acked writes, never answers
    fail-open, keeps an open watch stream gap- and duplicate-free
    across cutover, and NON-moving slices are served through every
    phase of every moving slice (copying, catch-up, dual-write, the
    frozen cutover) with no failed and no wrong answer."""
    import asyncio

    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )
    # a GROW move (3 -> 4 groups): the copy/catch-up import load lands
    # on the added group, which serves no pre-existing slice
    n_ns = 48
    old, new = _map(3, 1), _map(4, 2)
    loop = asyncio.new_event_loop()
    lt = threading.Thread(target=loop.run_forever, daemon=True)
    lt.start()

    def run(coro, timeout=60.0):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(
            timeout)

    servers, clients = [], []
    p = None
    try:
        for _ in range(4):
            srv = EngineServer(_engine())
            port = run(srv.start())
            servers.append(srv)
            clients.append(RemoteEngine("127.0.0.1", port))
        journal = SplitJournal(str(tmp_path / "sj.sqlite"))
        p = ShardedEngine(old, clients[:3], journal=journal)
        p.write_relationships(_seed_writes(n_ns))
        t = MapTransition(old, new, plan_moves(old, new))
        moving, staying = _moving_split(t, n_ns)
        assert moving and staying
        # warm the mover's power-of-two write/delete kernel shapes on
        # every host (in production they compile once, on the fleet's
        # first-ever move, and stay cached)
        for gi, c in enumerate(clients):
            for size in (16, 8, 4, 2, 1):
                warm = [rel("pod", f"{staying[0]}/warm{gi}", "viewer",
                            "user", f"warm{gi}-{size}-{k}")
                        for k in range(size)]
                c.write_relationships(
                    [WriteOp("touch", r) for r in warm])
                c.write_relationships(
                    [WriteOp("touch", r) for r in warm])
                c.write_relationships(
                    [WriteOp("delete", r) for r in warm])

        stream = p.watch_push_stream(p.revision_vector())
        acked: list = []
        acked_lock = threading.Lock()
        fail_open, failed, wrong = [], [], []
        served = {"n": 0}
        served_lock = threading.Lock()
        stop = threading.Event()

        probes = staying[:8]

        def served_n():
            with served_lock:
                return served["n"]

        def load_worker(wi):
            """Closed-loop checks on NON-moving slices, each held to
            the seed's verdict (ns<i> and its pod are viewed by
            u<i % 4> alone), + never-granted intruder probes."""
            j = wi
            while not stop.is_set():
                ns = probes[j % len(probes)]
                want = int(ns[2:]) % 4 == j % 4
                try:
                    got = p.check(CheckItem("pod", f"{ns}/p0", "view",
                                            "user", f"u{j % 4}"))
                    intruder = p.check(CheckItem(
                        "pod", f"{ns}/p0", "view", "user", "intruder"))
                except Exception as e:  # noqa: BLE001 - counted below
                    failed.append(repr(e))
                else:
                    if bool(got) != want:
                        wrong.append((ns, j % 4, got))
                    if intruder:
                        fail_open.append(ns)
                    with served_lock:
                        served["n"] += 2
                j += 4

        def write_worker():
            """Sustained writes to MOVING slices (unique subjects: the
            watch stream's dedupe oracle)."""
            i = 0
            while not stop.is_set():
                ns = moving[i % len(moving)]
                name = f"mv{i}"
                try:
                    p.write_relationships([WriteOp("touch", rel(
                        "pod", f"{ns}/p0", "viewer", "user", name))])
                except Exception:  # noqa: BLE001 - unacked: no claim
                    pass
                else:
                    with acked_lock:
                        acked.append((ns, name))
                i += 1
                time.sleep(0.1)

        # the mover holds a slice in each state it enters until the
        # non-moving probes have been answered HELD more times in that
        # state: "served during the phase" is then a count, whatever
        # the host's speed (a phase that blocked non-moving traffic
        # would run into the deadline and leave its count short). CUT
        # is entered with the moving slice's write gate still frozen.
        HELD = 8
        in_phase = {COPYING: 0, CATCHUP: 0, DUAL: 0, CUT: 0}
        entered = dict.fromkeys(in_phase, 0)
        set_state = MapTransition.set_state

        def held_set_state(self, sl, state, **fields):
            changed = state != sl.state
            set_state(self, sl, state, **fields)
            if not changed or state not in in_phase:
                return
            n0 = served_n()
            deadline = time.monotonic() + 60
            while served_n() < n0 + HELD and not stop.is_set() \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            entered[state] += 1
            in_phase[state] += served_n() - n0

        monkeypatch.setattr(MapTransition, "set_state", held_set_state)

        workers = [threading.Thread(target=load_worker, args=(wi,),
                                    daemon=True) for wi in range(4)]
        writer = threading.Thread(target=write_worker, daemon=True)
        for w in workers:
            w.start()
        writer.start()

        # traffic is flowing (jit shapes, caches) before the move starts
        deadline = time.monotonic() + 60
        while served_n() < 4 * HELD and time.monotonic() < deadline:
            time.sleep(0.01)
        assert served_n() >= 4 * HELD, (served_n(), failed[:3])

        # live move, paced so migration bandwidth is a bounded small
        # fraction of host capacity
        coord = p.begin_rebalance(new, new_clients={3: clients[3]},
                                  pace_seconds=0.25, batch_rows=8,
                                  poll_seconds=0.3)

        assert coord.wait(120), "mover never finished"
        assert coord.error is None, coord.error
        stop.set()
        writer.join(10)
        for w in workers:
            w.join(10)

        assert not fail_open, f"fail-open on {fail_open[:3]}"
        assert p.map.version == 2 and len(p.groups) == 4

        # zero acked writes lost (read back through the NEW placement)
        with acked_lock:
            acked_now = list(acked)
        for ns, name in acked_now:
            assert p.exists(RelationshipFilter(
                resource_type="pod", resource_id=f"{ns}/p0",
                relation="viewer", subject_id=name)), (ns, name)

        # watch stream: every acked moving-slice write exactly once
        want = {name for _, name in acked_now}
        seen: list = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            for e in stream.next_batch():
                sid = e.relationship.subject_id
                if sid.startswith("mv"):
                    seen.append(sid)
            if want <= set(seen):
                break
        stream.close()
        missing = want - set(seen)
        assert not missing, f"gap: {sorted(missing)[:5]}"
        dups = {n for n in seen if seen.count(n) > 1}
        assert not dups, f"duplicates: {sorted(dups)[:5]}"

        # non-moving slices: served in every phase of every moving
        # slice, nothing failed, nothing wrong
        n_slices = len(coord.t.slices)
        sys.stderr.write(
            f"\nlive move: {n_slices} slices, non-moving probes "
            f"served per phase {in_phase}\n")
        assert not failed, failed[:3]
        assert not wrong, wrong[:3]
        for state, n in in_phase.items():
            assert entered[state] == n_slices, (state, entered)
            assert n >= HELD * n_slices, (state, in_phase)
    finally:
        if p is not None:
            p.close()
        for srv in servers:
            try:
                run(srv.stop(), timeout=15.0)
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        loop.call_soon_threadsafe(loop.stop)
        lt.join(10)


def test_resume_replays_deletes_from_the_crash_window(tmp_path):
    """Review regression: resuming an interrupted slice move must
    replay from the PERSISTED watermark, not the fresh copy revision —
    a tuple copied to the destination and then deleted on the source
    during the crash window would otherwise survive on the new owner
    (a revoked grant answering allow after cutover: fail-open)."""
    old, new, engines, journal, t = _persisted_transition(
        tmp_path, cut_first=True)
    sl = next(s for s in t.slices if s.state != CUT)
    idx = next(i for i in range(12)
               if t.slice_for_key(f"ns{i}", "pod") is sl)
    ns = f"ns{idx}"
    # a grant whose ONLY path is the moved pod tuple ("vic" has no
    # namespace-level access): present on BOTH stores — as if the copy
    # carried it — then granted+revoked on the source strictly after
    # the persisted replay watermark (the "crash window")
    victim = rel("pod", f"{ns}/p0", "viewer", "user", "vic")
    engines[sl.dst].write_relationships([WriteOp("touch", victim)])
    engines[sl.src].write_relationships([WriteOp("touch", victim)])
    engines[sl.src].write_relationships([WriteOp("delete", victim)])
    vic_f = RelationshipFilter(resource_type="pod",
                               resource_id=f"{ns}/p0",
                               subject_id="vic")
    assert engines[sl.dst].store.exists(vic_f)

    p2 = ShardedEngine(old, engines, journal=journal)
    coord = p2._coordinator
    assert coord is not None and coord.wait(90)
    assert coord.error is None, coord.error
    assert p2.map.version == 2
    # the revocation reached the new owner: never a stale allow
    assert not engines[sl.dst].store.exists(vic_f)
    assert not p2.check(CheckItem("pod", f"{ns}/p0", "view", "user",
                                  "vic"))
    p2.close()


def test_stale_flags_restart_boots_the_completed_map(tmp_path):
    """Review regression: after a completed move, a restart whose CLI
    flags still name the OLD map must serve the committed new map from
    the durable "done" marker — re-running the move would route the
    moved slices to the GC'd (empty) source groups."""
    old, new = _map(2, 1), _map(2, 2, vnodes=96)
    engines = [_engine(), _engine()]
    journal = SplitJournal(str(tmp_path / "sj.sqlite"))
    p = ShardedEngine(old, engines, journal=journal)
    p.write_relationships(_seed_writes(12))
    coord = p.begin_rebalance(new)
    assert coord.wait(90) and coord.error is None, coord.error
    p.close(close_journal=False)

    # restart with the STALE map (the operator has not rolled the
    # flag): the done marker makes V+1 authoritative
    p2 = ShardedEngine(old, engines, journal=journal)
    assert p2.map.version == 2
    for i in range(12):
        assert p2.check(CheckItem("pod", f"ns{i}/p0", "view", "user",
                                  f"u{i % 4}"))
    assert journal.load_transition()["phase"] == "done"  # marker kept
    p2.close(close_journal=False)

    # the flag catches up: booting WITH the new map clears the marker
    p3 = ShardedEngine(new, engines, journal=journal)
    assert p3.map.version == 2
    assert journal.load_transition() is None
    assert p3.check(CheckItem("pod", "ns0/p0", "view", "user", "u0"))
    p3.close()

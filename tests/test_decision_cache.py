"""Decision cache + singleflight: zero repeat dispatches, exact
invalidation (writes, deletes, expiration boundaries), differential
agreement with the oracle, and the authz fast-path probe.

The acceptance gates (ISSUE 2): a repeated identical lookup at an
unchanged revision performs ZERO new device dispatches (read off
``engine_lookups_total`` / batch counters), N concurrent identical
misses dispatch exactly once, and a cache-enabled engine agrees with
``OracleEvaluator`` across writes, deletes, and expiration boundaries.
"""

import threading
import time

import numpy as np
import pytest

from fusing import warm
from spicedb_kubeapi_proxy_tpu.engine import (
    CheckItem,
    Engine,
    RelationshipFilter,
    WriteOp,
)
from spicedb_kubeapi_proxy_tpu.engine.decision_cache import (
    MISS,
    DecisionCache,
)
from spicedb_kubeapi_proxy_tpu.engine.store import Store
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import (
    Relationship,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

SCHEMA = parse_schema("""
use expiration
definition user {}
definition group {
  relation member: user
}
definition ns {
  relation viewer: user | group#member | user with expiration
  permission view = viewer
}
""")


def build(cache=True, rels=None):
    e = Engine(schema=SCHEMA)
    e.write_relationships([
        WriteOp("touch", parse_relationship(r)) for r in (rels or (
            "ns:n0#viewer@user:u0",
            "ns:n1#viewer@user:u0",
            "ns:n1#viewer@user:u1",
            "ns:n2#viewer@group:g0#member",
            "group:g0#member@user:u2",
        ))
    ])
    if cache:
        e.enable_decision_cache()
    return e


def lookups_total():
    return metrics.counter("engine_lookups_total").value


def checks_total():
    return metrics.counter("engine_checks_total").value


# ---------------------------------------------------------------------------
# Zero repeat dispatches + copy-on-read
# ---------------------------------------------------------------------------


def test_repeat_lookup_zero_dispatches():
    e = build()
    m1, it1 = e.lookup_resources_mask("ns", "view", "user", "u0")
    before = lookups_total()
    m2, it2 = e.lookup_resources_mask("ns", "view", "user", "u0")
    assert lookups_total() == before  # served host-side, no dispatch
    np.testing.assert_array_equal(m1, m2)
    assert it2 is it1
    # lookup_resources shares the SAME mask entry
    ids = e.lookup_resources("ns", "view", "user", "u0")
    assert lookups_total() == before
    assert set(ids) == {"n0", "n1"}


def test_repeat_lookup_zero_dispatches_with_batcher():
    e = build()
    warm(e, "ns")
    e.lookup_resources_mask("ns", "view", "user", "u1")
    before = lookups_total()
    batches = metrics.counter("engine_lookup_batches_total").value
    e.lookup_resources_mask("ns", "view", "user", "u1")
    assert lookups_total() == before
    assert metrics.counter("engine_lookup_batches_total").value == batches


def test_copy_on_read_protects_cached_mask():
    e = build()
    m1, _ = e.lookup_resources_mask("ns", "view", "user", "u0")
    assert m1.any()
    m1[:] = False  # caller mutates its copy
    m2, _ = e.lookup_resources_mask("ns", "view", "user", "u0")
    assert m2.any(), "cached array was mutated through a caller's copy"


def test_repeat_check_zero_dispatches_and_negative_caching():
    e = build()
    items = [CheckItem("ns", "n0", "view", "user", "u0"),
             CheckItem("ns", "n0", "view", "user", "u1")]
    assert e.check_bulk(items) == [True, False]
    before = checks_total()
    assert e.check_bulk(items) == [True, False]  # both polarities cached
    assert checks_total() == before


def test_check_miss_residue_dispatches_in_order():
    e = build()
    e.check_bulk([CheckItem("ns", "n0", "view", "user", "u0")])
    before = checks_total()
    # one hit + one miss: only the residue dispatches, order preserved
    got = e.check_bulk([CheckItem("ns", "n1", "view", "user", "u1"),
                        CheckItem("ns", "n0", "view", "user", "u0"),
                        CheckItem("ns", "n2", "view", "user", "u2")])
    assert got == [True, True, True]
    assert checks_total() - before == 2


def test_explicit_now_bypasses_cache():
    e = build()
    now = time.time()
    e.lookup_resources_mask("ns", "view", "user", "u0", now=now)
    before = lookups_total()
    e.lookup_resources_mask("ns", "view", "user", "u0", now=now)
    assert lookups_total() - before == 1  # pinned-clock queries never cache


def test_trivial_lookup_counts_and_caches():
    e = build()
    before = lookups_total()
    assert e.lookup_resources_mask("nosuch", "view", "user", "u0") == \
        (None, None)
    # the direct path counts trivial lookups like the batched path does
    assert lookups_total() - before == 1
    assert e.lookup_resources_mask("nosuch", "view", "user", "u0") == \
        (None, None)
    assert lookups_total() - before == 1  # repeat is a cache hit


# ---------------------------------------------------------------------------
# Invalidation: writes, deletes, expiration boundaries
# ---------------------------------------------------------------------------


def test_write_and_delete_invalidate():
    e = build()
    assert e.check_bulk([CheckItem("ns", "n9", "view", "user", "u9")]) == \
        [False]
    e.write_relationships(
        [WriteOp("touch", parse_relationship("ns:n9#viewer@user:u9"))])
    assert e.check_bulk([CheckItem("ns", "n9", "view", "user", "u9")]) == \
        [True]
    mask, interner = e.lookup_resources_mask("ns", "view", "user", "u9")
    assert mask[interner.lookup("n9")]
    e.delete_relationships(
        RelationshipFilter(resource_type="ns", resource_id="n9"))
    assert e.check_bulk([CheckItem("ns", "n9", "view", "user", "u9")]) == \
        [False]
    mask, _ = e.lookup_resources_mask("ns", "view", "user", "u9")
    assert not mask.any()


def test_expiration_boundary_kills_entries():
    e = build()
    e.check_bulk([CheckItem("ns", "n0", "view", "user", "u0")])  # warm jit
    now = time.time()
    e.write_relationships([WriteOp("touch", Relationship(
        "ns", "nexp", "viewer", "user", "uexp", expiration=now + 1.2))])
    item = CheckItem("ns", "nexp", "view", "user", "uexp")
    assert e.check_bulk([item]) == [True]
    before = checks_total()
    assert e.check_bulk([item]) == [True]
    assert checks_total() == before  # cached while the watermark holds
    time.sleep(max(0.0, now + 1.25 - time.time()))
    # the boundary passed with NO write: the entry must die at the
    # watermark and the fresh dispatch must see the expired tuple
    assert e.check_bulk([item]) == [False]
    mask, _ = e.lookup_resources_mask("ns", "view", "user", "uexp")
    assert not mask.any()


def test_differential_vs_oracle_across_mutations():
    """A cache-enabled engine must agree with OracleEvaluator after every
    mutation step — writes, deletes, and a tuple-expiration boundary."""
    e = build()
    e.check_bulk([CheckItem("ns", "n0", "view", "user", "u0")])  # warm jit
    base = time.time()
    exp_at = base + 2.5
    steps = [
        lambda: e.write_relationships(
            [WriteOp("touch", parse_relationship("ns:n3#viewer@user:u1"))]),
        lambda: e.write_relationships([WriteOp("touch", Relationship(
            "ns", "n4", "viewer", "user", "u0", expiration=exp_at))]),
        lambda: e.delete_relationships(
            RelationshipFilter(resource_type="ns", resource_id="n1")),
        lambda: e.write_relationships(
            [WriteOp("touch",
                     parse_relationship("group:g0#member@user:u1"))]),
        lambda: e.write_relationships(
            [WriteOp("delete",
                     parse_relationship("ns:n0#viewer@user:u0"))]),
        lambda: time.sleep(max(0.0, exp_at + 0.05 - time.time())),  # expiry
    ]
    users = [f"u{i}" for i in range(4)]
    nss = [f"n{i}" for i in range(5)]

    def compare_once():
        oracle = e.oracle()  # snapshot + clock at comparison time
        bad = []
        for u in users:
            got = set(e.lookup_resources("ns", "view", "user", u))
            want = oracle.lookup_resources("ns", "view", "user", u)
            if got != want:
                bad.append((u, got, want))
        items = [CheckItem("ns", n, "view", "user", u)
                 for n in nss for u in users]
        got = e.check_bulk(items)
        want = [oracle.check("ns", n, "view", "user", u)
                for n in nss for u in users]
        if got != want:
            bad.append(("checks", got, want))
        return bad

    def assert_agreement():
        # double-query: the second round is served from the cache and
        # must still agree (catches stale entries surviving a mutation)
        for _ in range(2):
            bad = compare_once()
            if bad:
                # the wall clock may cross an expiration boundary BETWEEN
                # oracle construction and the engine query — a real cache
                # bug reproduces against a fresh oracle, a clock race
                # does not
                bad = compare_once()
            assert not bad, bad

    assert_agreement()
    for step in steps:
        step()
        assert_agreement()


def test_cache_disabled_engine_agrees():
    plain, cached = build(cache=False), build()
    for u in ("u0", "u1", "u2", "u9"):
        a = set(plain.lookup_resources("ns", "view", "user", u))
        b = set(cached.lookup_resources("ns", "view", "user", u))
        assert a == b


# ---------------------------------------------------------------------------
# Singleflight
# ---------------------------------------------------------------------------


def test_singleflight_one_dispatch_for_concurrent_identical_lookups():
    e = build()
    e.lookup_resources_mask("ns", "view", "user", "uwarm")  # warm jit
    gate = threading.Event()
    orig = e._lookup_submit
    calls = []

    def gated(*a, **k):
        calls.append(a)
        gate.wait(5.0)
        return orig(*a, **k)

    e._lookup_submit = gated
    before = lookups_total()
    piggy0 = metrics.counter(
        "engine_decision_cache_piggybacks_total").value
    n = 8
    results = [None] * n

    def run(i):
        results[i] = e.lookup_resources_mask("ns", "view", "user", "u0")

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    time.sleep(0.2)  # let every thread reach the flight
    gate.set()
    for t in threads:
        t.join()
    e._lookup_submit = orig
    assert len(calls) == 1  # ONE leader dispatched
    assert lookups_total() - before == 1  # metrics delta agrees
    assert metrics.counter(
        "engine_decision_cache_piggybacks_total").value - piggy0 == n - 1
    ref = results[0][0].copy()
    for mask, _ in results:
        np.testing.assert_array_equal(mask, ref)
    # every caller got its OWN copy: mutating one leaves the rest intact
    results[0][0][:] = False
    np.testing.assert_array_equal(results[1][0], ref)


def test_singleflight_error_propagates_and_is_not_cached():
    e = build()
    e.lookup_resources_mask("ns", "view", "user", "uwarm")

    def boom(*a, **k):
        raise RuntimeError("device on fire")

    orig = e._lookup_submit
    e._lookup_submit = boom
    with pytest.raises(RuntimeError):
        e.lookup_resources_mask("ns", "view", "user", "u0")
    e._lookup_submit = orig
    # the error was not cached: the next call dispatches and succeeds
    mask, _ = e.lookup_resources_mask("ns", "view", "user", "u0")
    assert mask.any()


# ---------------------------------------------------------------------------
# try_cached_check (the middleware fast path)
# ---------------------------------------------------------------------------


def test_try_cached_check_probe():
    e = build()
    items = [CheckItem("ns", "n0", "view", "user", "u0"),
             CheckItem("ns", "n1", "view", "user", "u1")]
    assert e.try_cached_check(items) is None  # cold: no full answer
    e.check_bulk(items)
    assert e.try_cached_check(items) == [True, True]
    assert e.try_cached_check([]) == []
    # partial coverage -> None (a partial answer would dispatch anyway)
    assert e.try_cached_check(
        items + [CheckItem("ns", "n2", "view", "user", "u0")]) is None
    # a write moves the revision: the probe must miss, not serve stale
    e.write_relationships(
        [WriteOp("touch", parse_relationship("ns:n7#viewer@user:u7"))])
    assert e.try_cached_check(items) is None
    e2 = build(cache=False)
    assert e2.try_cached_check(items) is None


def test_cached_verdict_helper():
    from spicedb_kubeapi_proxy_tpu.authz.check import cached_verdict

    class _Probe:
        def __init__(self, answer):
            self.answer = answer

        def try_cached_check(self, items):
            return self.answer

    class _Rule:
        checks = ()
        post_checks = ()

    items, verdict = cached_verdict(_Probe([True, True]), [_Rule()], None)
    assert items == [] and verdict is True  # no checks -> allowed


# ---------------------------------------------------------------------------
# Store watermark + cache internals
# ---------------------------------------------------------------------------


def test_store_next_expiry_watermark():
    s = Store()
    now = time.time()
    assert s.next_expiry(now) == float("inf")
    s.write([WriteOp("touch", Relationship("ns", "a", "viewer", "user", "x",
                                           expiration=now + 50)),
             WriteOp("touch", Relationship("ns", "b", "viewer", "user", "x",
                                           expiration=now + 10)),
             WriteOp("touch", Relationship("ns", "c", "viewer", "user", "x"))])
    assert s.next_expiry(now) == pytest.approx(now + 10)
    # strictly-after semantics: AT the boundary the next one is reported
    assert s.next_expiry(now + 10) == pytest.approx(now + 50)
    assert s.next_expiry(now + 50) == float("inf")
    # deleting the nearest boundary moves the watermark
    s.write([WriteOp("delete", Relationship("ns", "b", "viewer", "user", "x",
                                            expiration=now + 10))])
    assert s.next_expiry(now) == pytest.approx(now + 50)


def test_lru_eviction_and_byte_budget():
    c = DecisionCache(max_entries=4, max_mask_bytes=1 << 30, shards=1)
    t = time.time()
    for i in range(8):
        c.put(("check", 1, i), True, float("inf"), 0, t)
    assert c.stats()["entries"] == 4
    assert c.get(("check", 1, 0), t) is MISS  # cold end evicted
    assert c.get(("check", 1, 7), t) is True
    # byte budget evicts mask-bearing entries independently of count
    cb = DecisionCache(max_entries=1000, max_mask_bytes=100, shards=1)
    cb.put(("lookup", 1, "a"), ("m", None), float("inf"), 60, t)
    cb.put(("lookup", 1, "b"), ("m", None), float("inf"), 60, t)
    assert cb.stats()["mask_bytes"] <= 100
    assert cb.get(("lookup", 1, "a"), t) is MISS
    assert cb.get(("lookup", 1, "b"), t) is not MISS


def test_born_dead_entries_are_not_stored():
    c = DecisionCache(shards=1)
    t = time.time()
    c.put(("check", 1, "k"), True, t - 1.0, 0, t)  # deadline already past
    assert c.stats()["entries"] == 0
    assert c.get(("check", 1, "k"), t) is MISS


def test_disable_clears_gauges():
    e = build()
    e.lookup_resources_mask("ns", "view", "user", "u0")
    g = metrics.gauge("engine_decision_cache_entries")
    before = g.value
    assert before >= 1
    e.disable_decision_cache()
    assert g.value <= before - 1
    # cache off: dispatches again (no phantom hits)
    before_l = lookups_total()
    e.lookup_resources_mask("ns", "view", "user", "u0")
    assert lookups_total() - before_l == 1


# ---------------------------------------------------------------------------
# The bulk entries (get_many / put_many): a bulk check's verdicts pass the
# cache a shard at a time, and leave it as key-by-key get / put leave it
# ---------------------------------------------------------------------------

T0 = 1_000_000.0
FOREVER = float("inf")


def _k(i, rev=1):
    return ("check", rev, "ns", f"n{i}", "view", "user", "u0", None)


def _bulk(ids, deadline=FOREVER, now=T0, rev=1):
    """One bulk check as the cache sees it: keys, the verdict each would
    get if it missed, the fill's deadline, the clock."""
    return ([_k(i, rev) for i in ids], [i % 3 == 0 for i in ids],
            deadline, now)


def _seeded_bulks(seed):
    rng = np.random.default_rng(seed)
    bulks = []
    for step in range(40):
        ids = rng.integers(0, 400, size=int(rng.integers(1, 120))).tolist()
        now = T0 + step
        # one fill in five dies ten ticks on, one in ten is born dead
        deadline = (now + 10 if step % 5 == 0
                    else now - 1 if step % 10 == 7 else FOREVER)
        bulks.append(_bulk(ids, deadline, now, rev=1 + step // 25))
    return bulks


BULK_CASES = {
    "fresh_keys": [_bulk(range(40)), _bulk(range(40, 80))],
    "repeats_hit_and_refresh_recency": [
        _bulk(range(40)), _bulk(range(20)), _bulk(range(30, 70))],
    "duplicates_inside_one_bulk": [
        _bulk([1, 2, 1, 3, 2, 1]), _bulk([3, 3, 1, 9, 9])],
    "bulk_of_one": [_bulk([5]), _bulk([5]), _bulk([6])],
    "expired_entries_drop_on_the_spot": [
        _bulk(range(30), deadline=T0 + 5),
        _bulk(range(10, 50), now=T0 + 6)],
    "born_dead_deadline_stores_nothing": [
        _bulk(range(30)), _bulk(range(20, 60), deadline=T0 - 1),
        _bulk(range(60))],
    "bulk_larger_than_the_budget": [
        _bulk(range(50)), _bulk(range(300)), _bulk(range(250, 320))],
    "another_revision_is_another_key": [
        _bulk(range(40)), _bulk(range(40), rev=2), _bulk(range(40))],
    "seeded_mix_7": _seeded_bulks(7),
    "seeded_mix_8": _seeded_bulks(8),
}

_CACHE_SERIES = (
    ("counter", "engine_decision_cache_hits_total", {"kind": "check"}),
    ("counter", "engine_decision_cache_hits_total", {"kind": "lookup"}),
    ("counter", "engine_decision_cache_misses_total", {"kind": "check"}),
    ("counter", "engine_decision_cache_misses_total", {"kind": "lookup"}),
    ("counter", "engine_decision_cache_evictions_total", {}),
    ("gauge", "engine_decision_cache_entries", {}),
    ("gauge", "engine_decision_cache_mask_bytes", {}),
)


def _cache_series():
    return [getattr(metrics, kind)(name, **labels).value
            for kind, name, labels in _CACHE_SERIES]


def _moved(drive):
    before = _cache_series()
    got = drive()
    return got, [a - b for a, b in zip(_cache_series(), before)]


def _key_by_key(cache, bulks):
    """The passes check_bulk_async made before the bulk entries: a get a
    key, then a put a key that missed."""
    answers = []
    for keys, verdicts, deadline, now in bulks:
        values = [cache.get(k, now) for k in keys]
        for k, v, verdict in zip(keys, values, verdicts):
            if v is MISS:
                cache.put(k, verdict, deadline, 0, now)
        answers.append(values)
    return answers


def _shard_at_a_time(cache, bulks):
    answers = []
    for keys, verdicts, deadline, now in bulks:
        values, missed = cache.get_many(keys, now)
        assert (sorted(i for _, positions in missed for i in positions)
                == [i for i, v in enumerate(values) if v is MISS])
        cache.put_many(keys, verdicts, deadline, now, missed)
        answers.append(values)
    return answers


def _shards(cache):
    return [list(sh.entries.items()) for sh in cache._shards]


def _small_cache():
    # 16 entries a shard; one lookup mask already over its shard's byte
    # budget (a lone entry is never evicted), for the puts to push out
    c = DecisionCache(max_entries=64, max_mask_bytes=400, shards=4)
    c.put(("lookup", 1, "ns", "view", "user", "u0", None), ("mask", None),
          FOREVER, 150, T0)
    return c


@pytest.mark.parametrize("case", sorted(BULK_CASES))
def test_bulk_entries_leave_the_cache_as_key_by_key_passes_do(case):
    bulks = BULK_CASES[case]
    one, many = _small_cache(), _small_cache()
    want, want_moved = _moved(lambda: _key_by_key(one, bulks))
    got, got_moved = _moved(lambda: _shard_at_a_time(many, bulks))
    assert got == want
    # the same keys in the same recency order under the same entries
    assert _shards(many) == _shards(one)
    assert many.stats() == one.stats()
    assert all(len(sh.entries) <= 16 for sh in many._shards)
    names = [f"{name}{labels or ''}" for _, name, labels in _CACHE_SERIES]
    assert dict(zip(names, got_moved)) == dict(zip(names, want_moved))


def test_get_many_counts_hits_and_misses_by_each_keys_kind():
    one, many = (DecisionCache(max_entries=64, shards=4) for _ in "ab")
    keys = [_k(1), ("lookup", 1, "ns", "view", "user", "u0", None),
            ("lookup", 1, "ns", "view", "user", "u9", None), _k(2)]
    for c in (one, many):
        c.put(keys[1], ("mask", None), FOREVER, 10, T0)
        c.put(_k(2), False, FOREVER, 0, T0)
    want, want_moved = _moved(lambda: [one.get(k, T0) for k in keys])
    (got, missed), got_moved = _moved(lambda: many.get_many(keys, T0))
    assert got == want == [MISS, ("mask", None), MISS, False]
    assert sorted(i for _, positions in missed for i in positions) == [0, 2]
    assert got_moved == want_moved
    assert _shards(many) == _shards(one)


def test_put_many_without_a_grouping_puts_every_key():
    one, many = _small_cache(), _small_cache()
    keys, verdicts, _, _ = _bulk(range(100))
    _, want_moved = _moved(lambda: [
        one.put(k, v, FOREVER, 0, T0) for k, v in zip(keys, verdicts)])
    _, got_moved = _moved(
        lambda: many.put_many(keys, verdicts, FOREVER, T0))
    assert _shards(many) == _shards(one)
    assert got_moved == want_moved


def test_put_many_after_clear_stores_nothing_and_moves_no_gauge():
    c = DecisionCache(max_entries=64, shards=4)
    keys, verdicts, _, _ = _bulk(range(40))
    _, missed = c.get_many(keys, T0)
    c.clear()  # the cache is detached while the bulk is on the device
    _, moved = _moved(
        lambda: c.put_many(keys, verdicts, FOREVER, T0, missed))
    assert c.stats() == {"entries": 0, "mask_bytes": 0}
    assert moved == [0] * len(_CACHE_SERIES)
    assert c.get_many(keys, T0)[0] == [MISS] * 40


def test_bulk_entries_share_one_entry_a_verdict():
    c = DecisionCache(shards=1)
    keys, verdicts, _, _ = _bulk(range(30))
    c.put_many(keys, verdicts, FOREVER, T0)
    assert len({id(ent) for ent in c._shards[0].entries.values()}) == 2
    assert c.get_many(keys, T0)[0] == verdicts


def test_check_bulk_takes_a_lock_a_shard_a_pass_and_observes_once():
    e = build()
    items = [CheckItem("ns", f"n{i}", "view", "user", "u0")
             for i in range(1000)]
    takes = metrics.counter("engine_bulk_cache_lock_takes_total")
    spent = metrics.histogram("engine_bulk_cache_seconds")
    misses = metrics.counter("engine_decision_cache_misses_total",
                             kind="check")
    hits = metrics.counter("engine_decision_cache_hits_total", kind="check")
    t0, n0, m0, h0 = takes.value, spent.n, misses.value, hits.value
    want = [True, True] + [False] * 998
    assert e.check_bulk(items) == want
    # a probe pass and a put pass, each at most one visit a shard
    assert 2 <= takes.value - t0 <= 2 * 16
    assert spent.n - n0 == 1
    assert (misses.value - m0, hits.value - h0) == (1000, 0)
    assert e._decision_cache.stats()["entries"] == 1000
    before = checks_total()
    t1 = takes.value
    assert e.check_bulk(items) == want  # served whole, one probe pass
    assert checks_total() == before
    assert 1 <= takes.value - t1 <= 16
    assert spent.n - n0 == 2
    assert (misses.value - m0, hits.value - h0) == (1000, 1000)
    # a bulk of one passes the same two entries
    t2 = takes.value
    assert e.check(CheckItem("ns", "n1", "view", "user", "u1")) is True
    assert takes.value - t2 == 2 and spent.n - n0 == 3


def test_check_bulk_stores_under_the_key_check_key_builds():
    from spicedb_kubeapi_proxy_tpu.engine.decision_cache import check_key

    e = build()
    items = [CheckItem("ns", "n0", "view", "user", "u0"),
             CheckItem("ns", "n2", "view", "group", "g0", "member")]
    e.check_bulk(items)
    rev = e.compiled().revision
    stored = {k for sh in e._decision_cache._shards for k in sh.entries}
    assert stored == {check_key(rev, it) for it in items}


def _run_threads(target, offsets):
    """Threads that change hands every few bytecodes (so they do meet
    inside the passes), joined with a time limit."""
    import sys

    threads = [threading.Thread(target=target, args=(o,)) for o in offsets]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)


def test_two_threads_pass_overlapping_bulks_through_one_cache():
    """Load-sensitive: counts only. Two threads put and probe bulks whose
    keys overlap; every shard stays within its budget, the gauge agrees
    with the cache, and a key probed after its put (same revision, no
    eviction possible: the budget holds every key) hits."""
    c = DecisionCache(max_entries=4096, shards=4)
    gauge = metrics.gauge("engine_decision_cache_entries")
    g0 = gauge.value
    errors = []

    def run(offset):
        try:
            for round_ in range(30):
                ids = range(offset + 20 * round_, offset + 20 * round_ + 400)
                keys, verdicts, _, _ = _bulk(ids)
                _, missed = c.get_many(keys, T0)
                c.put_many(keys, verdicts, FOREVER, T0, missed)
                values, again = c.get_many(keys, T0)
                assert values == verdicts and not again
        except BaseException as ex:  # noqa: BLE001 - reported below
            errors.append(ex)
            raise

    _run_threads(run, (0, 200))
    assert not errors, errors
    # ids 0..1179 between them, each stored once whoever put it
    assert c.stats()["entries"] == 1180
    assert all(len(sh.entries) <= 1024 for sh in c._shards)
    assert gauge.value - g0 == c.stats()["entries"]
    # over budget, two threads evicting in the same shards: never more
    # than a shard's share, and the gauge still agrees
    small = DecisionCache(max_entries=64, shards=4)
    g1 = gauge.value

    def churn(offset):
        for round_ in range(30):
            keys, verdicts, _, _ = _bulk(
                range(offset + 50 * round_, offset + 50 * round_ + 300))
            _, missed = small.get_many(keys, T0)
            small.put_many(keys, verdicts, FOREVER, T0, missed)

    _run_threads(churn, (0, 120))
    assert all(len(sh.entries) <= 16 for sh in small._shards)
    assert gauge.value - g1 == small.stats()["entries"] <= 64

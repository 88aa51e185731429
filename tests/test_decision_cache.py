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

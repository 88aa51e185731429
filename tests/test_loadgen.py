"""Open-loop macrobench + live SLO layer tests (ISSUE 7).

Covers the loadgen subsystem (schedule determinism, the open-loop pin),
the SLO monitor (objective parsing, burn rates, the
``slo_*`` metric family, ``/debug/slo``), the previously-unexercised
authz surface the macrobench drives (LookupSubjects, wildcard relations
through the proxy filter path, Table filtering at >=1k rows), and the
shed-503 ``X-Trace-Id`` + rate-capped shed audit line regression.
"""

import asyncio
import json
import threading
import time

import pytest

from spicedb_kubeapi_proxy_tpu.admission import AdmissionRejected
from spicedb_kubeapi_proxy_tpu.authz import AuthzDeps, authorize
from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
from spicedb_kubeapi_proxy_tpu.loadgen import (
    OpenLoopDriver,
    ScheduleConfig,
    build_schedule,
    trace_shaped_config,
)
from spicedb_kubeapi_proxy_tpu.loadgen.driver import (
    OUTCOME_ERROR,
    OUTCOME_OK,
    OUTCOME_SHED,
    DriverReport,
)
from spicedb_kubeapi_proxy_tpu.loadgen.schedule import (
    OP_CHECK,
    OP_LIST_PREFILTER,
    OP_WATCH_OPEN,
    burst_windows,
)
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.obs.audit import AuditLog
from spicedb_kubeapi_proxy_tpu.obs.slo import (
    SLOError,
    SLOMonitor,
    default_objectives,
    parse_objectives,
)
from spicedb_kubeapi_proxy_tpu.obs.trace import tracer
from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyRequest, json_response
from spicedb_kubeapi_proxy_tpu.rules import MapMatcher
from spicedb_kubeapi_proxy_tpu.rules.input import UserInfo
from spicedb_kubeapi_proxy_tpu.utils.metrics import (
    Histogram,
    Registry,
    metrics,
)

SCHEMA = """
definition user {}
definition group {
  relation member: user
}
definition namespace {
  relation viewer: user | user:* | group#member
  permission view = viewer
}
"""

LIST_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: ns-list
match:
  - apiVersion: v1
    resource: namespaces
    verbs: [list]
prefilter:
  - fromObjectIDNameExpr: "{{resourceId}}"
    lookupMatchingResources:
      tpl: "namespace:$#view@user:{{user.name}}"
"""


def _engine(tuples) -> Engine:
    """An engine over SCHEMA holding ``(ns, subject_type, subject_id[,
    subject_relation])`` viewer tuples (or group member tuples via
    ``("group:g", "user", id)``)."""
    import numpy as np

    cols = {k: [] for k in ("resource_type", "resource_id", "relation",
                            "subject_type", "subject_id",
                            "subject_relation")}
    for t in tuples:
        res, st, sid = t[0], t[1], t[2]
        srl = t[3] if len(t) > 3 else ""
        rt, rid = res.split(":", 1)
        cols["resource_type"].append(rt)
        cols["resource_id"].append(rid)
        cols["relation"].append("viewer" if rt == "namespace" else "member")
        cols["subject_type"].append(st)
        cols["subject_id"].append(sid)
        cols["subject_relation"].append(srl)
    e = Engine(schema=parse_schema(SCHEMA))
    e.bulk_load({k: np.asarray(v) for k, v in cols.items()})
    return e


def _request(method, path, user="alice", query=None):
    query = query or {}
    return ProxyRequest(
        method=method, path=path, query=query,
        headers={"Content-Type": "application/json"}, body=b"",
        user=UserInfo(name=user),
        request_info=parse_request_info(method, path, query))


# -- schedule -----------------------------------------------------------------


def test_identical_seed_identical_schedule():
    """The reproducibility pin: same seed => byte-identical arrivals;
    a different seed diverges."""
    cfg = trace_shaped_config(4.0, 200.0, tenants=6, seed=99)
    a, b = build_schedule(cfg), build_schedule(cfg)
    assert a == b
    assert len(a) > 400
    c = build_schedule(trace_shaped_config(4.0, 200.0, tenants=6, seed=98))
    assert a != c


def test_burst_phases_modulate_rate_and_mix():
    cfg = trace_shaped_config(10.0, 100.0, seed=3, burst_multiplier=4.0)
    sched = build_schedule(cfg)
    wins = dict((n, (a, b)) for n, a, b in burst_windows(cfg))
    s0, s1 = wins["watch-storm"]

    def rate(t0, t1):
        return sum(1 for a in sched if t0 <= a.t < t1) / (t1 - t0)

    # the storm window runs ~4x the pre-storm baseline
    assert rate(s0, s1) > 2.5 * rate(0.0, s0)
    # and its mix shifts toward watch-open
    in_storm = [a for a in sched if s0 <= a.t < s1]
    storm_watch = sum(a.op == OP_WATCH_OPEN for a in in_storm) / len(in_storm)
    base = [a for a in sched if a.t < s0]
    base_watch = sum(a.op == OP_WATCH_OPEN for a in base) / max(1, len(base))
    assert storm_watch > 3 * base_watch
    # arrivals are tagged with their phase
    assert all(a.phase == "watch-storm" and a.burst for a in in_storm)


def test_zipf_tenant_skew():
    cfg = ScheduleConfig(duration=5.0, rate=400.0, tenants=8, zipf_s=1.2,
                         seed=1)
    sched = build_schedule(cfg)
    counts = {}
    for a in sched:
        counts[a.tenant] = counts.get(a.tenant, 0) + 1
    # rank-0 tenant dominates the tail tenant by a wide margin
    assert counts["tenant0"] > 4 * counts.get("tenant7", 1)


# -- driver: the open-loop pin ------------------------------------------------


def test_open_loop_never_closes_under_shedding():
    """THE acceptance pin: a server that sheds half its arrivals and
    stalls the rest gets the full scheduled offered load anyway —
    offered stays within 5% of the schedule."""
    shed = [0]
    done = [0]

    def slow_shedding_op(a):
        if a.key % 2:
            shed[0] += 1
            raise AdmissionRejected("check", "queue full", retry_after=1.0)
        time.sleep(0.02)  # far slower than the arrival gap
        done[0] += 1

    cfg = ScheduleConfig(duration=1.5, rate=300.0, tenants=4, seed=5,
                         mix={OP_CHECK: 1.0})
    sched = build_schedule(cfg)
    driver = OpenLoopDriver({OP_CHECK: slow_shedding_op}, max_workers=8,
                            drain_timeout=10.0)
    rep = driver.run(sched, duration=cfg.duration)
    # every scheduled arrival was fired: the loop never closed, so the
    # offered load is the schedule's, within 5%, no matter what the
    # server did (here: half shed, the rest 6x slower than the gap)
    assert rep.fired_n == rep.scheduled_n == len(sched)
    assert abs(rep.offered_rps - len(sched) / cfg.duration) \
        <= 0.05 * len(sched) / cfg.duration
    # generator drift is REPORTED (late_n), never silently absorbed into
    # arrival times; a stalling server must not push the whole schedule
    # late (that would be the loop closing through the dispatcher)
    assert rep.late_n < rep.fired_n / 2, \
        f"{rep.late_n}/{rep.fired_n} arrivals submitted late"
    # sheds are accounted outcomes, not errors
    per = rep.per_class()[OP_CHECK]
    assert per["shed"] == shed[0] > 50
    assert per["error"] == 0


def test_driver_outcome_accounting_and_exec_split():
    def op(a):
        if a.key % 3 == 0:
            raise AdmissionRejected("check", "shed", retry_after=0.5)
        if a.key % 3 == 1:
            raise ValueError("boom")

    cfg = ScheduleConfig(duration=0.4, rate=200.0, seed=2,
                         mix={OP_CHECK: 1.0}, key_space=30)
    rep = OpenLoopDriver({OP_CHECK: op}, max_workers=4).run(
        build_schedule(cfg), duration=cfg.duration)
    outs = {r.outcome for r in rep.records}
    assert outs == {OUTCOME_OK, OUTCOME_SHED, OUTCOME_ERROR}
    assert rep.error_samples and "boom" in rep.error_samples[0]
    for r in rep.records:
        assert r.latency_s >= r.exec_s >= 0.0


# -- metrics satellites -------------------------------------------------------


def test_histogram_quantile_empty_window_is_none_not_zero():
    h = Histogram()
    assert h.quantile(0.5) is None
    assert h.quantile(0.999) is None
    h.observe(0.004)
    assert h.quantile(0.5) is not None
    assert h.quantile(0.999) == h.quantile(0.5)  # single sample


def test_hist_snapshot_label_filter():
    r = Registry()
    r.histogram("lg_test_seconds", op="a").observe(0.001)
    r.histogram("lg_test_seconds", op="b").observe(0.001)
    r.histogram("lg_test_seconds", op="b").observe(0.001)
    assert r.hist_snapshot("lg_test_seconds")["n"] == 3
    assert r.hist_snapshot("lg_test_seconds", op="b")["n"] == 2
    assert r.hist_snapshot("lg_test_seconds", op="nope") is None


# -- SLO monitor --------------------------------------------------------------


def test_parse_objectives_good_and_bad():
    objs = parse_objectives("check=25:99.9, lookup=100:99")
    assert [o.name for o in objs] == ["check", "lookup"]
    assert [o.latency_ms for o in objs] == [25.0, 100.0]
    assert [o.target for o in objs] == pytest.approx([0.999, 0.99])
    assert objs[0].histogram == "engine_check_seconds"
    for bad in ("nope=25:99", "check", "check=abc:99", "check=25:0",
                "check=-1:99", ""):
        with pytest.raises(SLOError):
            parse_objectives(bad)


def test_burn_rate_multi_window():
    """1% bad at a 99.9% target burns 10x; the short window recovers
    once traffic goes clean while the long window still remembers."""
    r = Registry()
    clock = [1000.0]
    mon = SLOMonitor(parse_objectives("check=25:99.9"),
                     windows=(10.0, 100.0), tick_seconds=1.0,
                     clock=lambda: clock[0], registry=r)
    h = r.histogram("engine_check_seconds")
    for _ in range(990):
        h.observe(0.001)  # good
    for _ in range(10):
        h.observe(0.5)  # bad (>25ms)
    clock[0] += 5.0
    mon.tick()
    st = mon._window_stats("check")
    for w in (10.0, 100.0):
        assert st[w]["events"] == 1000
        assert st[w]["bad"] == 10
        assert st[w]["attainment"] == pytest.approx(0.99)
        assert st[w]["burn_rate"] == pytest.approx(10.0, rel=1e-6)
    # clean traffic afterwards: the 10s window forgives, 100s remembers
    for _ in range(1000):
        h.observe(0.001)
    clock[0] += 20.0
    mon.tick()
    st = mon._window_stats("check")
    assert st[10.0]["bad"] == 0 and st[10.0]["burn_rate"] == 0.0
    assert st[100.0]["bad"] == 10 and st[100.0]["burn_rate"] > 0.0
    # gauges exported per window
    assert r.gauge("slo_burn_rate", objective="check",
                   window="10s").value == 0.0
    assert r.gauge("slo_burn_rate", objective="check",
                   window="100s").value > 0.0


def test_slo_counts_sheds_as_bad_events():
    """A shed never reaches the latency histogram; the objective's bad
    counters fold it into both events and bad."""
    r = Registry()
    clock = [0.0]
    mon = SLOMonitor(parse_objectives("check=25:99"), windows=(60.0,),
                     tick_seconds=1.0, clock=lambda: clock[0], registry=r)
    h = r.histogram("engine_check_seconds")
    for _ in range(99):
        h.observe(0.001)
    r.counter("admission_shed_total", **{"class": "check"}).inc()
    clock[0] += 1.0
    mon.tick()
    st = mon._window_stats("check")[60.0]
    assert st["events"] == 100 and st["bad"] == 1
    assert st["burn_rate"] == pytest.approx(1.0, rel=1e-6)


def test_slo_metrics_pass_exposition_contract():
    """slo_* gauges registered in the SHARED registry render through the
    same exposition path the contract lint gates."""
    mon = SLOMonitor(default_objectives(), windows=(60.0,),
                     tick_seconds=1.0)
    mon.tick()
    text = metrics.render()
    assert 'slo_burn_rate{objective="check",window="60s"}' in text
    assert 'slo_attainment{objective="check",window="60s"}' in text
    assert 'slo_objective_latency_ms{objective="check"} 25' in text


def test_slo_ring_prunes_by_age_not_count():
    """Frequent external ticks (every /debug/slo read appends a sample)
    must not shrink the span the long window measures: samples are kept
    for the longest window's duration regardless of tick count."""
    r = Registry()
    clock = [0.0]
    mon = SLOMonitor(parse_objectives("check=25:99"), windows=(100.0,),
                     tick_seconds=5.0, clock=lambda: clock[0], registry=r)
    h = r.histogram("engine_check_seconds")
    h.observe(0.5)  # one bad event at t=0
    mon.tick()
    # a read storm: 500 ticks over 50s — far more samples than the
    # old count-based depth (100/5+2) would have kept
    for i in range(500):
        clock[0] = 0.1 * (i + 1) + 1.0
        mon.tick()
    st = mon._window_stats("check")[100.0]
    assert st["bad"] == 1, "the old bad event fell out of a 100s window"
    # and age pruning still bounds the ring: once the clock moves past
    # the window (plus slack), the old samples are dropped
    clock[0] = 300.0
    mon.tick()
    clock[0] = 301.0
    mon.tick()
    assert mon._ring[0][0] >= 301.0 - 100.0 - 2 * 5.0
    assert len(mon._ring) <= 3


def test_slo_monitor_thread_lifecycle():
    mon = SLOMonitor(default_objectives(), windows=(30.0,),
                     tick_seconds=0.01)
    mon.start()
    mon.start()  # idempotent
    time.sleep(0.05)
    mon.stop()
    assert mon._thread is None
    with pytest.raises(SLOError):
        SLOMonitor([], windows=(30.0,))
    with pytest.raises(SLOError):
        SLOMonitor(default_objectives(), windows=())


# -- /debug/slo ---------------------------------------------------------------


def test_debug_slo_endpoint_flag_gated_and_live(tmp_path):
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    async def go():
        # gated off: 404 even though authenticated
        off = Options(
            rule_content=LIST_RULES, upstream=FakeKube(),
            workflow_database_path=str(tmp_path / "dtx1.sqlite"),
        ).complete()
        alice = InMemoryClient(off.server.handle, user="alice")
        assert (await alice.get("/debug/slo")).status == 404
        await off.workflow.shutdown()

        on = Options(
            rule_content=LIST_RULES, upstream=FakeKube(),
            workflow_database_path=str(tmp_path / "dtx2.sqlite"),
            enable_debug_slo=True,
            slo_objectives="check=25:99.9,request=250:99",
            slo_windows="30,300",
        ).complete()
        try:
            alice = InMemoryClient(on.server.handle, user="alice")
            # unauthenticated is rejected before the endpoint
            anon = InMemoryClient(on.server.handle)
            assert (await anon.get("/debug/slo")).status == 401
            # drive one real request so the request objective has events
            assert (await alice.get("/api/v1/namespaces")).status == 200
            resp = await alice.get("/debug/slo")
            assert resp.status == 200
            doc = json.loads(resp.body)
            assert doc["windows_seconds"] == [30.0, 300.0]
            by_name = {o["name"]: o for o in doc["objectives"]}
            assert set(by_name) == {"check", "request"}
            o = by_name["request"]
            assert o["latency_ms"] == 250.0 and o["target"] == 0.99
            w = o["windows"]["30s"]
            assert {"events", "bad", "attainment", "burn_rate"} <= set(w)
            # the endpoint tick sampled the request we just made
            assert w["events"] >= 1
        finally:
            await on.workflow.shutdown()
            if on.slo_monitor is not None:
                on.slo_monitor.stop()

    asyncio.run(go())


def test_slo_options_validation():
    from spicedb_kubeapi_proxy_tpu.proxy.options import (
        Options,
        OptionsError,
    )

    for kw in ({"slo_objectives": "nope=1:99"},
               {"slo_objectives": "check=25:99", "slo_windows": "0,60"},
               {"enable_debug_slo": True, "slo_windows": "garbage"},
               {"slo_objectives": "check=25:99",
                "slo_tick_seconds": 0.0},
               # a window sampled less than once per span is blind
               {"slo_objectives": "check=25:99", "slo_windows": "60,300",
                "slo_tick_seconds": 90.0}):
        with pytest.raises(OptionsError):
            Options(rule_content="x", upstream_url="http://u",
                    **kw).validate()


# -- shed 503: X-Trace-Id + audit agreement -----------------------------------


def test_shed_503_header_and_audit_line_without_server_wrapper(tmp_path):
    """Regression (ISSUE 7 satellite): the early-reject 503 emitted
    before the root span's normal finish path still carries
    ``X-Trace-Id``, and the shed leaves a rate-capped audit line whose
    trace_id agrees with the header."""
    class AlwaysShed:
        async def acquire_async(self, tenant, cls):
            raise AdmissionRejected(cls.name, "queue full",
                                    retry_after=2.0)

    audit_path = str(tmp_path / "audit.jsonl")
    audit = AuditLog(audit_path, allow_rps=10.0)
    e = _engine([("namespace:ns0", "user", "alice")])
    deps = AuthzDeps(matcher=MapMatcher.from_yaml(LIST_RULES), engine=e,
                     upstream=None, admission=AlwaysShed(), audit=audit)

    async def go():
        tracer.configure(sample=1.0)
        # no server wrapper: authorize() runs under a bare root span the
        # way executor-side callers and in-memory transports drive it
        with tracer.start("request", method="GET",
                          path="/api/v1/namespaces") as root:
            resp = await authorize(
                _request("GET", "/api/v1/namespaces"), deps)
        assert resp.status == 503
        assert resp.headers["X-Trace-Id"] == root.trace_id
        assert resp.headers["Retry-After"] == "2"
        return root.trace_id

    trace_id = asyncio.run(go())
    audit.flush()
    audit.close()
    lines = [json.loads(ln) for ln in open(audit_path)]
    sheds = [r for r in lines if r["decision"] == "shed"]
    assert len(sheds) == 1
    s = sheds[0]
    assert s["class"] == "lookup-prefilter"
    assert s["tenant"] == "alice"
    assert s["verb"] == "list" and s["resource"] == "namespaces"
    assert s["retry_after"] == 2.0
    assert s["trace_id"] == trace_id  # audit and trace agree


def test_shed_audit_lines_rate_capped():
    clock = [0.0]
    import io

    a = AuditLog.__new__(AuditLog)
    # construct against stderr to avoid files, then swap the stream
    AuditLog.__init__(a, "stderr", allow_rps=3.0, clock=lambda: clock[0])
    a._fh = io.StringIO()
    before = metrics.counter("audit_sheds_sampled_out_total").value
    for i in range(10):
        a.shed(op_class="check", tenant=f"t{i}", retry_after=1.0,
               trace_id=f"{i:032x}")
    a.flush()
    out = [json.loads(ln) for ln in a._fh.getvalue().splitlines()]
    assert len(out) == 3  # burst = shed_rps with the clock frozen
    assert all(r["decision"] == "shed" for r in out)
    assert metrics.counter(
        "audit_sheds_sampled_out_total").value - before == 7
    a.close()


# -- the macrobench's authz surface -------------------------------------------


def test_lookup_subjects_direct_group_and_wildcard():
    e = _engine([
        ("namespace:ns0", "user", "alice"),
        ("namespace:ns0", "group", "g0", "member"),
        ("group:g0", "user", "bob"),
        ("group:g0", "user", "carol"),
        ("namespace:other", "user", "dave"),
        ("namespace:pub", "user", "*"),
        ("namespace:pub", "user", "eve"),
    ])
    # direct + group-expanded subjects; dave (other ns only) excluded
    assert e.lookup_subjects("namespace", "ns0", "view", "user") == [
        "alice", "bob", "carol"]
    # the wildcard namespace admits every KNOWN subject, reported as
    # concrete ids — never a literal '*' row
    subs = e.lookup_subjects("namespace", "pub", "view", "user")
    assert "*" not in subs
    assert set(subs) == {"alice", "bob", "carol", "dave", "eve"}
    assert e.lookup_subjects("namespace", "nothere", "view", "user") == []


def test_wildcard_relations_through_proxy_filter_path():
    """A ``user:*`` grant flows end-to-end: prefiltered list responses
    include public namespaces for a subject holding no direct tuples."""
    e = _engine([
        ("namespace:mine", "user", "alice"),
        ("namespace:pub", "user", "*"),
    ])
    items = [{"apiVersion": "v1", "kind": "Namespace",
              "metadata": {"name": n}} for n in ("mine", "pub")]

    async def upstream(req):
        return json_response(200, {"kind": "NamespaceList",
                                   "apiVersion": "v1", "items": items})

    deps = AuthzDeps(matcher=MapMatcher.from_yaml(LIST_RULES), engine=e,
                     upstream=upstream)

    async def names(user):
        resp = await authorize(
            _request("GET", "/api/v1/namespaces", user=user), deps)
        assert resp.status == 200
        return sorted(o["metadata"]["name"]
                      for o in json.loads(resp.body)["items"])

    async def go():
        assert await names("alice") == ["mine", "pub"]
        # ghost has NO tuples at all: the wildcard alone grants pub
        assert await names("ghost") == ["pub"]

    asyncio.run(go())


def test_table_response_filtering_at_1k_rows():
    """Table filtering at macrobench scale: >=1k rows filtered by the
    allowed-set in one pass, kept rows byte-preserved."""
    n_rows, allowed_every = 1500, 3
    e = _engine([(f"namespace:ns{i}", "user", "alice")
                 for i in range(0, n_rows, allowed_every)])
    table = {
        "kind": "Table", "apiVersion": "meta.k8s.io/v1",
        "columnDefinitions": [{"name": "Name", "type": "string"}],
        "rows": [{"cells": [f"ns{i}"],
                  "object": {"kind": "PartialObjectMetadata",
                             "metadata": {"name": f"ns{i}"}}}
                 for i in range(n_rows)],
    }

    async def upstream(req):
        return json_response(200, table)

    deps = AuthzDeps(matcher=MapMatcher.from_yaml(LIST_RULES), engine=e,
                     upstream=upstream)

    async def go():
        resp = await authorize(
            _request("GET", "/api/v1/namespaces", user="alice"), deps)
        assert resp.status == 200
        doc = json.loads(resp.body)
        kept = [r["cells"][0] for r in doc["rows"]]
        assert kept == [f"ns{i}" for i in range(0, n_rows, allowed_every)]
        # a no-tuples user keeps nothing
        resp = await authorize(
            _request("GET", "/api/v1/namespaces", user="ghost"), deps)
        assert json.loads(resp.body)["rows"] == []

    asyncio.run(go())


# -- loadgen metrics land in the shared registry ------------------------------


def test_driver_observes_loadgen_metrics():
    before = metrics.counter("loadgen_ops_total", op=OP_CHECK,
                             outcome=OUTCOME_OK).value
    cfg = ScheduleConfig(duration=0.2, rate=100.0, seed=4,
                         mix={OP_CHECK: 1.0})
    rep = OpenLoopDriver({OP_CHECK: lambda a: None}, max_workers=2).run(
        build_schedule(cfg), duration=cfg.duration)
    after = metrics.counter("loadgen_ops_total", op=OP_CHECK,
                            outcome=OUTCOME_OK).value
    assert after - before == rep.fired_n
    snap = metrics.hist_snapshot("loadgen_op_seconds", op=OP_CHECK)
    assert snap is not None and snap["n"] >= rep.fired_n

"""Observability subsystem (ISSUE 6): end-to-end request tracing, the
decision audit log, engine profiling hooks, and the Prometheus exposition
contract.

The e2e pins: a live proxy + tcp engine-host request produces ONE trace
holding both proxy-side and engine-host-side spans (stitched via the wire
frame field); denies always land in the audit log with the matched rule
and trace_id; the failure paths (admission shed, breaker-open
fail-closed, failover re-aim) keep their traces and carry the trace id to
the client.
"""

import asyncio
import gc
import json
import os
import random
import re
import time
import weakref

import pytest

from spicedb_kubeapi_proxy_tpu.obs.audit import AuditLog
from spicedb_kubeapi_proxy_tpu.obs.trace import (
    Tracer,
    format_traceparent,
    parse_traceparent,
    tracer,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import (
    Histogram,
    Registry,
    metrics,
    snapshot_delta_quantile,
)

RULES = open(os.path.join(os.path.dirname(__file__), "..", "deploy",
                          "rules.yaml")).read()


@pytest.fixture(autouse=True)
def _tracing_on():
    """Every test starts from a clean, keep-everything tracer and leaves
    the module-global in its default state."""
    tracer.configure(sample=1.0, slow_ms=250.0, ring=256)
    tracer.reset()
    yield
    tracer.configure(sample=0.1, slow_ms=250.0, ring=256,
                     _rand=random.random)  # a test may have loaded the dice
    tracer.reset()


# -- traceparent --------------------------------------------------------------


def test_traceparent_roundtrip():
    tp = format_traceparent("0af7651916cd43dd8448eb211c80319c",
                            "b7ad6b7169203331")
    assert tp == ("00-0af7651916cd43dd8448eb211c80319c-"
                  "b7ad6b7169203331-01")
    trace_id, span_id, flags = parse_traceparent(tp)
    assert trace_id == "0af7651916cd43dd8448eb211c80319c"
    assert span_id == "b7ad6b7169203331"
    assert flags == 1


def test_traceparent_malformed_is_none():
    for bad in (None, "", "garbage", "00-short-short-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace
                "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # zero span
                "00-" + "z" * 32 + "-" + "1" * 16 + "-01",  # non-hex
                "00-" + "1" * 32 + "-" + "1" * 16,  # missing flags
                42):
        assert parse_traceparent(bad) is None, bad


def test_concurrent_same_traceparent_requests_stay_separate():
    """A client retry reusing its traceparent while the original is
    still in flight must NOT share a live trace (engine-host spans and
    stage timings would cross-stitch between unrelated requests): the
    second request gets a fresh trace_id, keeping the requested one as
    an attribute."""
    tp = format_traceparent("e" * 32, "f" * 16)
    with tracer.start("request", traceparent=tp) as first:
        with tracer.start("request", traceparent=tp) as second:
            assert second.trace_id != first.trace_id
            assert second.attrs["requested_trace_id"] == "e" * 32
            # adopt() while both live stitches to the ORIGINAL holder
            with tracer.adopt(tp, "engine_host.op") as sp:
                assert sp.trace_id == first.trace_id
        # the inner root's finish must not evict the original live entry
        with tracer.adopt(tp, "engine_host.op2") as sp:
            assert sp.trace_id == first.trace_id
    kept = {t["trace_id"] for t in tracer.recent()}
    assert {first.trace_id, second.trace_id} <= kept


def test_ingress_adopts_incoming_traceparent():
    with tracer.start("request", traceparent=format_traceparent(
            "c" * 32, "d" * 16)) as root:
        assert root.trace_id == "c" * 32
    kept = tracer.recent(1)
    assert kept and kept[0]["trace_id"] == "c" * 32
    # the root's parent is the incoming span id
    root_span = [s for s in kept[0]["spans"] if s["name"] == "request"][0]
    assert root_span["parent_id"] == "d" * 16


# -- tail sampling ------------------------------------------------------------


def test_tail_sampling_keeps_errors_sheds_and_slow_only():
    t = Tracer(sample=0.5, slow_ms=10_000.0, ring=64)
    t.configure(_rand=lambda: 0.99)  # above sample: ordinary drops
    with t.start("request"):
        pass
    assert t.recent() == []
    with t.start("request"):
        t.flag("error", "boom")
    with t.start("request"):
        t.flag("shed")
    assert len(t.recent()) == 2
    t.configure(slow_ms=0.0)  # everything is "slow" now
    with t.start("request"):
        pass
    assert len(t.recent()) == 3
    # sample=0 disables recording entirely
    t.configure(sample=0.0, slow_ms=0.0)
    with t.start("request") as root:
        assert root.trace_id is None
    assert len(t.recent()) == 3


def test_span_exception_flags_trace_error():
    t = Tracer(sample=0.0001, slow_ms=10_000.0, ring=64)
    t.configure(_rand=lambda: 0.99)
    with pytest.raises(RuntimeError):
        with t.start("request"):
            with t.span("engine_dispatch"):
                raise RuntimeError("device fell over")
    kept = t.recent()
    assert len(kept) == 1 and kept[0]["flags"].get("error")
    sp = [s for s in kept[0]["spans"] if s["name"] == "engine_dispatch"][0]
    assert "device fell over" in sp["attrs"]["error"]


def test_spans_cross_executor_hops_via_capture_activate():
    import concurrent.futures

    with tracer.start("request") as root:
        cap = tracer.capture()

        def worker():
            with tracer.activate(cap), tracer.span("engine_device"):
                return tracer.current_trace_id()

        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            tid = pool.submit(worker).result()
        assert tid == root.trace_id
    kept = tracer.recent(1)[0]
    assert {"engine_device", "request"} <= {s["name"]
                                            for s in kept["spans"]}


# -- histogram quantile + exposition ------------------------------------------


def test_histogram_quantile_overflow_clamps_to_max():
    h = Histogram(buckets=(0.001, 0.01))
    h.observe(42.5)
    h.observe(97.25)
    # both observations overflow the last bucket: p50/p99 must be the
    # largest observed value, never float("inf") (BENCH_*.json fields)
    assert h.quantile(0.5) == 97.25
    assert h.quantile(0.99) == 97.25
    assert h.quantile(0.99) != float("inf")
    h.observe(0.0005)
    assert h.quantile(0.01) == 0.001  # in-range targets keep bucket UB


def test_snapshot_delta_quantile_windows():
    h = Histogram(buckets=(0.001, 0.01, 0.1))
    h.observe(0.05)
    before = h.snapshot()
    assert snapshot_delta_quantile(before, h.snapshot(), 0.5) is None
    for _ in range(9):
        h.observe(0.005)
    h.observe(7.0)
    after = h.snapshot()
    assert snapshot_delta_quantile(before, after, 0.5) == 0.01
    assert snapshot_delta_quantile(before, after, 0.999) == 7.0


def test_histogram_renders_cumulative_buckets_and_types():
    r = Registry()
    r.counter("demo_total").inc()
    r.gauge("demo_gauge").set(3)
    h = r.histogram("demo_seconds", dependency="x")
    for v in (0.0001, 0.004, 50.0):
        h.observe(v)
    text = r.render()
    assert "# TYPE demo_total counter" in text
    assert "# TYPE demo_gauge gauge" in text
    assert "# TYPE demo_seconds histogram" in text
    # cumulative bucket series, closed by +Inf == _count
    assert 'demo_seconds_bucket{dependency="x",le="0.005"} 2' in text
    assert 'demo_seconds_bucket{dependency="x",le="+Inf"} 3' in text
    # the historical lines are unchanged (backward compatibility)
    assert 'demo_seconds_count{dependency="x"} 3' in text
    assert 'demo_seconds_sum{dependency="x"}' in text
    # buckets are monotonically non-decreasing
    counts = [int(m.group(1)) for m in re.finditer(
        r'demo_seconds_bucket\{[^}]*\} (\d+)', text)]
    assert counts == sorted(counts)


_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (.+)$")


def test_metrics_exposition_lints():
    """The scrape-format contract (CI-pinned): every registered metric
    name matches Prometheus naming rules, no duplicate name+label-set
    sample, and every histogram renders a bucket series closed by +Inf.
    Exercises a representative slice of the real instrumentation first so
    the lint sees the names production registers."""
    async def exercise():
        from fake_kube import FakeKube
        from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient
        from spicedb_kubeapi_proxy_tpu.proxy.options import Options

        import tempfile

        cfg = Options(
            rule_content=RULES, upstream=FakeKube(), bind_port=0,
            workflow_database_path=os.path.join(
                tempfile.mkdtemp(prefix="obslint-"), "dtx.sqlite"),
            admission=True,
        ).complete()
        alice = InMemoryClient(cfg.server.handle, user="alice")
        assert (await alice.post(
            "/api/v1/namespaces",
            {"metadata": {"name": "lint"}})).status == 201
        assert (await alice.get("/api/v1/namespaces")).status == 200
        assert (await alice.get("/api/v1/namespaces/lint")).status == 200
        bob = InMemoryClient(cfg.server.handle, user="bob")
        assert (await bob.get("/api/v1/namespaces/lint")).status == 403
        await cfg.workflow.shutdown()

    asyncio.run(exercise())
    text = metrics.render()
    assert text.strip(), "registry rendered empty after real traffic"
    seen: set = set()
    hist_names: set = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert _NAME_RE.match(name), f"bad metric name {name!r}"
            if kind == "histogram":
                hist_names.add(name)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        assert _NAME_RE.match(name), f"bad metric name {name!r}"
        for lk in re.findall(r'([a-zA-Z0-9_]+)="', labels):
            assert _NAME_RE.match(lk), f"bad label name {lk!r} in {line!r}"
        float(value)  # every sample value parses as a number
        assert (name, labels) not in seen, f"duplicate sample {line!r}"
        seen.add((name, labels))
    assert hist_names, "no histograms registered by real traffic"
    for name in hist_names:
        assert f'{name}_bucket' in text, f"{name} renders no buckets"
        assert re.search(rf'{name}_bucket{{[^}}]*le="\+Inf"}}', text), \
            f"{name} bucket series not closed by +Inf"


# -- audit log ----------------------------------------------------------------


def test_audit_denies_always_allows_rate_capped(tmp_path):
    path = str(tmp_path / "audit.jsonl")
    clock = [0.0]
    a = AuditLog(path, allow_rps=2.0, clock=lambda: clock[0])
    for _ in range(10):
        a.decision(allow=True, verb="list", subject="alice",
                   rule="namespace-list-watch")
    for _ in range(5):
        a.decision(allow=False, verb="get", subject="bob",
                   rule="namespace-get", reason="check denied")
    a.close()
    lines = [json.loads(ln) for ln in open(path)]
    allows = [r for r in lines if r["decision"] == "allow"]
    denies = [r for r in lines if r["decision"] == "deny"]
    assert len(allows) == 2  # burst = allow_rps, clock frozen
    assert len(denies) == 5  # never capped
    assert denies[0]["rule"] == "namespace-get"
    # budget refills with time
    clock[0] += 1.0
    a2 = AuditLog(path, allow_rps=2.0, clock=lambda: clock[0])
    a2.decision(allow=True, verb="list", subject="alice")
    a2.close()
    assert sum(1 for ln in open(path)
               if json.loads(ln)["decision"] == "allow") == 3


# -- e2e: live proxy + tcp engine host ----------------------------------------


def _free_client(handle, user):
    from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient

    return InMemoryClient(handle, user=user)


def test_trace_end_to_end_proxy_tcp_engine(tmp_path):
    """THE acceptance pin: one request through a live proxy + tcp engine
    host yields ONE trace containing proxy-side spans (admission wait,
    engine rpc, upstream) AND engine-host spans (queue wait, device
    dispatch) stitched via the wire frame field; denies always appear in
    the audit log with the matched rule and trace_id."""
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.admission import AdmissionController
    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.remote import EngineServer
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    audit_path = str(tmp_path / "audit.jsonl")

    async def go():
        e = Engine()
        srv = EngineServer(
            e, admission=AdmissionController(
                dependency="engine-admission"))
        port = await srv.start()
        cfg = Options(
            engine_endpoint=f"tcp://127.0.0.1:{port}",
            engine_insecure=True,
            rule_content=RULES,
            upstream=FakeKube(),
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            admission=True,
            trace_sample=1.0,
            enable_debug_traces=True,
            audit_log=audit_path,
        ).complete()
        await cfg.workflow.resume_pending()
        alice = _free_client(cfg.server.handle, "alice")
        bob = _free_client(cfg.server.handle, "bob")

        resp = await alice.post("/api/v1/namespaces",
                                {"metadata": {"name": "team-a"}})
        assert resp.status == 201, resp.body
        resp = await alice.get("/api/v1/namespaces/team-a")
        assert resp.status == 200
        allow_trace = resp.headers["X-Trace-Id"]
        resp = await bob.get("/api/v1/namespaces/team-a")
        assert resp.status == 403
        deny_trace = resp.headers["X-Trace-Id"]

        # /debug/traces serves the ring; find the allowed get's trace
        resp = await alice.get("/debug/traces")
        assert resp.status == 200
        traces = {t["trace_id"]: t
                  for t in json.loads(resp.body)["traces"]}
        t = traces[allow_trace]
        names = {s["name"] for s in t["spans"]}
        # proxy-side stages
        assert {"request", "rule_match", "admission_wait", "cache_probe",
                "engine_dispatch", "engine_rpc", "upstream"} <= names, \
            names
        # engine-host-side stages, stitched into the SAME trace via the
        # wire frame field
        assert {"engine_host.check_bulk", "engine_queue_wait",
                "engine_device"} <= names, names
        # admission-wait, device-dispatch, and upstream individually
        # timed (finished spans with a recorded duration)
        by_name = {s["name"]: s for s in t["spans"]}
        for stage in ("admission_wait", "engine_device", "upstream"):
            assert by_name[stage]["duration_us"] >= 0
        # the engine-host span names the endpoint it served on
        assert by_name["engine_host.check_bulk"]["attrs"][
            "endpoint"].endswith(str(port))
        # deny trace was kept too (tail sampling at 1.0 keeps all)
        assert deny_trace in traces

        await cfg.workflow.shutdown()
        cfg.engine.close()
        await srv.stop()

        # audit: the deny line carries the matched rule and trace_id
        # (writes drain through the audit writer thread: flush first)
        cfg.deps.audit.flush()
        lines = [json.loads(ln) for ln in open(audit_path)]
        denies = [r for r in lines if r["decision"] == "deny"]
        assert denies, lines
        d = denies[-1]
        assert d["subject"] == "bob"
        assert d["rule"] == "namespace-get"
        assert d["trace_id"] == deny_trace
        assert d["verb"] == "get" and d["name"] == "team-a"
        # per-stage micros recorded up to the decision point
        assert "engine_dispatch" in d["stages_us"] \
            or "cache_probe" in d["stages_us"]
        allows = [r for r in lines if r["decision"] == "allow"]
        assert any(r["trace_id"] == allow_trace for r in allows)

    asyncio.run(go())


def test_admission_shed_503_carries_trace_id_and_shed_flag(tmp_path):
    """Failure path 1: an admission shed's 503 carries the trace id and
    the trace is flagged shed (always kept by tail sampling)."""
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.admission import AdmissionRejected
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    class AlwaysShed:
        async def acquire_async(self, tenant, cls):
            raise AdmissionRejected(cls.name, "queue full",
                                    retry_after=2.0)

        def status(self):
            return {"limit": 0, "inflight": 0, "queued": 0,
                    "shed_total": 1}

    async def go():
        cfg = Options(
            rule_content=RULES, upstream=FakeKube(), bind_port=0,
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            trace_sample=1.0,
        ).complete()
        cfg.deps.admission = AlwaysShed()
        tracer.configure(_rand=lambda: 0.99)  # only flags keep traces
        tracer.configure(sample=0.0001)
        alice = _free_client(cfg.server.handle, "alice")
        resp = await alice.get("/api/v1/namespaces")
        assert resp.status == 503
        assert resp.headers["Retry-After"] == "2"
        trace_id = resp.headers["X-Trace-Id"]
        kept = {t["trace_id"]: t for t in tracer.recent()}
        assert trace_id in kept, "shed trace must survive tail sampling"
        assert kept[trace_id]["flags"].get("shed") is True
        # a shed is the admission design WORKING: it must not pollute an
        # operator's error-trace filter
        assert not kept[trace_id]["flags"].get("error")
        await cfg.workflow.shutdown()

    asyncio.run(go())


def test_breaker_open_fail_closed_trace_kept_with_error(tmp_path):
    """Failure path 2: breaker-open fail-closed 503s keep their trace
    (error-flagged) and carry the trace id to the client."""
    import socket

    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead = s.getsockname()[1]  # bound-then-closed: nothing listens

    async def go():
        cfg = Options(
            engine_endpoint=f"tcp://127.0.0.1:{dead}",
            engine_insecure=True,
            rule_content=RULES, upstream=FakeKube(), bind_port=0,
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            engine_retries=0, engine_connect_timeout=0.5,
            breaker_failure_threshold=1, breaker_reset_seconds=60.0,
            trace_sample=1.0,
        ).complete()
        tracer.configure(_rand=lambda: 0.99)
        tracer.configure(sample=0.0001)
        alice = _free_client(cfg.server.handle, "alice")
        resp = await alice.get("/api/v1/namespaces")  # trips the breaker
        assert resp.status >= 500
        resp = await alice.get("/api/v1/namespaces")  # breaker-open 503
        assert resp.status == 503
        trace_id = resp.headers["X-Trace-Id"]
        kept = {t["trace_id"]: t for t in tracer.recent()}
        assert trace_id in kept
        assert kept[trace_id]["flags"].get("error")
        await cfg.workflow.shutdown()

    asyncio.run(go())


def test_cross_process_fragments_recorded_and_fetchable_via_wire():
    """An engine host in ANOTHER process records satellite fragments
    under the proxy's trace_id; the wire `traces` op serves its ring so
    the proxy's /debug/traces can stitch them back in."""
    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )

    # adopt a traceparent whose trace is NOT live in this process — the
    # cross-process shape — and record a span under it
    tp = format_traceparent("a1" * 16, "b2" * 8)
    with tracer.adopt(tp, "engine_host.check_bulk", endpoint="x") as sp:
        assert sp.trace_id == "a1" * 16
    frags = [t for t in tracer.recent() if t["external"]]
    assert frags and frags[0]["trace_id"] == "a1" * 16
    # the fragment's root hangs off the proxy's wire-carried span id
    root = frags[0]["spans"][0]
    assert root["parent_id"] == "b2" * 8

    async def go():
        e = Engine()
        srv = EngineServer(e)
        port = await srv.start()
        r = RemoteEngine("127.0.0.1", port)
        got = await asyncio.to_thread(r.fetch_traces, 64)
        assert any(t["trace_id"] == "a1" * 16 and t["external"]
                   for t in got)
        r.close()
        await srv.stop()

    asyncio.run(go())


def test_failover_reaim_spans_two_endpoints_one_trace():
    """Failure path 3: a failover re-aim is ONE logical request whose
    spans cover BOTH engine endpoints under a single trace_id."""
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        FailoverEngine,
    )

    async def go():
        e = Engine()
        follower = EngineServer(
            e, failover_status=lambda: {"role": "follower", "term": 2,
                                        "revision": 0, "peer_id": 0,
                                        "lag": 0})
        leader = EngineServer(
            e, failover_status=lambda: {"role": "leader", "term": 2,
                                        "revision": 0, "peer_id": 1,
                                        "lag": 0})
        p1, p2 = await follower.start(), await leader.start()
        fe = FailoverEngine([("127.0.0.1", p1), ("127.0.0.1", p2)],
                            retries=0)
        with tracer.start("request") as root:
            out = await asyncio.to_thread(
                fe.check_bulk,
                [CheckItem("namespace", "dev", "view", "user", "alice")])
            assert out == [False]
            trace_id = root.trace_id
        kept = {t["trace_id"]: t for t in tracer.recent()}
        t = kept[trace_id]
        endpoints = {s["attrs"].get("endpoint") for s in t["spans"]
                     if s["name"] == "engine_rpc"}
        # the not_leader rejection on p1 and the re-aimed call on p2 are
        # spans of the SAME trace
        assert f"engine:127.0.0.1:{p1}" in endpoints, (endpoints, p1)
        assert f"engine:127.0.0.1:{p2}" in endpoints, (endpoints, p2)
        fe.close()
        await follower.stop()
        await leader.stop()

    asyncio.run(go())


# -- tracing-off invariants ---------------------------------------------------


def test_tracing_disabled_serves_with_no_spans_and_404_debug(tmp_path):
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    async def go():
        cfg = Options(
            rule_content=RULES, upstream=FakeKube(), bind_port=0,
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            trace_sample=0.0, enable_debug_traces=True,
        ).complete()
        alice = _free_client(cfg.server.handle, "alice")
        resp = await alice.get("/api/v1/namespaces")
        assert resp.status == 200
        assert "X-Trace-Id" not in resp.headers
        assert tracer.recent() == []
        resp = await alice.get("/debug/traces")
        assert resp.status == 404  # sampling off -> no ring to serve
        await cfg.workflow.shutdown()

    asyncio.run(go())


def test_debug_traces_flag_gated_and_infra_paths_untraced(tmp_path):
    """/debug/traces is 404 without --enable-debug-traces (the
    /debug/config posture), and health/scrape endpoints never record
    traces — probe cadence must not cycle real requests out of the
    ring."""
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    async def go():
        cfg = Options(
            rule_content=RULES, upstream=FakeKube(), bind_port=0,
            workflow_database_path=str(tmp_path / "dtx.sqlite"),
            trace_sample=1.0,  # keep everything that IS traced
        ).complete()
        alice = _free_client(cfg.server.handle, "alice")
        assert (await alice.get("/debug/traces")).status == 404
        for _ in range(5):
            assert (await alice.get("/readyz")).status == 200
            assert (await alice.get("/livez")).status == 200
            assert (await alice.get("/metrics")).status == 200
        assert tracer.recent() == [], "infra endpoints must not trace"
        resp = await alice.get("/api/v1/namespaces")
        assert resp.status == 200 and "X-Trace-Id" in resp.headers
        assert len(tracer.recent()) == 1
        await cfg.workflow.shutdown()

    asyncio.run(go())


@pytest.fixture
def every_stage_clocked(monkeypatch):
    """One ``cpu=`` stage in ``CPU_EVERY`` reads the CPU clock; a test
    that looks at one stage has every one read."""
    from spicedb_kubeapi_proxy_tpu.obs import trace

    monkeypatch.setattr(trace, "CPU_EVERY", 1)


@pytest.mark.parametrize("with_cpu", [False, True], ids=["wall", "cpu"])
def test_trace_overhead_disabled_is_negligible(with_cpu,
                                               every_stage_clocked):
    """The no-regression guard in unit form: with sample=0 the span hooks
    must cost nanoseconds, not microseconds (the bench-level pin is the
    check-throughput phase staying within noise); a stage that reads the
    thread's CPU clock too, every time, stays under the same bound."""
    tracer.configure(sample=0.0)
    cpu = (metrics.counter("test_overhead_cpu_seconds_total")
           if with_cpu else None)
    t0 = time.perf_counter()
    n = 20_000
    for _ in range(n):
        with tracer.stage("x", None, cpu):
            pass
        tracer.stage("y", None, cpu).finish()
    per_call = (time.perf_counter() - t0) / (2 * n)
    # generous bound: even a slow CI box does a no-op contextvar check in
    # well under 20us
    assert per_call < 20e-6, f"{per_call * 1e6:.2f}us per disabled hook"


# -- stages: span + histogram + profiler annotation (ISSUE 26) ----------------


def _hist_count(name, **labels):
    s = metrics.hist_snapshot(name, **labels)
    return 0 if s is None else s["n"]


def _profiled(tmp_path, body):
    """Run ``body()`` under a CPU ``jax.profiler`` session -> the host
    plane's ``sdbkp:`` events as {name: [duration_ns]}."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("sdbkp:"):
                        out.setdefault(e.name, []).append(e.duration_ns)
    return out


def test_stage_across_an_await_is_span_histogram_and_annotation(tmp_path):
    """Two stages interleave on one event loop: each is written whole,
    with its own length, three ways at once."""
    n0 = _hist_count("test_stage_await_seconds")

    async def one(name, seconds):
        with tracer.stage(
                name, metrics.histogram("test_stage_await_seconds")):
            await asyncio.sleep(seconds)

    async def both():
        with tracer.start("request"):
            await asyncio.gather(one("long_stage", 0.06),
                                 one("short_stage", 0.02))

    events = _profiled(tmp_path, lambda: asyncio.run(both()))
    (long_ns,), (short_ns,) = (events["sdbkp:long_stage"],
                               events["sdbkp:short_stage"])
    assert long_ns >= 0.06e9 > short_ns >= 0.02e9
    assert len(events["sdbkp:request"]) == 1  # the root carries one too
    assert _hist_count("test_stage_await_seconds") == n0 + 2
    (t,) = tracer.recent()
    spans = {s["name"]: s for s in t["spans"]}
    assert spans["long_stage"]["duration_us"] >= 60_000
    assert 20_000 <= spans["short_stage"]["duration_us"] < 60_000


def test_stage_finished_on_another_thread(tmp_path):
    """A stage begun here and finished by a worker thread (the shape of
    ``executor_wait``): one span, one observation, one annotation."""
    import threading

    n0 = _hist_count("test_stage_thread_seconds")

    def body():
        with tracer.start("request"):
            st = tracer.stage("handed_over", metrics.histogram(
                "test_stage_thread_seconds"))
            t = threading.Thread(target=st.finish)
            t.start()
            t.join()
            st.finish()  # a second finish is ignored

    events = _profiled(tmp_path, body)
    assert len(events["sdbkp:handed_over"]) == 1
    assert _hist_count("test_stage_thread_seconds") == n0 + 1
    (t,) = tracer.recent()
    assert [s["name"] for s in t["spans"]].count("handed_over") == 1


def test_stage_with_tracing_off_still_feeds_its_histogram():
    tracer.configure(sample=0.0)
    n0 = _hist_count("test_stage_off_seconds")
    with tracer.start("request"):
        with tracer.stage("unsampled", metrics.histogram(
                "test_stage_off_seconds")) as st:
            assert st.traceparent() is None
            st.set("ignored", 1)
    assert _hist_count("test_stage_off_seconds") == n0 + 1
    assert tracer.recent() == []


# -- the interpreter lock: CPU beside the wall clock (ISSUE 37) ---------------


def _burn(cpu_seconds):
    """Compute until this thread's own CPU clock has advanced."""
    c0 = time.thread_time()
    while time.thread_time() - c0 < cpu_seconds:
        sum(range(1000))


def _staged(body):
    """Run ``body`` inside one ``cpu=`` stage -> (wall, cpu) seconds it
    added to its histogram and its counter."""
    hist = metrics.histogram("test_stage_cpu_seconds")
    cpu = metrics.counter("test_stage_cpu_seconds_total")
    wall0, cpu0 = hist.total, cpu.value
    with tracer.stage("clocked", hist, cpu):
        body()
    return hist.total - wall0, cpu.value - cpu0


def test_a_cpu_stage_around_a_busy_loop_adds_what_the_loop_burned(
        every_stage_clocked):
    """At least what the loop read off the same clock, and never more
    than the wall time (the CPU clock is read inside the wall clock's two
    readings). How far under the wall time it stays is the machine's and
    the other threads' business — what the counter is there to show —
    and no test's."""
    wall, cpu = _staged(lambda: _burn(0.02))
    assert 0.02 <= cpu <= wall


def test_a_cpu_stage_around_a_sleep_adds_next_to_nothing(
        every_stage_clocked):
    wall, cpu = _staged(lambda: time.sleep(0.05))
    assert wall >= 0.05 and 0.0 <= cpu < 0.005


def test_a_stage_without_cpu_reads_no_thread_clock(monkeypatch,
                                                   every_stage_clocked):
    """``cpu`` is per call site: a stage that crosses an ``await`` or
    ends on another thread gets none, touches no counter and pays for
    no clock but the wall's."""
    reads = []
    thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: reads.append(1) or thread_time())
    hist = metrics.histogram("test_stage_nocpu_seconds")
    counters = set(metrics._counters)
    with tracer.start("request"):
        with tracer.stage("unclocked", hist):
            pass
        tracer.stage("unclocked_leaf", hist).finish()
    assert reads == [] and set(metrics._counters) == counters
    with tracer.stage("clocked", hist, metrics.counter(
            "test_stage_cpu_seconds_total")):
        pass
    assert len(reads) == 2  # where the wall clock is read: both ends


def test_the_span_of_a_cpu_stage_carries_cpu_us(every_stage_clocked):
    cpu = metrics.counter("test_stage_cpu_seconds_total")
    with tracer.start("request"):
        with tracer.stage("clocked", None, cpu):
            _burn(0.002)
        with tracer.stage("unclocked"):
            pass
    (t,) = tracer.recent()
    spans = {s["name"]: s for s in t["spans"]}
    assert 2000 <= spans["clocked"]["attrs"]["cpu_us"] \
        <= spans["clocked"]["duration_us"]
    assert "cpu_us" not in spans["unclocked"]["attrs"]


def test_one_cpu_stage_in_cpu_every_is_read_and_counts_for_them_all(
        monkeypatch):
    """The CPU clock is a system call: of ``CPU_EVERY`` stages one, drawn
    by the tracer's own dice, reads it at both ends and adds
    ``CPU_EVERY`` times what it read; the others read no clock, add
    nothing and carry no ``cpu_us``. Equal stages sum to their CPU."""
    from spicedb_kubeapi_proxy_tpu.obs import trace

    monkeypatch.setattr(trace, "CPU_EVERY", 4)
    # the first of every four; the last throw is the tail sampler's
    dice = iter(([0.1, 0.3, 0.6, 0.9] * 3 + [0.0]) * 2)
    tracer.configure(_rand=lambda: next(dice))
    reads = []
    thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: reads.append(1) or thread_time())
    cpu = metrics.counter("test_stage_sampled_cpu_seconds_total")
    c0 = cpu.value
    with tracer.start("request"):
        for _ in range(12):
            with tracer.stage("clocked", None, cpu):
                time.sleep(0.001)  # reads no clock of its own
    assert len(reads) == 2 * 3
    with tracer.start("request"):
        for _ in range(12):
            with tracer.stage("clocked", None, cpu):
                _burn(0.002)
    (_, t) = sorted(tracer.recent(), key=lambda t: t["start"])
    clocked = [s for s in t["spans"] if s["name"] == "clocked"]
    assert len(clocked) == 12
    assert [("cpu_us" in s["attrs"]) for s in clocked] \
        == [True, False, False, False] * 3
    assert all(s["attrs"]["cpu_us"] >= 2000 for s in clocked[::4])
    # three read, each standing for four: what twelve such stages burned
    assert 12 * 0.002 <= cpu.value - c0 <= 12 * 0.002 * 3


def test_a_stage_timed_by_hand_asks_for_its_weight():
    from spicedb_kubeapi_proxy_tpu.obs import trace

    tracer.configure(_rand=lambda: 0.99)
    assert tracer.cpu_weight() == 0
    tracer.configure(_rand=lambda: 0.0)
    assert tracer.cpu_weight() == trace.CPU_EVERY == 16


def _rendered(registry) -> dict:
    return {line.split(" ")[0]: float(line.split(" ")[1])
            for line in registry.render().splitlines()
            if line.startswith("process_")}


LEDGER = ("process_cpu_seconds_total", "process_loop_cpu_seconds_total",
          "process_worker_cpu_seconds_total")


def test_a_render_refreshes_the_cpu_ledger_and_it_never_runs_backwards():
    """Three counters set from the kernel's clocks when the registry is
    rendered, and only then: what the loop's thread and the pool's
    threads burned shows at the next render, the parts never exceed the
    whole, and a worker that has ended keeps its last reading."""
    from concurrent.futures import ThreadPoolExecutor

    from spicedb_kubeapi_proxy_tpu.obs.profile import CpuLedger

    reg = Registry()
    ledger = CpuLedger(reg)
    seen = []

    async def serve():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=2))
        ledger.serve_from(loop)
        ledger.serve_from(loop)  # a second start registers nothing more
        seen.append(_rendered(reg))
        _burn(0.02)  # on the loop's thread
        await asyncio.gather(asyncio.to_thread(_burn, 0.03),
                             asyncio.to_thread(_burn, 0.03))
        unrendered = {n: reg.counter(n).value for n in LEDGER}
        assert unrendered == seen[0]  # nothing moves between renders
        seen.append(_rendered(reg))

    assert reg.render() == "\n"  # nothing registered, nothing rendered
    asyncio.run(serve())
    assert len(reg._refreshers) == 1
    seen.append(_rendered(reg))  # the loop closed, its pool's threads gone
    _burn(0.01)
    seen.append(_rendered(reg))
    first, burned, ended, last = seen
    assert set(first) == set(LEDGER)
    assert burned["process_loop_cpu_seconds_total"] \
        >= first["process_loop_cpu_seconds_total"] + 0.02
    assert burned["process_worker_cpu_seconds_total"] \
        >= first["process_worker_cpu_seconds_total"] + 0.06
    for earlier, later in zip(seen, seen[1:]):
        assert all(later[n] >= earlier[n] for n in LEDGER)
    for r in seen:
        assert r["process_loop_cpu_seconds_total"] \
            + r["process_worker_cpu_seconds_total"] \
            <= r["process_cpu_seconds_total"]
    assert ended["process_worker_cpu_seconds_total"] \
        == burned["process_worker_cpu_seconds_total"]
    assert not ledger._worker_clock._last  # its readings folded, not kept
    assert last["process_cpu_seconds_total"] \
        >= ended["process_cpu_seconds_total"] + 0.01


def test_a_threads_cpu_clock_is_asked_by_its_kernel_id():
    """The id the ledger builds from ``native_id`` is the one
    ``pthread_getcpuclockid`` computes from the ``pthread_t``; for a
    thread that has gone the kernel refuses it, where a stale
    ``pthread_t`` would be undefined behaviour."""
    import threading

    me = threading.current_thread()
    assert (~me.native_id << 3) | 6 == time.pthread_getcpuclockid(me.ident)
    t = threading.Thread(target=lambda: None)
    t.start()
    t.join(10)
    assert not t.is_alive()
    deadline = time.monotonic() + 10  # the kernel's thread ends a little
    with pytest.raises(OSError):      # after the interpreter's
        while time.monotonic() < deadline:
            time.clock_gettime((~t.native_id << 3) | 6)


def test_a_serving_process_renders_the_cpu_ledger():
    asyncio.run(_start_and_stop())
    r = _rendered(metrics)
    assert set(LEDGER) <= set(r)
    assert 0 < r["process_loop_cpu_seconds_total"] \
        <= r["process_cpu_seconds_total"]


@pytest.mark.parametrize("sleeps", [True, False],
                         ids=["late", "early"])
def test_a_collectors_poll_is_one_observation_of_the_lock_wait(
        monkeypatch, sleeps):
    """How late the collector's sleep returns, once a poll; a sleep that
    came back early (a clock's step) observes 0, never less."""
    from spicedb_kubeapi_proxy_tpu.obs import profile

    for thread in _collector_threads():  # an earlier test's, on its way out
        thread.join(10)
    monkeypatch.setattr(profile, "GC_POLL_S", 0.002)
    if not sleeps:
        monkeypatch.setattr(time, "sleep", lambda s: None)
    profile._sleep_a_poll()  # the histogram exists
    before = metrics.hist_snapshot("process_lock_wait_seconds")
    profile._sleep_a_poll()
    after = metrics.hist_snapshot("process_lock_wait_seconds")
    assert after["n"] == before["n"] + 1
    late = after["total"] - before["total"]
    assert 0.0 <= late < 10.0
    if not sleeps:
        assert late == 0.0


def test_the_collectors_thread_probes_the_lock_and_writes_no_annotation(
        unserved_process, tmp_path):
    """Twenty observations a second from the thread that sleeps anyway,
    straight into the histogram: no ``sdbkp:lock_wait`` enters the
    profiler's trace, where it would count as a working stage."""
    n0 = _hist_count("process_lock_wait_seconds")

    def body():
        asyncio.run(_start_and_stop())
        deadline = time.monotonic() + 10
        while _hist_count("process_lock_wait_seconds") < n0 + 3 \
                and time.monotonic() < deadline:
            time.sleep(0.01)

    events = _profiled(tmp_path, body)
    assert _hist_count("process_lock_wait_seconds") >= n0 + 3
    assert not [name for name in events if "lock" in name]


def test_gc_hook_adds_the_collections_own_cpu():
    from spicedb_kubeapi_proxy_tpu.obs.profile import install_gc_hook

    install_gc_hook()
    cpu = metrics.counter("process_gc_cpu_seconds_total")
    wall = metrics.hist_snapshot("process_gc_seconds", generation=2)
    c0, w0 = cpu.value, wall["total"] if wall else 0.0
    gc.collect()
    wall = metrics.hist_snapshot("process_gc_seconds", generation=2)
    assert 0.0 < cpu.value - c0 <= wall["total"] - w0


def test_executor_wait_grows_when_the_pool_is_saturated():
    """``tracer.to_thread`` measures submit -> first line in the worker:
    with every worker of a one-thread pool busy, the wait is the busy
    time; with the pool free it is a thread hand-off. The way back is
    ``loop_wait``: it grows while the loop is kept from the coroutine."""
    from concurrent.futures import ThreadPoolExecutor

    def waits_since(before):
        s = metrics.hist_snapshot("proxy_executor_wait_seconds")
        return s["total"] - before["total"], s["n"] - before["n"]

    async def go():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=1))
        await tracer.to_thread(time.sleep, 0)  # the histogram exists
        before = metrics.hist_snapshot("proxy_executor_wait_seconds")
        await tracer.to_thread(time.sleep, 0)
        free, n = waits_since(before)
        assert n == 1 and free < 0.1
        before = metrics.hist_snapshot("proxy_executor_wait_seconds")
        busy = loop.run_in_executor(None, time.sleep, 0.3)
        assert await tracer.to_thread(lambda: "ran") == "ran"
        await busy
        saturated, n = waits_since(before)
        assert n == 1 and saturated >= 0.25 > free
        back = metrics.hist_snapshot("proxy_loop_wait_seconds")
        # the worker is done at once; the loop is held for 0.2 s more
        loop.call_later(0.02, time.sleep, 0.2)
        await tracer.to_thread(time.sleep, 0.05)
        held = metrics.hist_snapshot("proxy_loop_wait_seconds")
        assert held["n"] == back["n"] + 1
        assert held["total"] - back["total"] >= 0.1

    asyncio.run(go())


async def _spawn_cancelled_before_its_first_step():
    task = tracer.spawn(lambda: asyncio.sleep(0))
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task.task
    return 1  # the spawn's crossing, closed by cancel()


async def _spawn_waited_for_while_it_runs():
    task = tracer.spawn(lambda: asyncio.sleep(0.02))
    await task.wait(1.0)
    return 2  # to its first step, and back to the waiter


async def _spawn_done_before_anyone_waits():
    task = tracer.spawn(lambda: asyncio.sleep(0))
    await asyncio.sleep(0.02)
    await task.wait(1.0)
    return 1  # the way back was nobody's wait


async def _to_thread_cancelled_while_the_worker_runs():
    import threading

    release = threading.Event()
    waiter = asyncio.ensure_future(tracer.to_thread(release.wait, 5))
    await asyncio.sleep(0.02)
    waiter.cancel()
    with pytest.raises(asyncio.CancelledError):
        await waiter
    release.set()
    await asyncio.sleep(0.05)  # the worker's last line has run
    return 0  # nobody is left to hand over to


@pytest.mark.parametrize("case", [
    _spawn_cancelled_before_its_first_step,
    _spawn_waited_for_while_it_runs,
    _spawn_done_before_anyone_waits,
    _to_thread_cancelled_while_the_worker_runs])
def test_loop_wait_crossings_close_whatever_is_cancelled(case, monkeypatch):
    """Every ``loop_wait`` stage that opens is finished (annotation
    exited, histogram fed), and none opens for a waiter that is gone."""
    opened = []
    stage = tracer.stage

    def recording(name, *a, **kw):
        opened.append((name, stage(name, *a, **kw)))
        return opened[-1][1]

    monkeypatch.setattr(tracer, "stage", recording)
    n0 = _hist_count("proxy_loop_wait_seconds")
    expected = asyncio.run(case())
    assert [st._t0 for _, st in opened] == [None] * len(opened)
    assert [n for n, _ in opened].count("loop_wait") == expected
    assert _hist_count("proxy_loop_wait_seconds") == n0 + expected


LIST_STAGES = {"rule_match", "cache_probe", "prefilter", "executor_wait",
               "engine_encode", "engine_enqueue", "device_wait",
               "mask_to_ids", "prefilter_map", "loop_wait", "upstream",
               "body_filter"}
GET_STAGES = {"rule_match", "cache_probe", "engine_dispatch",
              "executor_wait", "engine_encode", "engine_enqueue",
              "device_wait", "loop_wait", "upstream"}
STAGE_HISTOGRAMS = (
    "proxy_executor_wait_seconds", "proxy_loop_wait_seconds",
    "engine_encode_seconds",
    "engine_enqueue_seconds", "engine_device_wait_seconds",
    "engine_mask_to_ids_seconds", "proxy_prefilter_map_seconds",
    "proxy_body_filter_seconds", "proxy_upstream_seconds",
    "proxy_response_write_seconds")


async def _serve_and_get(tmp_path, trace_sample, paths):
    """A tiny served deployment over real TCP (so the response write is
    there), its upstream 20 ms away as a near apiserver is. -> status
    per path, after a namespace was created through the proxy."""
    from fake_kube import FakeKube
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    kube = FakeKube()

    async def upstream(req):
        await asyncio.sleep(0.02)
        return await kube(req)

    cfg = Options(
        rule_content=RULES, upstream=upstream, bind_host="127.0.0.1",
        bind_port=0, workflow_database_path=str(tmp_path / "dtx.sqlite"),
        trace_sample=trace_sample,
    ).complete()
    await cfg.run()

    async def http(method, path, body=b""):
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", cfg.server.port)
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                      "X-Remote-User: alice\r\nConnection: close\r\n"
                      "Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        raw = await reader.read()
        writer.close()
        return int(raw.split(b" ", 2)[1])

    try:
        assert await http("POST", "/api/v1/namespaces", json.dumps(
            {"metadata": {"name": "team-a"}}).encode()) == 201
        tracer.reset()
        return [await http("GET", p) for p in paths]
    finally:
        await cfg.server.stop()
        await cfg.workflow.shutdown()


@pytest.mark.parametrize("path,stages", [
    ("/api/v1/namespaces", LIST_STAGES),
    ("/api/v1/namespaces/team-a", GET_STAGES),
], ids=["list", "get"])
def test_served_read_carries_every_stage(tmp_path, path, stages):
    """Each step of a served read is a span of its own, so the root has
    next to no time that is nobody's: under a tenth of it."""
    assert asyncio.run(_serve_and_get(tmp_path, 1.0, [path])) == [200]
    (t,) = [t for t in tracer.recent()
            if t["spans"][-1]["attrs"].get("path") == path]
    names = {s["name"] for s in t["spans"]}
    assert stages <= names, stages - names
    assert "device" not in names  # its three parts replaced it
    root = next(s for s in t["spans"] if s["name"] == "request")
    lo = root["start"]
    covered = sorted(
        (s["start"], s["start"] + s["duration_us"] / 1e6)
        for s in t["spans"] if s["parent_id"] == root["span_id"])
    own, at = root["duration_us"] / 1e6, lo
    for a, b in covered:
        own -= max(0.0, b - max(a, at))
        at = max(at, b)
    assert own < 0.1 * root["duration_us"] / 1e6, (own, root)


def test_served_reads_feed_stage_histograms_with_tracing_off(tmp_path):
    """``trace_sample`` 0: no span anywhere, and every stage histogram
    of the two read paths still moves — they are what ``/metrics`` and
    the benchmark read, for every request."""
    before = {h: _hist_count(h) for h in STAGE_HISTOGRAMS}
    rows0 = metrics.counter("engine_dispatch_rows_total").value
    assert asyncio.run(_serve_and_get(
        tmp_path, 0.0, ["/api/v1/namespaces",
                        "/api/v1/namespaces/team-a"])) == [200, 200]
    assert tracer.recent() == []
    moved = {h: _hist_count(h) - before[h] for h in STAGE_HISTOGRAMS}
    assert all(n >= 1 for n in moved.values()), moved
    # one lookup and one check, a subject row each
    assert metrics.counter("engine_dispatch_rows_total").value - rows0 >= 2
    assert metrics.gauge("engine_residual_edges").value >= 1


# a synchronous stage's CPU counter beside its histogram (ISSUE 37)
CPU_BESIDE = {
    "engine_encode_cpu_seconds_total": "engine_encode_seconds",
    "engine_enqueue_cpu_seconds_total": "engine_enqueue_seconds",
    "engine_mask_to_ids_cpu_seconds_total": "engine_mask_to_ids_seconds",
    "engine_bulk_cache_cpu_seconds_total": "engine_bulk_cache_seconds",
    "proxy_prefilter_map_cpu_seconds_total": "proxy_prefilter_map_seconds",
    "proxy_body_filter_cpu_seconds_total": "proxy_body_filter_seconds",
}
_SERVED_CLOCKS = {}


def _served_clocks(tmp_path) -> dict:
    """{counter: (cpu, wall) seconds a served list and a served get
    added}, read once a process."""
    if not _SERVED_CLOCKS:
        def read():
            return {c: (metrics.counter(c).value,
                        (metrics.hist_snapshot(h) or {"total": 0.0})["total"])
                    for c, h in CPU_BESIDE.items()}

        before = read()
        assert asyncio.run(_serve_and_get(
            tmp_path, 0.0, ["/api/v1/namespaces",
                            "/api/v1/namespaces/team-a"])) == [200, 200]
        for c, (cpu, wall) in read().items():
            _SERVED_CLOCKS[c] = (cpu - before[c][0], wall - before[c][1])
    return _SERVED_CLOCKS


@pytest.mark.parametrize("counter", sorted(CPU_BESIDE))
def test_served_reads_add_each_stages_cpu_beside_its_wall(
        tmp_path, counter, every_stage_clocked):
    """A prefiltered list and a get pass every synchronous stage of the
    two read paths: each adds its thread's CPU seconds to a counter of
    its own, and never more than its histogram took."""
    cpu, wall = _served_clocks(tmp_path)[counter]
    assert 0.0 < cpu <= wall, (counter, cpu, wall)


def test_gc_hook_times_collections_by_generation():
    from spicedb_kubeapi_proxy_tpu.obs.profile import install_gc_hook

    install_gc_hook()
    install_gc_hook()  # once per process, however often it is asked
    n0 = _hist_count("process_gc_seconds", generation=2)
    gc.collect()
    assert _hist_count("process_gc_seconds", generation=2) == n0 + 1


def _collector_threads():
    import threading

    return [t for t in threading.enumerate() if t.name == "sdbkp-collector"]


@pytest.fixture
def unserved_process(monkeypatch):
    """The collector's policy is applied once a process, where it begins
    to serve: a test of it needs a process that has not served yet.
    tests/conftest.py puts heap and thresholds back after every test,
    which also ends the collector's thread."""
    from spicedb_kubeapi_proxy_tpu.obs import profile

    monkeypatch.setattr(profile, "_collector_settled", False)
    for thread in _collector_threads():  # an earlier test's, on its way out
        thread.join(10)
    return profile


async def _start_and_stop():
    from spicedb_kubeapi_proxy_tpu.proxy.server import Server

    server = Server(None)
    await server.start()
    await server.stop()


def test_start_freezes_the_heap_that_start_up_built(unserved_process):
    assert gc.get_freeze_count() == 0
    asyncio.run(_start_and_stop())
    # the gauge is the count at the freeze; a frozen object whose last
    # reference goes is freed like any other and leaves the count
    assert 0 < gc.get_freeze_count() \
        <= metrics.gauge("process_gc_frozen_objects").value


def test_a_second_start_freezes_nothing_more(unserved_process):
    """What is alive at a later ``start`` are requests in flight: they
    stay ordinary objects, with their garbage."""
    asyncio.run(_start_and_stop())
    frozen = metrics.gauge("process_gc_frozen_objects").value
    in_flight = [[] for _ in range(1000)]
    asyncio.run(_start_and_stop())
    assert gc.get_freeze_count() <= frozen
    assert metrics.gauge("process_gc_frozen_objects").value == frozen
    ordinary = {id(o) for o in gc.get_objects()}  # lists no frozen object
    assert all(id(x) in ordinary for x in in_flight)


def test_start_hands_the_collecting_to_one_thread(unserved_process):
    """The interpreter's own thresholds become the backstop and one
    thread collects, however often ``start`` is asked; it ends when the
    thresholds are set back (as tests/conftest.py does after this)."""
    before = gc.get_threshold()
    assert before != unserved_process.GC_BACKSTOP
    assert _collector_threads() == []
    asyncio.run(_start_and_stop())
    asyncio.run(_start_and_stop())
    assert gc.get_threshold() == unserved_process.GC_BACKSTOP
    (thread,) = _collector_threads()
    gc.set_threshold(*before)
    thread.join(10)
    assert not thread.is_alive()


def test_garbage_is_collected_on_the_collectors_thread(unserved_process):
    import threading

    class Node:
        pass

    collected_on = set()

    def note(phase, info):
        if phase == "stop":
            collected_on.add(threading.current_thread().name)

    asyncio.run(_start_and_stop())
    gc.callbacks.append(note)
    try:
        gone = None
        for _ in range(unserved_process.GC_YOUNG_AFTER + 1):
            a, b = Node(), Node()
            a.other, b.other = b, a
            gone = gone or weakref.ref(a)
        del a, b
        deadline = time.monotonic() + 10
        while gone() is not None and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        gc.callbacks.remove(note)
    assert gone() is None
    assert collected_on == {"sdbkp-collector"}


def test_a_cycle_made_after_the_freeze_is_collected(unserved_process):
    class Node:
        pass

    asyncio.run(_start_and_stop())
    a, b = Node(), Node()
    a.other, b.other = b, a
    gone = weakref.ref(a)
    del a, b
    assert gc.collect() >= 2
    assert gone() is None


def test_dispatch_batch_rows_counts_subject_rows_not_slots():
    """``engine_dispatch_batch_rows`` is rows per device dispatch: a
    lookup over many objects is one subject row, a bulk check of two
    subjects two."""
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship

    e = Engine()
    e.write_relationships([WriteOp("touch", Relationship(
        "namespace", f"ns{i}", "viewer", "user", "alice"))
        for i in range(40)])

    def moved(fn):
        h = metrics.hist_snapshot("engine_dispatch_batch_rows") \
            or {"n": 0, "total": 0.0}
        c = metrics.counter("engine_dispatch_rows_total").value
        fn()
        h2 = metrics.hist_snapshot("engine_dispatch_batch_rows")
        return (h2["n"] - h["n"], h2["total"] - h["total"],
                metrics.counter("engine_dispatch_rows_total").value - c)

    assert len(e.lookup_resources("namespace", "view", "user",
                                  "alice")) == 40
    assert moved(lambda: e.lookup_resources(
        "namespace", "view", "user", "bob")) == (1, 1.0, 1.0)
    assert moved(lambda: e.check_bulk(
        [CheckItem("namespace", "ns1", "view", "user", "alice"),
         CheckItem("namespace", "ns2", "view", "user", "alice"),
         CheckItem("namespace", "ns1", "view", "user", "bob")])) \
        == (1, 2.0, 2.0)


def test_compile_hook_counts_backend_compiles_and_cache_hits_apart():
    from spicedb_kubeapi_proxy_tpu.obs import profile

    c0 = metrics.counter("jax_backend_compiles_total").value
    h0 = metrics.counter("jax_compile_cache_hits_total").value
    s0 = _hist_count("jax_compile_seconds")
    profile._on_event_duration("/jax/core/compile/jaxpr_trace_duration", 1.0)
    profile._on_event_duration(
        "/jax/core/compile/backend_compile_duration", 0.5)
    profile._on_event("/jax/compilation_cache/cache_hits")
    profile._on_event("/jax/compilation_cache/cache_misses")
    assert metrics.counter("jax_backend_compiles_total").value == c0 + 1
    assert metrics.counter("jax_compile_cache_hits_total").value == h0 + 1
    assert _hist_count("jax_compile_seconds") == s0 + 1


def test_gc_hook_waits_for_no_lock_the_collecting_thread_holds():
    """A collection starts at whatever allocation fills the collector's
    count: also one made while this thread renders a scrape under the
    registry's lock, or copies the collector's own histogram under that
    histogram's lock. The callback must come back from both."""
    import gc
    import threading

    from spicedb_kubeapi_proxy_tpu.obs.profile import install_gc_hook

    install_gc_hook()
    n0 = _hist_count("process_gc_seconds", generation=2)
    done = []

    def body():
        with metrics._lock:
            gc.collect()
        with metrics.histogram("process_gc_seconds", generation=2)._lock:
            gc.collect()
        done.append(True)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(10)
    assert done, "the collector's callback deadlocked on a held lock"
    assert _hist_count("process_gc_seconds", generation=2) == n0 + 2

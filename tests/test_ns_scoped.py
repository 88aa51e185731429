"""Namespace-scoped RBAC through the postfilter: the benchmark's
``ns-scoped-10k`` deployment at its rehearsal size, built by its own
``generate.py`` through ``benchmark/deployment.py`` and served with
default flags behind the benchmark's stand-in upstream. No tuple names a
service: the rule checks every object of a cluster-wide list against the
object's own namespace, all in one bulk check. The served answer names
what the plain reference (``benchmark/reference.py`` and the by-namespace
step of ``benchmark/ops/list_scoped.py``) and the oracle
(``engine/evaluator.py``) name, for a JSON List, for a Table, and when
the client asks for protobuf; an object without a namespace fails the
whole list closed; a bulk check of three chunks answers item by item what
one chunk answers; the stale control is told from the reference. The
path's stages and counters are asserted as counts, never as timings.
"""

import asyncio
import importlib.util
import json
import os

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
from spicedb_kubeapi_proxy_tpu.obs.trace import tracer
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = "ns-scoped-10k"
SEEDS = (3500000011, 4100000023)
LISTS = 20  # users a case lists as
STAGES = {"postfilter": "proxy_postfilter_seconds",
          "postfilter_parse": "proxy_postfilter_parse_seconds",
          "postfilter_resolve": "proxy_postfilter_resolve_seconds",
          "postfilter_write": "proxy_postfilter_write_seconds"}
COUNTERS = ("proxy_postfilter_items_total", "proxy_postfilter_kept_total",
            "proxy_postfilter_resolved_total",
            "proxy_postfilter_column_resolved_total",
            "engine_checks_total", "engine_checks_distinct_total")
PROTOBUF = "application/vnd.kubernetes.protobuf,application/json"
TABLE = "application/json;as=Table;v=v1;g=meta.k8s.io,application/json"


def _bench_module(name: str):
    """A file of benchmark/ by path: its directory stays off sys.path,
    where ``client`` or ``run`` could shadow a test's import."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("/", "_").replace("-", "_"),
        os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Scoped:
    """The deployment of one seed, its reference and its stale twin."""

    def __init__(self, seed: int):
        self.dep = _bench_module("deployment").Deployment(
            CONFIG, seed, rehearse=True)
        reference = _bench_module("reference").Reference
        self.ref = reference(self.dep)
        self.stale = reference(self.dep,
                               self.dep.config["control"]["stale_share"])
        self.expect = _bench_module("ops/list_scoped").expect
        self.users = self.dep.names("user")
        # namespaces that hold a service: a list's distinct checks
        self.held = len({n.split("/")[0]
                         for n in self.dep.names("service").tolist()})

    def request(self, user: int, typ: str = "service") -> dict:
        return {"key": "namespace#view", "user_idx": user, "type": typ,
                "scope_type": "namespace"}

    def seen(self, user: int, ref=None, typ: str = "service") -> list:
        status, names = self.expect(self.request(user, typ), self.dep,
                                    ref or self.ref)
        assert status == 200
        return names

    def by_the_oracle(self, oracle, user: int) -> list:
        held = oracle.lookup_resources("namespace", "view", "user",
                                       str(self.users[user]))
        return sorted(n for n in self.dep.names("service").tolist()
                      if n.split("/")[0] in held)


@pytest.fixture(scope="module", params=SEEDS)
def scoped(request):
    return Scoped(request.param)


def as_table(body: bytes) -> bytes:
    doc = json.loads(body)
    rows = [{"cells": [it["metadata"]["name"]], "object": it}
            for it in doc["items"]]
    return json.dumps({"kind": "Table", "apiVersion": "meta.k8s.io/v1",
                       "columnDefinitions": [{"name": "Name"}],
                       "rows": rows}).encode()


def stand_in(dep, seen_accepts: list, reshape=None):
    """The benchmark's stand-in upstream behind a stub that notes the
    ``Accept`` it was sent and serves a Table to whoever asked for one
    (the stand-in itself serves Lists only)."""
    kube = _bench_module("upstream").ReadOnlyKube(dep.upstream_objects())

    async def upstream(req):
        accept = next((v for k, v in req.headers.items()
                       if k.lower() == "accept"), "")
        seen_accepts.append(accept)
        resp = await kube(req)
        if resp.status == 200 and "as=Table" in accept:
            resp.body = as_table(resp.body)
        if reshape is not None:
            resp.body = reshape(resp.body)
        resp.headers["Content-Length"] = str(len(resp.body))
        return resp
    return upstream


async def _served(dep, tmp_path, upstream, requests):
    """-> [(status, parsed body)] of ``GET /api/v1/services`` for each
    (user, Accept) of ``requests``, one after the other, through a
    configuration completed with default flags and its listener."""
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    cfg = Options(
        rule_content=dep.text("rules.yaml"),
        bootstrap_content=dep.text("bootstrap.yaml"), upstream=upstream,
        bind_host="127.0.0.1", bind_port=0,
        workflow_database_path=str(tmp_path / "dtx.sqlite"),
        trace_sample=1.0,
    ).complete()
    cfg.engine.bulk_load(dep.columns())
    cfg.engine.compiled()
    await cfg.run()

    async def listed(user, accept):
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", cfg.server.port)
        writer.write((f"GET /api/v1/services HTTP/1.1\r\nHost: x\r\n"
                      f"X-Remote-User: {user}\r\nAccept: {accept}\r\n"
                      "Connection: close\r\n\r\n").encode())
        await writer.drain()
        raw = await reader.read()
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ", 2)[1]), json.loads(body)

    try:
        tracer.reset()
        out = []
        for user, accept in requests:
            out.append(await listed(user, accept))
        return out, cfg.engine.oracle()
    finally:
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        cfg.engine.close_compaction()


def readings() -> dict:
    out = {h: metrics.histogram(h).n for h in STAGES.values()}
    out["engine_bulk_cache_seconds"] = metrics.histogram(
        "engine_bulk_cache_seconds").n
    out.update((c, metrics.counter(c).value) for c in COUNTERS)
    out["native_lists"] = metrics.counter("proxy_postfilter_total",
                                          path="native").value
    return out


def moved(before: dict) -> dict:
    return {k: v - before[k] for k, v in readings().items()}


def names_of(doc: dict) -> list:
    items = doc["rows"] if doc["kind"] == "Table" else doc["items"]
    objs = [it["object"] if doc["kind"] == "Table" else it for it in items]
    return sorted(f"{o['metadata']['namespace']}/{o['metadata']['name']}"
                  for o in objs)


@pytest.mark.parametrize("accept,kind,upstream_sees", [
    ("application/json", "ServiceList", "application/json"),
    (TABLE, "Table", TABLE),
    (PROTOBUF, "ServiceList", "application/json"),
], ids=["list", "table", "protobuf-accept"])
def test_served_lists_name_what_the_reference_and_the_oracle_name(
        scoped, tmp_path, accept, kind, upstream_sees):
    """Twenty users list every service of the cluster. Each answer holds
    just the services whose namespace that user may view, by the
    reference and by the oracle, whatever shape the upstream's answer
    has; a protobuf ``Accept`` reaches the upstream as JSON. A list is
    one observation of each stage of the path and one bulk check of as
    many checks as namespaces hold one of the upstream's objects."""
    dep = scoped.dep
    n_objects = dep.count("service")
    users = list(range(0, dep.count("user"), dep.count("user") // LISTS))
    accepts = []
    before = readings()
    got, oracle = asyncio.run(_served(
        dep, tmp_path, stand_in(dep, accepts),
        [(str(scoped.users[u]), accept) for u in users]))
    assert len(got) == LISTS and accepts == [upstream_sees] * LISTS
    kept = 0
    for u, (status, doc) in zip(users, got):
        assert status == 200 and doc["kind"] == kind, (u, status)
        assert names_of(doc) == scoped.seen(u), u
        assert names_of(doc) == scoped.by_the_oracle(oracle, u), u
        kept += len(scoped.seen(u))
    assert 0 < kept < LISTS * n_objects  # some kept, most dropped
    delta = moved(before)
    for hist in list(STAGES.values()) + ["engine_bulk_cache_seconds"]:
        assert delta[hist] == LISTS, hist
    assert delta["proxy_postfilter_items_total"] == LISTS * n_objects
    assert delta["proxy_postfilter_kept_total"] == kept
    # the rule reads nothing of an object but its namespace: the native
    # scanner reads every list, List or Table
    assert delta["native_lists"] == LISTS
    # the rule reads the object's namespace and the user: it is resolved
    # once a namespace that holds a service, and that is one check. Every
    # user is new to the cache, so every check is dispatched, and each is
    # a question of its own
    held = scoped.held
    assert held < n_objects
    assert delta["proxy_postfilter_resolved_total"] == LISTS * held
    # a bare {{namespace}}: every resolution is read off the key columns
    assert delta["proxy_postfilter_column_resolved_total"] == LISTS * held
    assert delta["engine_checks_total"] == LISTS * held
    assert delta["engine_checks_distinct_total"] == LISTS * held
    spans = [s["name"] for t in tracer.recent() for s in t["spans"]]
    for stage in STAGES:
        assert spans.count(stage) == LISTS, stage
    assert spans.count("bulk_cache") == 2 * LISTS  # probes, then puts
    assert spans.count("engine_encode") == LISTS  # one chunk a list


# each synchronous stage of the postfilter path adds its thread's CPU
# seconds to a counter beside its histogram (ISSUE 37)
CPU_BESIDE = {
    "proxy_postfilter_parse_cpu_seconds_total":
        "proxy_postfilter_parse_seconds",
    "proxy_postfilter_resolve_cpu_seconds_total":
        "proxy_postfilter_resolve_seconds",
    "proxy_postfilter_write_cpu_seconds_total":
        "proxy_postfilter_write_seconds",
    "engine_bulk_cache_cpu_seconds_total": "engine_bulk_cache_seconds",
}
_LIST_CLOCKS = {}


@pytest.mark.parametrize("counter", sorted(CPU_BESIDE))
def test_a_postfiltered_list_adds_each_stages_cpu_beside_its_wall(
        scoped, tmp_path, counter, monkeypatch):
    """One worker thread's Python from the parse to the write: every
    part's CPU counter moves, and never by more than its wall clock
    (with every stage's CPU read, not one in ``CPU_EVERY``)."""
    from spicedb_kubeapi_proxy_tpu.obs import trace

    monkeypatch.setattr(trace, "CPU_EVERY", 1)
    if not _LIST_CLOCKS:  # one served list a process
        def read():
            return {c: (metrics.counter(c).value, metrics.histogram(h).total)
                    for c, h in CPU_BESIDE.items()}

        before = read()
        got, _ = asyncio.run(_served(
            scoped.dep, tmp_path, stand_in(scoped.dep, []),
            [(str(scoped.users[0]), "application/json")]))
        assert got[0][0] == 200
        for c, (cpu, wall) in read().items():
            _LIST_CLOCKS[c] = (cpu - before[c][0], wall - before[c][1])
    cpu, wall = _LIST_CLOCKS[counter]
    assert 0.0 < cpu <= wall, (counter, cpu, wall)


def test_a_list_asked_again_is_answered_from_the_cache(scoped, tmp_path):
    """The same user's second list dispatches nothing: every verdict is
    a hit, and ``bulk_cache`` is still one observation a call."""
    dep = scoped.dep
    user = str(scoped.users[7])
    before = readings()
    got, _ = asyncio.run(_served(dep, tmp_path, stand_in(dep, []),
                                 [(user, "application/json")] * 2))
    assert names_of(got[0][1]) == names_of(got[1][1]) == scoped.seen(7)
    delta = moved(before)
    assert delta["engine_bulk_cache_seconds"] == 2
    assert delta["engine_checks_total"] == scoped.held
    assert delta["proxy_postfilter_items_total"] == 2 * dep.count("service")
    # the templates are resolved again: only verdicts are kept between lists
    assert delta["proxy_postfilter_resolved_total"] == 2 * scoped.held


def test_an_object_with_no_namespace_fails_the_whole_list(scoped, tmp_path):
    """One item whose check cannot be built (its namespace is empty)
    and the list is refused: nothing of it is kept, not even what the
    user may see."""
    dep = scoped.dep

    def strip_one(body: bytes) -> bytes:
        doc = json.loads(body)
        del doc["items"][len(doc["items"]) // 2]["metadata"]["namespace"]
        return json.dumps(doc).encode()

    user = next(u for u in range(dep.count("user")) if scoped.seen(u))
    before = readings()
    got, _ = asyncio.run(_served(
        dep, tmp_path, stand_in(dep, [], reshape=strip_one),
        [(str(scoped.users[user]), "application/json")]))
    (status, doc), = got
    assert status == 401 and doc["kind"] == "Status"
    assert "items" not in doc
    delta = moved(before)
    assert delta["proxy_postfilter_seconds"] == 1
    assert delta["proxy_postfilter_kept_total"] == 0
    assert delta["proxy_postfilter_resolved_total"] == 0
    assert delta["engine_checks_total"] == 0


def test_a_bulk_of_three_chunks_answers_what_one_chunk_answers(
        scoped, monkeypatch):
    """40,000 checks (every user against every namespace, twice) go
    out as three dispatches of at most 16,384 items; with a chunk that
    holds them all, as one. Item by item the answers are the same, and
    they are the reference's."""
    dep = scoped.dep
    engine = Engine(dep.text("bootstrap.yaml"))
    engine.bulk_load(dep.columns())
    n_users, n_ns = dep.count("user"), dep.count("namespace")
    ns_names = dep.names("namespace")
    pairs = [(u, k) for u in range(n_users) for k in range(n_ns)
             for _ in range(2)]  # each asked twice, side by side
    assert len(pairs) == 40_000
    items = [CheckItem("namespace", str(ns_names[k]), "view", "user",
                       str(scoped.users[u])) for u, k in pairs]
    encodes = metrics.histogram("engine_encode_seconds")
    checks = metrics.counter("engine_checks_total")
    distinct = metrics.counter("engine_checks_distinct_total")
    assert Engine.CHECK_PIPELINE_CHUNK == 16384
    n0, c0, d0 = encodes.n, checks.value, distinct.value
    chunked = engine.check_bulk(items)
    assert encodes.n - n0 == 3 and checks.value - c0 == 40_000
    assert distinct.value - d0 == 20_000  # no pair straddles a chunk
    monkeypatch.setattr(Engine, "CHECK_PIPELINE_CHUNK", 65536)
    n0, d0 = encodes.n, distinct.value
    whole = engine.check_bulk(items)
    assert encodes.n - n0 == 1 and distinct.value - d0 == 20_000
    assert chunked == whole
    held = [np.zeros(n_ns, dtype=bool) for _ in range(n_users)]
    for u in range(n_users):
        held[u][scoped.ref.lookup("namespace#view", u)] = True
    assert whole == [bool(held[u][k]) for u, k in pairs]
    assert any(whole) and not all(whole)


def test_the_stale_control_is_told_from_the_reference(scoped):
    """The reference at a revision before the last 5% of each relation's
    rows answers many users' lists otherwise, services and pods alike."""
    n_users = scoped.dep.count("user")
    for typ in ("service", "pod"):
        differ = sum(scoped.seen(u, typ=typ) != scoped.seen(
            u, ref=scoped.stale, typ=typ) for u in range(n_users))
        assert differ > n_users // 10, (typ, differ)

"""Namespaces-as-a-service (``namespace#view = viewer + creator +
tenant->manage``): the benchmark's ``multi-tenant-100k`` deployment at
its rehearsal size, built by its own ``generate.py`` through
``benchmark/deployment.py``. The engine, the benchmark's plain reference
and the oracle agree on what tenant admins, team members, creators and a
user of a tenant with no team see; a dispatch of several rows gives
every row the mask that row's own dispatch gives, whether the graph has
dense blocks or none, and so does every fused dispatch of the batcher,
whatever the number of lookups that wait (on the graph's block-less
twin: with its dense blocks the graph does not fuse); lookups that wait
together leave in fewer dispatches than there are lookups, one alone in
a dispatch of one row; and the served namespace list names what the
reference names. Counts, never timings, and the constants production
runs: the batcher is held and released by the test (tests/fusing.py).
"""

import asyncio
import contextlib
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from fusing import hold, release, warm
from spicedb_kubeapi_proxy_tpu.engine import Engine
from spicedb_kubeapi_proxy_tpu.engine.batcher import FUSED_ROWS, MIN_ROWS
from spicedb_kubeapi_proxy_tpu.engine.engine import mask_pseudo_objects
from spicedb_kubeapi_proxy_tpu.obs.trace import tracer
from spicedb_kubeapi_proxy_tpu.ops import reachability
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = "multi-tenant-100k"
SEED = 3200000023
KINDS = ("admin", "member", "creator", "teamless")


def _bench_module(name: str):
    """A file of benchmark/ by path: its directory stays off sys.path,
    where ``client`` or ``run`` could shadow a test's import."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("/", "_").replace("-", "_"),
        os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def compiled_with(dense_blocks: bool):
    """Graphs compiled inside keep their dense blocks, or have none."""
    was = reachability.DENSE_MIN_EDGES
    if not dense_blocks:
        reachability.DENSE_MIN_EDGES = 10 ** 9
    try:
        yield
    finally:
        reachability.DENSE_MIN_EDGES = was


class Tenants:
    """The deployment loaded once: reference, users by what binds them,
    and engines on demand (with the graph's dense blocks, or with
    none)."""

    def __init__(self):
        self.dep = _bench_module("deployment").Deployment(
            CONFIG, SEED, rehearse=True)
        self.reference = _bench_module("reference").Reference
        self.ref = self.reference(self.dep)
        self.users = self.dep.names("user")
        self.names = self.dep.names("namespace")
        edges = {(e[0], e[1], e[2]): e for e in self.dep.edges}
        of = _bench_module(f"configs/{CONFIG}/generate").generate(
            self.dep.sizes, SEED)["tenant_of"]
        has_team = np.bincount(of["group"],
                               minlength=self.dep.count("tenant")) > 0
        self.kind = {
            "admin": np.unique(edges["tenant", "admin", "user"][5]),
            "member": np.unique(edges["group", "member", "user"][5]),
            "creator": np.unique(edges["namespace", "creator", "user"][5]),
            # its tenant drew no team: nothing reaches it through one
            "teamless": np.flatnonzero(~has_team[of["user"]]),
        }

    def engine(self, fuse=True, dense_blocks=True) -> Engine:
        """An engine over the deployment, as every engine starts, or
        (``fuse=False``) with one dispatch a lookup;
        ``dense_blocks=False`` compiles every edge onto the residual
        path."""
        e = Engine(self.dep.text("bootstrap.yaml"))
        with compiled_with(dense_blocks):
            e.bulk_load(self.dep.columns())
            assert bool(e.compiled().blocks) is dense_blocks
        if not fuse:
            e.disable_lookup_batching()
        return e

    def seen(self, user: int) -> list:
        return sorted(self.names[
            self.ref.lookup("namespace#view", user)].tolist())


@pytest.fixture(scope="module")
def tenants():
    return Tenants()


@pytest.fixture(scope="module")
def direct(tenants):
    """One dispatch a lookup: what every fused answer is held to."""
    return tenants.engine(fuse=False)


def mask_of(engine, tenants, user: int) -> np.ndarray:
    return engine.lookup_resources_mask(
        "namespace", "view", "user", str(tenants.users[user]))[0]


def counts() -> tuple:
    return tuple(metrics.counter(n).value for n in (
        "engine_lookup_batches_total", "engine_lookups_total",
        "engine_dispatch_rows_total")) + (
        metrics.histogram("engine_lookup_seconds").n,)


def moved(before: tuple) -> tuple:
    """-> (fused dispatches, lookups, rows, dispatches) since."""
    return tuple(b - a for a, b in zip(before, counts()))


@pytest.mark.parametrize("kind", KINDS)
def test_lookups_equal_the_reference_and_the_oracle(tenants, direct, kind):
    oracle = direct.oracle()
    users = tenants.kind[kind][:12].tolist()
    assert len(users) >= 3, kind
    something = 0
    for u in users:
        user, want = str(tenants.users[u]), tenants.seen(u)
        assert sorted(direct.lookup_resources(
            "namespace", "view", "user", user)) == want, user
        assert sorted(oracle.lookup_resources(
            "namespace", "view", "user", user)) == want, user
        something += bool(want)
    # admins, members and creators see something; a user of a tenant with
    # no team sees at most what it created or manages
    assert something or kind == "teamless"


def test_an_admin_sees_its_whole_tenant_and_nobody_elses(tenants):
    edges = {(e[0], e[1], e[2]): e for e in tenants.dep.edges}
    _, _, _, _, admin_t, admin_u = edges["tenant", "admin", "user"]
    _, _, _, _, ns, ns_t = edges["namespace", "tenant", "tenant"]
    t, u = int(admin_t[0]), int(admin_u[0])
    seen = set(tenants.ref.lookup("namespace#view", u).tolist())
    assert set(ns[ns_t == t].tolist()) <= seen
    # hard multi-tenancy: every grant stays inside the user's tenant
    assert set(ns_t[sorted(seen)].tolist()) == {t}


def some_users(tenants, n: int) -> list:
    """Users of every kind, and some that see nothing."""
    users = np.unique(np.concatenate(
        [tenants.kind[k][:8] for k in KINDS] + [np.arange(40)]))[:n].tolist()
    assert len(users) == n
    return users


@pytest.fixture(scope="module", params=["dense blocks", "no dense block"])
def graph(request, tenants):
    return tenants.engine(fuse=False,
                          dense_blocks=request.param == "dense blocks")


@pytest.mark.parametrize("rows", [2, 5, 16, 32])
def test_a_dispatch_of_several_rows_gives_every_row_its_own_dispatchs_mask(
        tenants, direct, graph, rows):
    """The device program under a fused dispatch, asked directly: ``rows``
    subjects in one ``query_async``, read as the grid of that many rows
    over the type's window."""
    cg, objs = graph.compiled(), graph._objects_by_name()
    off, n = cg.offset_of("namespace", "view"), cg.type_sizes["namespace"]
    users = some_users(tenants, rows)
    seeds = np.array([cg.encode_subject("user", str(tenants.users[u]), None,
                                        objs) for u in users], dtype=np.int32)
    out = cg.query_async(seeds, None, None,
                         q_contig_grid=(off, n, rows)).result()
    seen = 0
    for i, u in enumerate(users):
        mask = mask_pseudo_objects(np.array(out[i * n:(i + 1) * n]))
        np.testing.assert_array_equal(mask, mask_of(direct, tenants, u))
        seen += int(mask.sum())
    assert seen > 0


@pytest.fixture(scope="module")
def fusing(tenants):
    """An engine whose lookups fuse, as every engine starts: the
    deployment's tuples with every edge on the residual path, its
    window's two programs compiled."""
    e = tenants.engine(dense_blocks=False)
    warm(e, "namespace")
    return e


@pytest.mark.parametrize("lookups", [2, 5, 16, 32])
def test_a_fused_dispatch_gives_every_row_its_own_dispatchs_mask(
        tenants, direct, fusing, lookups):
    users = some_users(tenants, lookups)
    hold(fusing._batcher)
    futs = [fusing.lookup_resources_mask_async(
        "namespace", "view", "user", str(tenants.users[u])) for u in users]
    c0 = counts()
    release(fusing._batcher, lookups)
    got = [f.result()[0] for f in futs]
    seen = 0
    for u, mask in zip(users, got):
        np.testing.assert_array_equal(mask, mask_of(direct, tenants, u))
        seen += int(mask.sum())
    assert seen > 0
    fused, left = divmod(lookups, FUSED_ROWS)
    if left >= MIN_ROWS:  # a dispatch for what is left; fewer go alone
        fused, left = fused + 1, 0
    # (direct's own lookups above count too: one dispatch a lookup)
    assert moved(c0) == (fused, 2 * lookups, 2 * lookups,
                         fused + left + lookups)


def test_64_threads_at_once_take_fewer_dispatches_than_lookups(
        tenants, direct, fusing):
    users = list(range(64))
    got = {}

    def one(u):
        got[u] = mask_of(fusing, tenants, u)

    hold(fusing._batcher)
    c0 = counts()
    threads = [threading.Thread(target=one, args=(u,)) for u in users]
    for t in threads:
        t.start()
    release(fusing._batcher, 64)
    for t in threads:
        t.join()
    assert moved(c0) == (64 // FUSED_ROWS, 64, 64, 64 // FUSED_ROWS)
    for u in users:  # each with its own user's mask
        np.testing.assert_array_equal(got[u], mask_of(direct, tenants, u))


def test_a_lookup_with_nothing_waiting_is_one_dispatch_of_one_row(
        tenants, direct, fusing):
    c0 = counts()
    mask = mask_of(fusing, tenants, int(tenants.kind["admin"][0]))
    assert moved(c0) == (0, 1, 1, 1)
    np.testing.assert_array_equal(
        mask, mask_of(direct, tenants, int(tenants.kind["admin"][0])))


def test_by_default_this_graph_does_not_fuse_and_one_with_no_block_does(
        tenants, direct, fusing):
    """With default flags the fused program is the compiled graph's to
    have or not: this deployment's has dense blocks, where 8 rows cost
    what 11 to 13 dispatches of one row cost on the chip, so its
    lookups never wait for each other and it compiles no second
    program; the same tuples with every edge on the residual path fuse
    from three waiting lookups, and two leave alone, one dispatch
    each."""
    from spicedb_kubeapi_proxy_tpu.engine import batcher

    e = tenants.engine()
    assert batcher.fused_rows(e.compiled()) == 0
    assert e._batcher._program(e.compiled(), "namespace", "view") is None
    hold(e._batcher)  # nothing of this graph ever waits behind it
    c0 = counts()
    for u in range(3):
        np.testing.assert_array_equal(
            mask_of(e, tenants, u), mask_of(direct, tenants, u))
    assert moved(c0) == (0, 6, 6, 6)
    assert not e._batcher._pending
    assert batcher.fused_rows(fusing.compiled()) == 8
    for lookups, want in ((2, (0, 2, 2, 2)), (3, (1, 3, 3, 1)),
                          (11, (2, 11, 11, 2))):
        hold(fusing._batcher)
        futs = [fusing.lookup_resources_mask_async(
            "namespace", "view", "user", str(tenants.users[u]))
            for u in range(lookups)]
        c0 = counts()
        release(fusing._batcher, lookups)
        got = [f.result()[0] for f in futs]
        assert moved(c0) == want, lookups
        for u, mask in enumerate(got):
            np.testing.assert_array_equal(mask, mask_of(direct, tenants, u))


async def _served_namespace_lists(tenants, tmp_path, users):
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    dep = tenants.dep
    cfg = Options(
        rule_content=dep.text("rules.yaml"),
        bootstrap_content=dep.text("bootstrap.yaml"),
        upstream=_bench_module("upstream").ReadOnlyKube(
            dep.upstream_objects()),
        bind_host="127.0.0.1", bind_port=0,
        workflow_database_path=str(tmp_path / "dtx.sqlite"),
        trace_sample=1.0,
    ).complete()
    # the deployment's graph has dense blocks and does not fuse: its
    # block-less twin is served, and the five lists wait together
    with compiled_with(dense_blocks=False):
        cfg.engine.bulk_load(dep.columns())
        cfg.engine.compiled()
    batcher = cfg.engine._batcher
    await cfg.run()

    async def listed(user):
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", cfg.server.port)
        writer.write((f"GET /api/v1/namespaces HTTP/1.1\r\nHost: x\r\n"
                      f"X-Remote-User: {user}\r\nConnection: close\r\n"
                      "\r\n").encode())
        await writer.drain()
        raw = await reader.read()
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        assert head.split(b" ", 2)[1] == b"200", head
        return sorted(o["metadata"]["name"]
                      for o in json.loads(body)["items"])

    try:
        await asyncio.to_thread(warm, cfg.engine, "namespace")
        tracer.reset()
        hold(batcher)
        lists = asyncio.gather(*(listed(str(tenants.users[u]))
                                 for u in users))
        await asyncio.to_thread(release, batcher, len(users))
        return await lists
    finally:
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        cfg.engine.close_compaction()


def test_served_namespace_lists_name_what_the_reference_names(tenants,
                                                              tmp_path):
    """``GET /api/v1/namespaces`` through the served path, as five users
    at once whose answer a stale revision would get wrong: each list is
    the reference's for its own user, not the stale reference's; the
    five prefilters left in one fused dispatch, each after a
    ``batch_wait``, and each ``device_wait`` span says so."""
    stale = tenants.reference(tenants.dep,
                              tenants.dep.config["control"]["stale_share"])
    users = [u for u in range(len(tenants.users))
             if len(stale.lookup("namespace#view", u))
             != len(tenants.ref.lookup("namespace#view", u))][:5]
    assert len(users) == 5
    c0 = counts()
    got = asyncio.run(_served_namespace_lists(tenants, tmp_path, users))
    # (the lookup that warmed the window counts too: one row, alone)
    assert moved(c0) == (1, 6, 6, 2)
    for u, names in zip(users, got):
        assert names == tenants.seen(u), u
        assert names != sorted(tenants.names[
            stale.lookup("namespace#view", u)].tolist()), u
    spans = [s for t in tracer.recent() for s in t["spans"]]
    assert sum(s["name"] == "batch_wait" for s in spans) == 5
    waits = [s["attrs"] for s in spans if s["name"] == "device_wait"]
    assert [w["rows"] for w in waits] == [5] * 5, waits

"""A permission that rests on itself through an arrow
(``namespace#view = viewer + creator + parent->view``): the benchmark's
``ns-tree-10hop`` deployment at its rehearsal size, built by its own
``generate.py`` through ``benchmark/deployment.py``. The engine, the
benchmark's plain reference and the oracle agree on lookups and checks at
every depth of the tree; the cycle alone is the iterated core, what
feeds it sits at feeder levels before the loop, and the loop's trips
follow the depth of the data; the gauges say what the loop walks and
what was walked once before it; and the served namespace list names what
the reference names.
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
SEED = 2800000017
DEPTHS = range(10)


def _bench_module(name: str):
    """A file of benchmark/ by path: its directory stays off sys.path,
    where ``client`` or ``run`` could shadow a test's import."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Tree:
    """The deployment loaded once: engine, reference, oracle, and the
    tree's own tables (level and root ancestor of every namespace)."""

    def __init__(self):
        self.dep = _bench_module("deployment").Deployment(
            "ns-tree-10hop", SEED, rehearse=True)
        self.reference = _bench_module("reference").Reference
        self.ref = self.reference(self.dep)
        self.engine = Engine(self.dep.text("bootstrap.yaml"))
        self.engine.bulk_load(self.dep.columns())
        self.oracle = self.engine.oracle()
        self.users = self.dep.names("user")
        self.names = {t: self.dep.names(t) for t in ("namespace", "pod")}
        levels = self.dep.sizes["levels"]
        self.level = np.repeat(np.arange(len(levels)), levels)
        edges = {(e[0], e[1], e[2]): e for e in self.dep.edges}
        _, _, _, _, child, parent = edges["namespace", "parent", "namespace"]
        self.root = np.arange(len(self.level))
        for c, p in zip(child.tolist(), parent.tolist()):  # parents first
            self.root[c] = self.root[p]
        self.creator = edges["namespace", "creator", "user"][5]
        self.pod_ns = edges["pod", "namespace", "namespace"][5]

    def seen(self, typ: str, user: int) -> list:
        return sorted(self.names[typ][
            self.ref.lookup(f"{typ}#view", user)].tolist())


@pytest.fixture(scope="module")
def tree():
    return Tree()


@pytest.mark.parametrize("typ", ["namespace", "pod"])
def test_lookups_equal_the_reference_and_the_oracle(tree, typ):
    some = 0
    for u in range(0, len(tree.users), 10):
        user = str(tree.users[u])
        want = tree.seen(typ, u)
        assert sorted(tree.engine.lookup_resources(
            typ, "view", "user", user)) == want, user
        assert sorted(tree.oracle.lookup_resources(
            typ, "view", "user", user)) == want, user
        some += bool(want)
    assert some > 20  # most users see something: the comparison has teeth


@pytest.mark.parametrize("depth", DEPTHS)
def test_checks_agree_at_every_depth(tree, depth):
    """For every namespace ``depth`` levels below its root: the root's
    creator sees it (and a pod in it) through ``depth`` arrows, and the
    next user who by the reference may not see it is refused."""
    items, want = [], []
    for ns in np.flatnonzero(tree.level == depth).tolist():
        heir = int(tree.creator[tree.root[ns]])
        stranger = next(u for u in range(heir + 1, heir + 400)
                        if not tree.ref.check("namespace#view", ns,
                                              u % len(tree.users)))
        pods = np.flatnonzero(tree.pod_ns == ns)[:1].tolist()
        for u in (heir, stranger % len(tree.users)):
            for typ, idx in [("namespace", ns)] + [("pod", p) for p in pods]:
                items.append(CheckItem(typ, str(tree.names[typ][idx]),
                                       "view", "user", str(tree.users[u])))
                want.append(tree.ref.check(f"{typ}#view", idx, u))
    assert True in want and False in want
    assert want[0] is True  # the heir's own line, whatever else binds
    assert tree.engine.check_bulk(items) == want
    assert [tree.oracle.check(i.resource_type, i.resource_id, "view",
                              "user", i.subject_id) for i in items] == want


def test_the_core_holds_the_cycle_alone_and_trips_follow_depth(tree):
    """Pins what the benchmark's cell measures: the loop holds the cycle
    ``namespace#view`` <-> its arrow term and nothing else; grants,
    creators and membership (plain relations, acyclic by themselves) are
    final before it starts; and a right bound at a root takes one trip
    per level to reach a leaf. (Until PR 29 the feeders sat at level 0
    and iterated with the cycle.) A change that closes ``parent`` on the
    host changes this test knowingly."""
    cg = tree.engine.compiled()

    def level_of(typ, rel):
        off = cg.offset_of(typ, rel)
        return int(cg.range_levels[np.searchsorted(
            cg.range_offs, off, side="right") - 1])

    assert level_of("namespace", "view") == 0
    assert level_of("namespace", "__arrow_view_0") == 0
    assert cg.core_ranges() == 2
    # feeder levels, in order: membership before the grants that name it
    assert level_of("user", "__self") < level_of("group", "member") \
        < level_of("namespace", "viewer") < -1
    assert level_of("user", "__self") < level_of("namespace", "creator") < -1
    assert level_of("pod", "view") > 0  # rests on the cycle, not in it
    leaf = int(np.flatnonzero(tree.level == 9)[0])
    heir = str(tree.users[tree.creator[tree.root[leaf]]])
    fut = tree.engine.check_bulk_async([CheckItem(
        "namespace", str(tree.names["namespace"][leaf]), "view", "user",
        heir)])
    assert fut.result() == [True]
    # nine arrows below the root: the entry phase sets the root's view
    # (creator and grants are final, through a group or not), nine trips
    # carry it down, the loop's last finds nothing new. Until PR 29 the
    # loop also spent a trip on the grant and one more on a group: 11, 12
    assert fut.iterations() == 10
    low = next(u for u in range(len(tree.users)) if tree.level[
        tree.ref.lookup("namespace#view", u)].min() == 9)
    fut = tree.engine.check_bulk_async([CheckItem(
        "namespace", str(tree.names["namespace"][leaf]), "view", "user",
        str(tree.users[low]))])
    fut.result()
    assert fut.iterations() <= 2  # bound at leaves only: nothing to carry


def test_core_gauges_read_what_the_level_bounds_say(tree):
    cg = tree.engine.compiled()
    tree.engine._publish_graph_gauges(cg)
    bounds = cg.res_level_bounds
    cells = sum(b.n_dst * b.n_src for b in cg.blocks if b.level == 0)
    edges, ranges = cg.core_edges(), cg.core_ranges()
    lo, hi = cg.run_meta().level_slice(0)
    assert (lo, hi) == (bounds[cg.n_pre], bounds[cg.n_pre + 1])
    assert edges == hi - lo + cells
    assert ranges == int((cg.range_levels == 0).sum()) == 2
    assert metrics.gauge("engine_core_edges").value == edges
    assert metrics.gauge("engine_core_ranges").value == ranges
    # what moved out of the loop: every slice before the core's, and the
    # ranges they finalize (the users' own range among them)
    assert metrics.gauge("engine_feeder_edges").value \
        == cg.feeder_edges() == lo - bounds[0] > edges
    assert metrics.gauge("engine_feeder_ranges").value \
        == cg.feeder_ranges() == int((cg.range_levels < 0).sum()) == 4
    # the core is part of the residual, not all of it: pods lie outside
    assert bounds[0] < lo < hi < bounds[-1]
    assert metrics.gauge("engine_residual_edges").value == len(cg.res_idx)


async def _served_namespace_lists(tree, tmp_path, users):
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    dep = tree.dep
    cfg = Options(
        rule_content=dep.text("rules.yaml"),
        bootstrap_content=dep.text("bootstrap.yaml"),
        upstream=_bench_module("upstream").ReadOnlyKube(
            dep.upstream_objects()),
        bind_host="127.0.0.1", bind_port=0,
        workflow_database_path=str(tmp_path / "dtx.sqlite"),
        trace_sample=1.0,
    ).complete()
    cfg.engine.bulk_load(dep.columns())
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
        tracer.reset()
        return [await listed(str(tree.users[u])) for u in users]
    finally:
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        cfg.engine.close_compaction()


def test_served_namespace_list_names_what_the_reference_names(tree,
                                                             tmp_path):
    """``GET /api/v1/namespaces`` through the default-flag served path,
    as five users whose answer a stale revision would get wrong: the
    list is the reference's, not the stale reference's; and the
    ``device_wait`` span says how many trips the lookup took over how
    many core edges."""
    stale = tree.reference(tree.dep,
                           tree.dep.config["control"]["stale_share"])
    users = [u for u in range(len(tree.users))
             if len(stale.lookup("namespace#view", u))
             != len(tree.ref.lookup("namespace#view", u))][:5]
    assert len(users) == 5
    got = asyncio.run(_served_namespace_lists(tree, tmp_path, users))
    for u, names in zip(users, got):
        assert names == tree.seen("namespace", u), u
        assert names != sorted(tree.names["namespace"][
            stale.lookup("namespace#view", u)].tolist()), u
    waits = [s["attrs"] for t in tracer.recent() for s in t["spans"]
             if s["name"] == "device_wait"]
    assert len(waits) >= 5
    core_edges = metrics.gauge("engine_core_edges").value
    assert all(w["core_edges"] == core_edges and w["fixpoint_iters"] >= 1
               for w in waits), waits

#!/usr/bin/env python3
"""The quickest proof that the served authorization path starts on the chip.

One process, JAX initialised in-process, one TPU v5e chip by default:

    python3 chip_smoke.py               # the driver's run: full width
    python3 chip_smoke.py --chips 4     # mesh vs single device, nothing else
    python3 chip_smoke.py --tiny        # rehearsal size (tests, CPU)

Phases, each printing its seconds on stdout: ``device``, ``load`` (the
headline deployment of bench.py / BASELINE config 2 — 100k pods, 10k
users, 1k namespaces, 1k groups, 10M relationships — bulk-loaded into the
engine a default-flag ``Options(...).complete()`` builds, then compiled
and placed on the device), ``engine`` (lookups and a 65k bulk check,
equal to the plain oracle, then writes through the overlay) and
``served`` (real HTTP over loopback against the same engine). Any failed
phase is a non-zero exit; a platform other than ``tpu`` is a failure
before any work (``--tiny`` still walks the phases there, for the CPU
rehearsal, but never prints a result and never exits 0). The last stdout
line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # git-ignored run-time files

FULL = dict(pods=100_000, users=10_000, ns=1_000, groups=1_000,
            rels=10_000_000)
TINY = dict(pods=200, users=100, ns=10, groups=10, rels=3_000)
N_LOOKUP_USERS = 3
CHECK_USERS, CHECKS_PER_USER = 64, 1024  # the 65,536-item bulk check
WATCH_S = 30.0  # a watch that has not delivered by then has failed


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    say(f"== {name}")
    t0 = time.perf_counter()
    yield
    say(f"== {name}: {time.perf_counter() - t0:.1f}s")


class CompileLog:
    """XLA compile accounting from jax.monitoring: backend compiles (count
    and seconds) and persistent-cache hits, so a second run in the same
    checkout shows its compiles were reads."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.compile_s += float(duration)

    def _event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def line(self) -> str:
        return (f"xla compiles={self.compiles} compile_s={self.compile_s:.1f}"
                f" persistent_cache_hits={self.cache_hits}")


def counter(name: str) -> float:
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    return metrics.counter(name).value


class GraphLog:
    """What the graph did since the last look, by metric series: O(write)
    overlay updates, full recompiles, and why the overlay declined (the
    ``reason`` label of the fall-back counter)."""

    SERIES = ("engine_graph_incremental_updates_total",
              "engine_graph_incremental_fallback_total",
              "engine_graph_compiles_total")

    def __init__(self):
        self.last = self._read()

    def _read(self) -> dict:
        from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

        return {series: float(value) for series, value in (
            line.rsplit(" ", 1) for line in metrics.render().splitlines()
            if line.startswith(self.SERIES))}

    def delta(self) -> dict:
        now = self._read()
        d = {k: int(v - self.last.get(k, 0)) for k, v in now.items()
             if v != self.last.get(k, 0)}
        self.last = now
        return d


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats()
    return int(stats["bytes_in_use"]) if stats else -1  # CPU reports none


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(args):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__} platform={d0.platform} "
        f"device_kind={d0.device_kind} count={len(devs)}")
    if d0.platform != "tpu" and not args.tiny:
        raise SystemExit(f"chip_smoke: platform is {d0.platform!r}, not "
                         "'tpu' — no accelerator, no result")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX "
                         f"found {len(devs)} device(s)")
    return device


def build_native() -> None:
    """Build the native graph core from source in THIS run, whatever its
    mtime says of a library that happens to lie on disk (it is git-ignored,
    but a copied tree can carry one). The build publishes over the old
    file atomically, so a process that is loading it meanwhile is safe.
    A failed g++ is an error here, not the package's numpy fall-back."""
    from spicedb_kubeapi_proxy_tpu import native

    t0 = time.perf_counter()
    if native._lib is not None or not native._build() \
            or not native.available():
        raise RuntimeError("native/graphcore.cpp did not build or load "
                           "in this run (g++ missing, or SDBKP_NATIVE=0?)")
    say(f"native graph core built from source: "
        f"{time.perf_counter() - t0:.1f}s")


def make_config(upstream, mesh=None):
    """The served configuration a user gets: the repo's own rules and
    bootstrap, default flags, default ``tpu://`` endpoint. Only paths and
    the listen address are given (and the mesh for ``--chips 4``)."""
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options

    with open(os.path.join(HERE, "deploy", "rules.yaml")) as f:
        rules = f.read()
    with open(os.path.join(HERE, "deploy", "bootstrap.yaml")) as f:
        bootstrap = f.read()
    return Options(
        rule_content=rules, bootstrap_content=bootstrap, upstream=upstream,
        bind_host="127.0.0.1", bind_port=0,
        workflow_database_path=os.path.join(WORK, "dtx.sqlite"),
        engine_mesh=mesh,
    ).complete()


def phase_load(args, dims, mesh=None):
    """-> (cfg, cols, kube): the deployment loaded, compiled, on device."""
    import jax

    from bench import build_columns
    from spicedb_kubeapi_proxy_tpu.ops import bitprop
    from spicedb_kubeapi_proxy_tpu.proxy.inmemkube import InMemoryKube

    build_native()
    say(f"deployment: pods={dims['pods']} users={dims['users']} "
        f"namespaces={dims['ns']} groups={dims['groups']} "
        f"relationships={dims['rels']} seed={args.seed}")
    t0 = time.perf_counter()
    cols = build_columns(dims["pods"], dims["users"], dims["ns"],
                         dims["groups"], dims["rels"], seed=args.seed)
    total = len(cols["resource_id"])
    say(f"columns: {total} relationships in "
        f"{time.perf_counter() - t0:.1f}s")

    # the upstream kube holds the same namespaces and pods (pod ids are
    # "ns/p<i>": kube namespace "ns", name "p<i>")
    kube = InMemoryKube()
    for i in range(dims["ns"]):
        kube.put("namespaces", f"ns{i}")
    for i in range(dims["pods"]):
        kube.put("pods", f"p{i}", ns="ns")

    cfg = make_config(kube, mesh=mesh)
    e = cfg.engine
    t0 = time.perf_counter()
    e.bulk_load(cols)
    say(f"bulk_load: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    cg = e.compiled()
    say(f"compile_graph: {time.perf_counter() - t0:.1f}s")
    if mesh is None:
        t0 = time.perf_counter()
        d = cg._dev()
        jax.block_until_ready((d["blocks"], d["blocks_bits"]))
        say(f"device placement: {time.perf_counter() - t0:.1f}s")
    # bit duals exist on the single-device path only (the mesh's blocks
    # are matmul/dense-kernel operands, split on src over "graph")
    n_bits = sum(bitprop.eligible(b.n_dst, b.n_src) for b in cg.blocks) \
        if mesh is None and bitprop.kernel_enabled() else 0
    say(f"graph: slots={cg.M} edges={cg.n_edges} dense_blocks="
        f"{len(cg.blocks)} bit_duals={n_bits} levels={cg.n_levels} "
        f"residual_edges={len(cg.res_src)}")
    say("dense block shapes [n_dst x n_src @level]: " + ", ".join(
        f"{b.n_dst}x{b.n_src}@{b.level}" for b in cg.blocks))
    say(f"device bytes in use: {bytes_in_use(jax.devices()[0])}")
    return cfg, cols, kube


def sample_users(args, dims) -> list:
    import numpy as np

    rng = np.random.default_rng(args.seed + 1)
    n = min(CHECK_USERS, dims["users"])
    return [f"u{i}" for i in
            rng.choice(dims["users"], size=n, replace=False).tolist()]


def check_items(args, dims, users) -> list:
    import numpy as np

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem

    rng = np.random.default_rng(args.seed + 2)
    per = CHECKS_PER_USER if not args.tiny else 32
    return [CheckItem("pod", f"ns/p{p}", "view", "user", u)
            for u in users
            for p in rng.integers(dims["pods"], size=per).tolist()]


def build_oracle(e, cols, users, now):
    """The plain oracle (engine/evaluator.py) over the part of the data
    that can bear on the sampled users: a tuple whose subject is another
    concrete user can never grant (or deny) anything to these, so it is
    left out — the full 10M-tuple snapshot would take the pure-Python
    evaluator minutes to index."""
    import numpy as np

    from spicedb_kubeapi_proxy_tpu.engine import Engine

    other_user = (cols["subject_type"] == "user") \
        & ~np.isin(cols["subject_id"], np.asarray(users + ["*"]))
    keep = ~other_user
    sub = Engine(schema=e.schema)
    sub.bulk_load({k: v[keep] for k, v in cols.items()})
    return sub.oracle(now=now), int(keep.sum())


def phase_engine(args, dims, cfg, cols):
    """Lookups and the bulk check against the oracle; then writes.
    Returns (users, oracle visible pods per lookup user, oracle) for the
    served phase."""
    import jax

    from spicedb_kubeapi_proxy_tpu.engine.engine import mask_to_ids
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.ops import bitprop

    e = cfg.engine
    on_tpu = jax.devices()[0].platform == "tpu"
    say(f"kernels: bit enabled={bitprop.kernel_enabled()} "
        f"interpreted={bitprop._interpret()}; dense enabled="
        f"{bitprop.dense_kernel_enabled()} "
        f"interpreted={bitprop._dense_interpret()}")
    if on_tpu:
        assert not bitprop._interpret() and not bitprop._dense_interpret(), \
            "kernels must be compiled, not interpreted, on the chip"

    users = sample_users(args, dims)
    lookup_users = users[:N_LOOKUP_USERS]
    items = check_items(args, dims, users)
    # a pinned clock: the engine and the oracle see the same instant (and
    # an explicit now bypasses the decision cache, so each call dispatches)
    now = time.time()
    t0 = time.perf_counter()
    oracle, n_oracle = build_oracle(e, cols, users, now)
    say(f"oracle: {n_oracle} tuples bear on {len(users)} sampled users "
        f"({time.perf_counter() - t0:.1f}s)")

    first = steady = None
    got_lookup = []
    for u in lookup_users:
        t0 = time.perf_counter()
        mask, interner = e.lookup_resources_mask(
            "pod", "view", "user", u, now=now)
        dt = time.perf_counter() - t0
        first = dt if first is None else first
        steady = dt
        got_lookup.append(frozenset(mask_to_ids(mask, interner)))
    t0 = time.perf_counter()
    got_check = e.check_bulk(items, now=now)
    c_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = e.check_bulk(items, now=now)
    c_steady = time.perf_counter() - t0
    assert again == got_check, "check_bulk unstable"
    say(f"lookup first={first * 1e3:.1f}ms steady={steady * 1e3:.1f}ms "
        f"visible={[len(s) for s in got_lookup]}; check_bulk[{len(items)}] "
        f"first={c_first * 1e3:.1f}ms steady={c_steady * 1e3:.1f}ms "
        f"({len(items) / c_steady:,.0f} checks/s) "
        f"allowed={sum(got_check)}")

    t0 = time.perf_counter()
    visible = {}
    for u, got in zip(lookup_users, got_lookup):
        want = frozenset(oracle.lookup_resources("pod", "view", "user", u))
        assert got == want, (
            f"lookup {u}: engine {len(got)} ids, oracle {len(want)}; "
            f"missing={sorted(want - got)[:5]} extra={sorted(got - want)[:5]}")
        assert got, f"lookup {u}: nothing visible — the sample proves nothing"
        visible[u] = want
    want_checks = [oracle.check(i.resource_type, i.resource_id,
                                i.permission, i.subject_type, i.subject_id)
                   for i in items]
    bad = [k for k, (g, w) in enumerate(zip(got_check, want_checks))
           if g != w]
    assert not bad, f"check_bulk: {len(bad)} of {len(items)} differ from " \
        f"the oracle, first {items[bad[0]]}"
    assert any(want_checks) and not all(want_checks), \
        "check sample must hold both verdicts"
    say(f"oracle: {len(lookup_users)} full lookups and {len(items)} checks "
        f"agree ({time.perf_counter() - t0:.1f}s)")

    # writes: a grant to a user who could not see the pod, then its
    # removal, each read back (fully consistent) before the next
    u = lookup_users[0]
    denied = next(i for i, w in zip(items, want_checks)
                  if not w and i.subject_id == u)
    rel = Relationship("pod", denied.resource_id, "viewer", "user", u)
    graph = GraphLog()
    for op, want in (("touch", True), ("delete", False)):
        t0 = time.perf_counter()
        e.write_relationships([WriteOp(op, rel)])
        t_write = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = e.check_bulk([denied])[0]
        say(f"write {op} {denied.resource_id}#viewer@{u}: "
            f"{t_write * 1e3:.1f}ms; re-check {got} in "
            f"{(time.perf_counter() - t0) * 1e3:.1f}ms")
        assert got is want, f"re-check after {op}: {got}, want {want}"
    d = graph.delta()
    say(f"write path: {d}")
    assert d == {"engine_graph_incremental_updates_total": 2}, \
        "a plain grant and its removal must ride the overlay"
    say(f"device bytes in use: {bytes_in_use(jax.devices()[0])}")
    return users, visible, oracle


class Http:
    """Minimal HTTP/1.1 client over asyncio streams (header authn)."""

    def __init__(self, port: int, user: str):
        self.port, self.user = port, user

    async def request(self, method: str, target: str, body=None,
                      stream: bool = False):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       self.port)
        data = json.dumps(body).encode() if body is not None else b""
        head = [f"{method} {target} HTTP/1.1", f"Host: 127.0.0.1:{self.port}",
                f"X-Remote-User: {self.user}",
                "Content-Type: application/json",
                f"Content-Length: {len(data)}", "Connection: close", "", ""]
        writer.write("\r\n".join(head).encode() + data)
        await writer.drain()
        status = int((await reader.readline()).split(b" ")[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        if stream:
            return status, reader, writer
        if "chunked" in headers.get("transfer-encoding", ""):
            chunks = []
            while (chunk := await self.read_chunk(reader)) is not None:
                chunks.append(chunk)
            payload = b"".join(chunks)
        else:
            n = int(headers.get("content-length", 0))
            payload = await reader.readexactly(n) if n \
                else await reader.read()
        writer.close()
        return status, payload

    @staticmethod
    async def read_chunk(reader):
        size = int((await reader.readline()).strip() or b"0", 16)
        if size == 0:
            return None
        data = await reader.readexactly(size)
        await reader.readline()
        return data


async def phase_served(args, dims, cfg, kube, users, visible, oracle):
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem

    e = cfg.engine
    # warm the namespace-list and single-check programs before serving,
    # as proxy/demo.py does: a first XLA compile inside a request can
    # outlast the prefilter's window
    t0 = time.perf_counter()
    e.lookup_resources_mask("namespace", "view", "user", users[0])
    e.check_bulk([CheckItem("namespace", "ns0", "view", "user", users[0])])
    say(f"warm namespace list + single check: "
        f"{time.perf_counter() - t0:.1f}s")

    await cfg.run()
    port = cfg.server.port
    say(f"serving on 127.0.0.1:{port} (engine endpoint "
        f"{cfg.options.engine_endpoint})")
    try:
        for u, want in visible.items():
            t0 = time.perf_counter()
            status, body = await Http(port, u).request("GET", "/api/v1/pods")
            dt = time.perf_counter() - t0
            assert status == 200, (status, body[:200])
            got = frozenset(
                f"{i['metadata']['namespace']}/{i['metadata']['name']}"
                for i in json.loads(body)["items"])
            say(f"GET /api/v1/pods as {u}: 200, {len(got)} items "
                f"({len(body)} bytes) in {dt * 1e3:.0f}ms")
            assert got == want, (
                f"list as {u}: {len(got)} items, oracle {len(want)}")
        u = next(iter(visible))
        allowed = sorted(visible[u])[0]
        denied = next(f"ns/p{i}" for i in range(dims["pods"])
                      if f"ns/p{i}" not in visible[u])
        for pod, want in ((allowed, 200), (denied, 403)):
            ns, name = pod.split("/")
            status, _ = await Http(port, u).request(
                "GET", f"/api/v1/namespaces/{ns}/pods/{name}")
            say(f"GET pod {pod} as {u}: {status}")
            assert status == want, f"GET {pod} as {u}: {status} != {want}"

        # dual-write: create a namespace, then see it in the list. The
        # first dual-write after a bulk load writes types and relations
        # no loaded tuple had (lock, workflow, activity, creator). They
        # ride the overlay like any other write: the next list answers
        # 200 at once — one answer refused while a graph recompiles
        # (401, fail closed) is a failure here — and nothing on this path
        # declines an overlay update or compiles a graph.
        graph = GraphLog()
        before = frozenset(oracle.lookup_resources(
            "namespace", "view", "user", u))

        def graph_delta() -> dict:
            d = graph.delta()
            assert set(d) <= {"engine_graph_incremental_updates_total"}, \
                f"a served write left the overlay: {d}"
            return d

        async def create(name):
            t0 = time.perf_counter()
            status, body = await Http(port, u).request(
                "POST", "/api/v1/namespaces",
                body={"apiVersion": "v1", "kind": "Namespace",
                      "metadata": {"name": name}})
            say(f"POST namespace {name} as {u}: {status} in "
                f"{(time.perf_counter() - t0) * 1e3:.0f}ms; "
                f"graph {graph_delta()}")
            assert status == 201, (status, body[:200])

        async def ns_list(user):
            t0 = time.perf_counter()
            status, body = await Http(port, user).request(
                "GET", "/api/v1/namespaces")
            dt = time.perf_counter() - t0
            assert status == 200, (status, body[:200])
            got = frozenset(i["metadata"]["name"]
                            for i in json.loads(body)["items"])
            say(f"GET /api/v1/namespaces as {user}: 200, {len(got)} items "
                f"in {dt * 1e3:.0f}ms; graph {graph_delta()}")
            return got

        mine = f"smoke-{args.seed}-a"
        await create(mine)
        got = await ns_list(u)
        assert got == before | {mine}, \
            f"namespace list as {u}: {sorted(got)} != oracle + {mine}"
        other = users[-1]
        assert mine not in await ns_list(other), \
            f"{other} sees {u}'s namespace"

        # watch: the stream delivers the next create
        status, reader, writer = await Http(port, u).request(
            "GET", "/api/v1/namespaces?watch=true", stream=True)
        assert status == 200, status
        second = f"smoke-{args.seed}-b"
        await create(second)
        t0 = time.perf_counter()
        seen = []

        async def until_second():
            while (chunk := await Http.read_chunk(reader)) is not None:
                for line in chunk.splitlines():
                    ev = json.loads(line)
                    seen.append(ev["object"]["metadata"]["name"])
                    if seen[-1] == second:
                        return ev["type"]
            raise AssertionError(f"watch ended without {second}: {seen}")

        typ = await asyncio.wait_for(until_second(), timeout=WATCH_S)
        say(f"watch namespaces as {u}: {len(seen)} events, {typ} {second} "
            f"{time.perf_counter() - t0:.1f}s after its create; "
            f"graph {graph_delta()}")
        writer.close()
        got = await ns_list(u)
        assert got == before | {mine, second}, sorted(got)
    finally:
        kube.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        cfg.engine.close_compaction()


def phase_mesh(args, dims, cfg, cols):
    """``--chips 4``: the same lookups and checks through the mesh engine
    and through a single-device engine over the same store, compared."""
    import jax

    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.engine import mask_to_ids

    e = cfg.engine
    assert e.mesh is not None and dict(e.mesh.shape) == {
        "data": 2, "graph": 2}, f"mesh is {e.mesh}"
    say(f"mesh: {dict(e.mesh.shape)} over "
        f"{[d.id for d in e.mesh.devices.flat]}")
    t0 = time.perf_counter()
    e._backend(e.compiled())
    say(f"mesh placement: {time.perf_counter() - t0:.1f}s")
    single = Engine(schema=e.schema)
    single.store = e.store  # the same store, no second load
    assert single.mesh is None

    users = sample_users(args, dims)
    lookup_users = users[:N_LOOKUP_USERS]
    items = check_items(args, dims, users)
    now = time.time()
    tier0 = counter("engine_tier_mesh_fallback_total")
    cav0 = counter("engine_caveat_mesh_fallback_total")

    def run(engine, label):
        sets = []
        for u in lookup_users:
            t0 = time.perf_counter()
            mask, interner = engine.lookup_resources_mask(
                "pod", "view", "user", u, now=now)
            sets.append(frozenset(mask_to_ids(mask, interner)))
            say(f"{label}: lookup {u} visible={len(sets[-1])} in "
                f"{(time.perf_counter() - t0) * 1e3:.1f}ms")
        t0 = time.perf_counter()
        checks = engine.check_bulk(items, now=now)
        say(f"{label}: check_bulk[{len(items)}] first="
            f"{(time.perf_counter() - t0) * 1e3:.1f}ms")
        t0 = time.perf_counter()
        assert engine.check_bulk(items, now=now) == checks
        dt = time.perf_counter() - t0
        say(f"{label}: check_bulk steady={dt * 1e3:.1f}ms "
            f"({len(items) / dt:,.0f} checks/s) allowed={sum(checks)}")
        return sets, checks

    mesh_out = run(e, "mesh")
    assert e._sharded is not None, "mesh engine served from one device"
    single_out = run(single, "single")
    assert mesh_out[0] == single_out[0], "lookups differ: mesh vs single"
    assert mesh_out[1] == single_out[1], "checks differ: mesh vs single"
    assert all(mesh_out[0]) and any(mesh_out[1]) and not all(mesh_out[1]), \
        "sample must hold visible pods and both verdicts"
    say("parity: mesh == single device on every lookup and check")
    d_tier = counter("engine_tier_mesh_fallback_total") - tier0
    d_cav = counter("engine_caveat_mesh_fallback_total") - cav0
    say(f"mesh fall-backs: tier +{d_tier:.0f} caveat +{d_cav:.0f}")
    assert d_tier == 0 and d_cav == 0, "the mesh fell back to one device"
    used = [bytes_in_use(d) for d in jax.devices()]
    say(f"device bytes in use: {used}")
    assert all(b > 0 for b in used) or jax.devices()[0].platform != "tpu", \
        "a device of the mesh holds nothing"
    cfg.engine.close_compaction()


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated deployment and samples")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path against a single device")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal size; walks the phases without a TPU "
                         "but then prints no result and exits non-zero")
    args = ap.parse_args()

    from spicedb_kubeapi_proxy_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    t_all = time.perf_counter()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    cache_dir = place_compile_cache()
    compiles = CompileLog()
    with phase("device"):
        device = phase_device(args)
        say(f"compile cache: {cache_dir}")
    dims = TINY if args.tiny else FULL
    if args.chips == 4:
        with phase("load"):
            cfg, cols, _ = phase_load(args, dims, mesh="auto")
        with phase("mesh"):
            phase_mesh(args, dims, cfg, cols)
    else:
        with phase("load"):
            cfg, cols, kube = phase_load(args, dims)
        say(compiles.line())
        with phase("engine"):
            users, visible, oracle = phase_engine(args, dims, cfg, cols)
        say(compiles.line())
        with phase("served"):
            asyncio.run(phase_served(args, dims, cfg, kube, users,
                                     visible, oracle))
    say(compiles.line())
    say(f"total: {time.perf_counter() - t_all:.1f}s")
    if device["platform"] != "tpu":
        print("chip_smoke: rehearsal walked every phase, but the platform "
              f"is {device['platform']!r}: no result", file=sys.stderr)
        return 2
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as ex:
        if isinstance(ex.code, str):
            print(ex.code, file=sys.stderr)
        code = ex.code if isinstance(ex.code, int) else 2
    except BaseException:  # noqa: BLE001 - any failed phase fails the run
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # leave at once: a phase that failed may have left serving threads
    # behind, and a smoke that hangs holds the chip
    os._exit(code)

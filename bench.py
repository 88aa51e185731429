"""The seeded generator of the headline deployment, and nothing else.

``build_columns`` synthesizes BASELINE.md's headline graph (pods, users,
namespaces, groups, relationships) as the column dict ``Engine.bulk_load``
takes; ``build_engine`` loads it into a fresh engine. ``chip_smoke.py``
loads the deployment at full width through it and
``tests/test_chip_compile.py`` compiles the mesh program over it.

This file measures nothing: the benchmark is ``benchmark/run.py``
(``BENCHMARK.json``; numbers in ``PERF.md`` and ``PERF_LEDGER.jsonl``).
It keeps its name because the benchmark's own test imports it by that
name (``benchmark/tests/test_generate.py`` holds
``benchmark/configs/kube-rbac-10m/generate.py`` to this generator draw
for draw); ``ROADMAP.md`` D6a says when it goes.
"""

from __future__ import annotations

import sys
import time

import numpy as np

BENCH_SCHEMA = """
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
"""


# BENCH_SCHEMA plus conditional grants (the caveated mix of the mesh
# compile test): a share of the flat pod#viewer grants carry an
# IP-allowlist caveat, evaluated ON the mesh.
MESH_SCHEMA = """
use expiration

caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
  ip in allowed
}

definition user {}
definition group {
  relation member: user | group#member
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
  relation viewer: user | user with ip_allowlist
  permission edit = creator
  permission view = viewer + creator + namespace->view
}
"""

# the two stored contexts the caveated mix interleaves (two distinct
# (caveat, ctx) instances => an 8-row padded bucket with spare rows for
# incremental instance appends)
MESH_CTXS = ('{"allowed":["10.0.0.0/8","192.168.0.0/16"]}',
             '{"allowed":["10.0.0.0/8"]}')


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_columns(n_pods: int, n_users: int, n_ns: int, n_groups: int,
                  n_rels: int, seed: int = 0,
                  cav_share: float = 0.0) -> dict:
    """Synthesize the graph columnar-side (no per-row Python objects):
    the ``Engine.bulk_load`` column dict. ``cav_share`` > 0 marks that
    fraction of the flat pod#viewer grants with the ``ip_allowlist``
    caveat (the loading schema must declare it — MESH_SCHEMA),
    alternating the two MESH_CTXS stored contexts."""
    rng = np.random.default_rng(seed)
    pods = np.char.add("ns/p", np.arange(n_pods).astype(str))
    users = np.char.add("u", np.arange(n_users).astype(str))
    groups = np.char.add("g", np.arange(n_groups).astype(str))
    nss = np.char.add("ns", np.arange(n_ns).astype(str))

    keys = ["resource_type", "resource_id", "relation",
            "subject_type", "subject_id", "subject_relation"]
    if cav_share > 0:
        keys += ["caveat", "caveat_context"]
    cols = {k: [] for k in keys}

    def add(rt, rid, rl, st, sid, srl=None, cav=None, ctx=None):
        n = len(rid)
        cols["resource_type"].append(np.full(n, rt))
        cols["resource_id"].append(rid)
        cols["relation"].append(np.full(n, rl))
        cols["subject_type"].append(np.full(n, st))
        cols["subject_id"].append(sid)
        cols["subject_relation"].append(
            np.full(n, srl if srl is not None else ""))
        if cav_share > 0:
            cols["caveat"].append(
                cav if cav is not None else np.full(n, ""))
            cols["caveat_context"].append(
                ctx if ctx is not None else np.full(n, ""))

    # group membership: ~20 users per group
    gm = min(20 * n_groups, n_rels // 20)
    add("group", groups[rng.integers(n_groups, size=gm)], "member",
        "user", users[rng.integers(n_users, size=gm)])
    if cav_share > 0:
        # the mesh mix adds a SHORT nested-group chain (g1 ⊂ g0, ...):
        # a genuinely cyclic-core range too sparse for the dense-closure
        # peel, so the mesh program holds a loop with its collectives
        # (the shallow headline graph stratifies to a zero-iteration core)
        chain = int(min(6, n_groups - 1))
        if chain > 0:
            add("group", groups[np.arange(chain)], "member",
                "group", groups[np.arange(1, chain + 1)], "member")
    # namespace viewer grants via groups (2 per ns) — exercises the
    # group#member userset + namespace->view arrow rewrite chain
    nv = 2 * n_ns
    add("namespace", nss[rng.integers(n_ns, size=nv)], "viewer",
        "group", groups[rng.integers(n_groups, size=nv)], "member")
    # every pod lives in a namespace
    pod_ns = np.char.add("ns", rng.integers(n_ns, size=n_pods).astype(str))
    add("pod", pods, "namespace", "namespace", pod_ns)
    # the rest: flat pod#viewer@user direct grants, deduplicated
    n_flat = n_rels - gm - nv - n_pods
    pair = rng.integers(0, n_pods * n_users, size=int(n_flat * 1.01),
                        dtype=np.int64)
    pair = np.unique(pair)[:n_flat]
    rng.shuffle(pair)
    cav_col = ctx_col = None
    if cav_share > 0:
        idx = np.arange(len(pair))
        is_cav = idx < int(len(pair) * cav_share)
        cav_col = np.where(is_cav, "ip_allowlist", "")
        ctx_col = np.where(is_cav,
                           np.asarray(MESH_CTXS)[idx % len(MESH_CTXS)], "")
    add("pod", pods[pair // n_users], "viewer", "user",
        users[pair % n_users], cav=cav_col, ctx=ctx_col)

    return {k: np.concatenate(v) for k, v in cols.items()}


def build_engine(n_pods: int, n_users: int, n_ns: int, n_groups: int,
                 n_rels: int, seed: int = 0, cav_share: float = 0.0,
                 schema: str = BENCH_SCHEMA):
    """:func:`build_columns` bulk-loaded into a fresh engine over
    ``schema``; returns ``(engine, relationship count)``."""
    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.models import parse_schema

    rels_cols = build_columns(n_pods, n_users, n_ns, n_groups, n_rels,
                              seed, cav_share)
    total = len(rels_cols["resource_id"])
    log(f"built columns: {total} relationships"
        + (f" ({cav_share:.0%} of flat grants caveated)"
           if cav_share > 0 else ""))

    e = Engine(schema=parse_schema(schema))
    t0 = time.perf_counter()
    e.bulk_load(rels_cols)
    log(f"bulk_load: {time.perf_counter() - t0:.1f}s")
    return e, total

"""Headline benchmark: the BASELINE.md north-star config.

Filter a 100k-pod list response against a 10M-relationship graph on one
chip — the reference's prefilter/list hot path (SURVEY.md §3.3:
runLookupResources + filterList) executed as one slot-space reachability
query (`Engine.lookup_resources_mask`). Also reports bulk-check throughput
(reference CheckBulkPermissions path, SURVEY.md §3.2) on stderr.

Prints ONE JSON line on stdout:
    {"metric": ..., "value": p50_ms, "unit": "ms", "vs_baseline": ...}
vs_baseline is the 50 ms BASELINE.json target divided by the measured p50
(>1.0 means the target is beaten).

Usage: python bench.py [--quick]   (--quick: small graph, CPU-friendly)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional

import numpy as np

# BASELINE.md north-star target: <50ms p50 list filter on one v5e chip
BASELINE_TARGET_MS = 50.0

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


# BENCH_SCHEMA plus conditional grants: the mesh phase's caveated mix
# (ISSUE 15) — a share of the flat pod#viewer grants carry an
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
        # peel, so the fixpoint iterates a few hops and the K-step
        # convergence fuse has collectives to save — the shallow
        # headline graph stratifies to a zero-iteration core, which
        # would make the reduction unmeasurable
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


# Per-stage attribution (ISSUE 6): each stage maps to the histogram(s)
# its code path observes. Phases snapshot before/after and report the
# delta's p50/p99, so BENCH_*.json rows carry stage breakdowns instead of
# only end-to-end percentiles.
_STAGE_HISTOGRAMS = {
    "admission_wait": ("admission_queue_seconds",),
    "device": ("engine_check_seconds", "engine_lookup_seconds"),
    "upstream": ("proxy_upstream_seconds",),
}


def _stage_snapshot() -> dict:
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    out = {}
    for stage, names in _STAGE_HISTOGRAMS.items():
        out[stage] = {n: metrics.hist_snapshot(n) for n in names}
    return out


def _record_stage_breakdown(result: dict, key: str, before: dict) -> None:
    """p50/p99/p99.9 (ms) + sample count per stage for the window since
    ``before`` (a ``_stage_snapshot()``), merged across each stage's
    histograms. Stages whose window saw NO samples are omitted entirely
    (a zero-count row with null percentiles reads like a measurement);
    recorded percentiles are always finite — never Infinity, never a
    crash (the JSON contract)."""
    from spicedb_kubeapi_proxy_tpu.utils.metrics import (
        snapshot_delta_quantile,
    )

    after = _stage_snapshot()
    stages = {}
    for stage, names in _STAGE_HISTOGRAMS.items():
        n = 0
        p50 = p99 = p999 = None
        for name in names:
            b, a = before[stage][name], after[stage][name]
            if a is None:
                continue
            dn = a["n"] - (b["n"] if b else 0)
            if dn <= 0:
                continue
            n += dn
            q50 = snapshot_delta_quantile(b, a, 0.5)
            q99 = snapshot_delta_quantile(b, a, 0.99)
            q999 = snapshot_delta_quantile(b, a, 0.999)
            # multiple histograms per stage: keep the slower series'
            # percentile (an upper bound; exact merging would need raw
            # samples the registry deliberately doesn't retain)
            p50 = q50 * 1e3 if p50 is None else max(p50, q50 * 1e3)
            p99 = q99 * 1e3 if p99 is None else max(p99, q99 * 1e3)
            p999 = q999 * 1e3 if p999 is None else max(p999, q999 * 1e3)
        if n == 0:
            continue
        stages[stage] = {
            "n": n,
            "p50_ms": None if p50 is None else round(p50, 3),
            "p99_ms": None if p99 is None else round(p99, 3),
            "p999_ms": None if p999 is None else round(p999, 3),
        }
    result[key] = stages


def _dispatch_floor_ms(trials: int = 12) -> float:
    """Wall p50 of a no-op jitted dispatch+readback — the floor below
    which no synchronous device query can go."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((), jnp.int32)
    np.asarray(f(x))  # compile + warm
    lat = []
    for _ in range(trials):
        t0 = time.perf_counter()
        np.asarray(f(x))
        lat.append((time.perf_counter() - t0) * 1e3)
    return float(np.percentile(lat, 50))


def _chained_device_estimate(e, subjects, trials: int, k: int = 8):
    """Per-query device time for the list-filter query, via the slope of
    chained dispatches: lax.scan runs K fixpoints back-to-back on device
    (the carry makes query i+1 depend on query i's result, so they cannot
    overlap), and (wall_K - wall_1)/(K-1) cancels every fixed
    per-dispatch cost. Returns (ms_per_query, wall1_ms, wallK_ms, k)."""
    import jax
    import jax.numpy as jnp

    from spicedb_kubeapi_proxy_tpu.ops.reachability import (
        DEFAULT_MAX_ITERS,
        _next_bucket,
        _run,
    )

    cg = e.compiled()
    objs = e._objects_by_name()
    d = cg._dev()
    off = cg.offset_of("pod", "view")
    n = cg.type_sizes["pod"]
    q_pad = _next_bucket(n, 8)
    qs = np.full(q_pad, cg.M, dtype=np.int32)
    qs[:n] = off + np.arange(n, dtype=np.int32)
    qb = np.zeros(q_pad, dtype=np.int32)
    now_rel = np.float32(time.time() - cg.base_time)
    uniq = list(dict.fromkeys(subjects))
    picks = [uniq[i % len(uniq)] for i in range(k)]
    seed_stack = np.asarray(
        [[cg.encode_subject("user", u, None, objs)] for u in picks],
        dtype=np.int32,
    )  # [k, 1, 2]

    def chained(blocks, blocks_bits, src, dst, exp, cav,
                dsrc, ddst, dexp, dcav, cav_static,
                seed_stack, qs, qb, now_rel):
        def body(dep, seeds):
            # optimization_barrier ties each query's input to the previous
            # result in a way XLA cannot fold away (an arithmetic no-op
            # like `+ dep * 0` would be simplified out); together with
            # scan's sequential While lowering this guarantees the K
            # queries execute back-to-back, never overlapped
            seeds, _ = jax.lax.optimization_barrier((seeds, dep))
            out, _, _, _, _ = _run(cg.run_meta(), blocks, blocks_bits,
                                   src, dst, exp, cav,
                                   dsrc, ddst, dexp, dcav, cav_static, (),
                                   seeds, qs, qb, now_rel,
                                   jnp.float32(1.0),
                                   max_iters=DEFAULT_MAX_ITERS)
            return out.astype(jnp.int32).sum(), out[:1]
        dep, _ = jax.lax.scan(body, jnp.int32(0), seed_stack)
        return dep

    fn = jax.jit(chained)
    a = (d["blocks"], d["blocks_bits"], d["src"], d["dst"], d["exp"],
         d["cav"], d["dsrc"], d["ddst"], d["dexp"], d["dcav"],
         d["cav_static"])
    jqs, jqb = jnp.asarray(qs), jnp.asarray(qb)
    s1 = jnp.asarray(seed_stack[:1])
    sk = jnp.asarray(seed_stack)
    np.asarray(fn(*a, s1, jqs, jqb, now_rel))  # compile both shapes
    np.asarray(fn(*a, sk, jqs, jqb, now_rel))
    w1, wk = [], []
    for _ in range(trials):
        t0 = time.perf_counter()
        np.asarray(fn(*a, s1, jqs, jqb, now_rel))
        w1.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        np.asarray(fn(*a, sk, jqs, jqb, now_rel))
        wk.append((time.perf_counter() - t0) * 1e3)
    p1 = float(np.percentile(w1, 50))
    pk = float(np.percentile(wk, 50))
    return max((pk - p1) / (k - 1), 0.0), p1, pk, k


def run_suite(quick: bool, result: Optional[dict] = None) -> None:
    """BASELINE.md eval configs 3-5 (the headline run is config 2; config 1
    is the trivial ~10-relationship check, covered by every unit test).
    Results go to stderr AND, when ``result`` is given, into the emitted
    JSON as config3_*/config4_*/config5_* fields so a suite artifact is
    self-contained."""
    if result is None:
        result = {}
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.models import parse_schema

    rng = np.random.default_rng(3)
    scale = 10 if quick else 1

    # -- config 3: nested-group userset rewrites, ~1M rels ------------------
    n_users, n_g2, n_g1, n_g0, n_ns = (np.array(
        [100_000, 20_000, 2_000, 200, 200_000]) // scale).tolist()
    schema = parse_schema("""
definition user {}
definition group { relation member: user | group#member }
definition namespace {
  relation viewer: group#member
  permission view = viewer
}
""")
    cols = {k: [] for k in ("resource_type", "resource_id", "relation",
                            "subject_type", "subject_id", "subject_relation")}

    def add(rt, rid, rl, st, sid, srl):
        m = len(rid)
        cols["resource_type"].append(np.full(m, rt))
        cols["resource_id"].append(rid)
        cols["relation"].append(np.full(m, rl))
        cols["subject_type"].append(np.full(m, st))
        cols["subject_id"].append(sid)
        cols["subject_relation"].append(np.full(m, srl))

    users = np.char.add("u", np.arange(n_users).astype(str))
    g2 = np.char.add("g2-", np.arange(n_g2).astype(str))
    g1 = np.char.add("g1-", np.arange(n_g1).astype(str))
    g0 = np.char.add("g0-", np.arange(n_g0).astype(str))
    nss = np.char.add("ns", np.arange(n_ns).astype(str))
    # leaf membership: ~40 users per g2; g2 in g1; g1 in g0; ns viewer g0
    # (totals ~1M relationships at full scale, BASELINE config 3)
    m = 40 * n_g2
    add("group", g2[rng.integers(n_g2, size=m)], "member",
        "user", users[rng.integers(n_users, size=m)], "")
    add("group", g1[rng.integers(n_g1, size=n_g2)], "member",
        "group", g2, "member")
    add("group", g0[rng.integers(n_g0, size=n_g1)], "member",
        "group", g1, "member")
    add("namespace", nss, "viewer", "group",
        g0[rng.integers(n_g0, size=n_ns)], "member")
    e3 = Engine(schema=schema)
    merged = {k: np.concatenate(v) for k, v in cols.items()}
    total = len(merged["resource_id"])
    e3.bulk_load(merged)
    # a user that is definitely a leaf member, so visibility is non-trivial
    member = str(merged["subject_id"][0])
    t0 = time.perf_counter()
    mask, _ = e3.lookup_resources_mask("namespace", "view", "user", member)
    warm = time.perf_counter() - t0
    vis_member = int(mask.sum())
    lat = []
    iters = 0
    for u in rng.integers(n_users, size=11):
        t0 = time.perf_counter()
        fut = e3.lookup_resources_mask_async("namespace", "view", "user",
                                             f"u{u}")
        fut.result()
        lat.append((time.perf_counter() - t0) * 1e3)
        iters = max(iters, fut.iterations())
    # fixpoint_iters makes the closured-self-block win auditable in ANY
    # run (VERDICT r3 weak #2: pre-closure this config took 4 iterations;
    # the closure collapses the recursive-group chain to 1)
    log(f"[config 3] nested-group LookupResources @ {total} rels: "
        f"p50_wall={np.percentile(lat, 50):.1f}ms "
        f"fixpoint_iters={iters} (warmup {warm:.1f}s, "
        f"member {member} sees {vis_member}/{n_ns})")
    result["config3_rels"] = total
    result["config3_p50_wall_ms"] = round(float(np.percentile(lat, 50)), 3)
    result["config3_fixpoint_iters"] = iters

    # -- config 4: 10-hop tupleset-to-userset chains ------------------------
    # (built as chains of group#member USERSETS, with no arrow anywhere;
    # BASELINE's "tupleset-to-userset" form, rights inherited through a
    # recursive arrow, is the benchmark's measured ns-tree-10hop
    # deployment, benchmark/configs/ns-tree-10hop/)
    n_chains = 2_000 // scale
    cols = {k: [] for k in cols}
    hops = []
    for h in range(10):
        a = np.char.add(f"t{h}-", np.arange(n_chains).astype(str))
        b = np.char.add(f"t{h + 1}-", np.arange(n_chains).astype(str))
        hops.append((a, b))
    for h, (a, b) in enumerate(hops):
        add("group", a, "member", "group", b, "member")
    leaf = np.char.add("t10-", np.arange(n_chains).astype(str))
    add("group", leaf, "member", "user",
        np.char.add("u", np.arange(n_chains).astype(str)), "")
    add("namespace", np.char.add("ns", np.arange(n_chains).astype(str)),
        "viewer", "group",
        np.char.add("t0-", np.arange(n_chains).astype(str)), "member")
    e4 = Engine(schema=schema)
    merged = {k: np.concatenate(v) for k, v in cols.items()}
    total = len(merged["resource_id"])
    e4.bulk_load(merged)
    items = [CheckItem("namespace", f"ns{i}", "view", "user", f"u{i}")
             for i in rng.integers(n_chains, size=512).tolist()]
    e4.check_bulk(items)  # warm
    t0 = time.perf_counter()
    got = e4.check_bulk(items)
    dt = (time.perf_counter() - t0) * 1e3
    log(f"[config 4] 10-hop chains @ {total} rels: 512 checks in "
        f"{dt:.1f}ms ({all(got) and 'all allowed' or 'DENIALS!'})")
    result["config4_rels"] = total
    result["config4_512checks_ms"] = round(dt, 3)

    # -- config 5: multi-tenant concurrent lists ----------------------------
    n_ns, n_users, conc = (np.array([100_000, 10_000, 256]) // scale).tolist()
    schema5 = parse_schema("""
definition user {}
definition namespace {
  relation viewer: user
  permission view = viewer
}
""")
    cols = {k: [] for k in cols}
    nss = np.char.add("ns", np.arange(n_ns).astype(str))
    # ~20 viewers per namespace
    m = 20 * n_ns
    add("namespace", nss[rng.integers(n_ns, size=m)], "viewer",
        "user", np.char.add("u", rng.integers(n_users, size=m).astype(str)),
        "")
    e5 = Engine(schema=schema5)
    merged = {k: np.concatenate(v) for k, v in cols.items()}
    total = len(merged["resource_id"])
    e5.bulk_load(merged)
    e5.lookup_resources_mask("namespace", "view", "user", "u0")  # warm
    subs = [f"u{u}" for u in rng.integers(n_users, size=conc)]

    def run_conc():
        t0 = time.perf_counter()
        futs = [e5.lookup_resources_mask_async(
            "namespace", "view", "user", u) for u in subs]
        for f in futs:
            f.result()
        return time.perf_counter() - t0

    dt = run_conc()
    log(f"[config 5] {conc} concurrent ns-list queries @ {total} rels "
        f"x {n_ns} ns: {dt * 1e3:.0f}ms total = {conc / dt:.0f} "
        f"list-queries/s/chip ({dt * 1e3 / conc:.2f}ms/query amortized)")
    # same workload with cross-request dispatch fusion (the deployment
    # shape: a fleet of same-type list requests) — up to 8 subjects share
    # one fixpoint whose grid extraction is a single dynamic_slice
    e5.enable_lookup_batching()
    run_conc()  # warm the fused-grid trace (B=8 compile)
    dt_b = run_conc()
    log(f"[config 5+batcher] same workload, fused dispatches: "
        f"{dt_b * 1e3:.0f}ms total = {conc / dt_b:.0f} list-queries/s/chip "
        f"({dt_b * 1e3 / conc:.2f}ms/query amortized, "
        f"{dt / dt_b:.1f}x the unbatched run)")
    result["config5_conc"] = conc
    result["config5_ms_per_query"] = round(dt * 1e3 / conc, 3)
    result["config5_batched_ms_per_query"] = round(dt_b * 1e3 / conc, 3)


# ---------------------------------------------------------------------------
# Backend init and the one-JSON-line contract. The parent initialises JAX
# once, in-process: the chip belongs to one process, so nothing here starts
# a child that touches the default backend. A full-size run that finds no
# TPU is an error (one JSON line with ``error``, non-zero exit); ``--tiny``
# and ``--quick`` under JAX_PLATFORMS=cpu are the CPU contract runs. A
# watchdog THREAD (not a signal: a hang inside a C extension never returns
# to the bytecode loop, so a Python signal handler would wait forever)
# enforces an overall deadline and emits the partial JSON.
# ---------------------------------------------------------------------------

_EMIT_LOCK = threading.Lock()
_EMITTED = False


def emit(result: dict, code: int = 0, os_exit: bool = False) -> None:
    """Print the one JSON contract line exactly once, whoever gets there
    first (main path, signal handler, or watchdog thread)."""
    global _EMITTED
    with _EMIT_LOCK:
        if not _EMITTED:
            _EMITTED = True
            sys.stdout.write(json.dumps(result) + "\n")
            sys.stdout.flush()
    if os_exit:
        os._exit(code)


def _measure(args, result: dict) -> None:
    """The benchmark body; fills ``result`` in place so the caller can emit
    whatever was measured even if a later stage dies."""
    import jax

    from spicedb_kubeapi_proxy_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    place_compile_cache()
    # logged BEFORE backend init: the signal handlers are installed, so
    # once this line is visible a SIGTERM test can kill deterministically
    log("initialising the JAX backend")
    devs = jax.devices()
    backend = jax.default_backend()
    log(f"jax {jax.__version__} backend={backend} devices={devs}")
    result["backend"] = backend
    # "degraded" = not a chip run: the CPU contract sizes only
    degraded = backend != "tpu"
    result["degraded"] = degraded
    quick = args.quick or args.tiny
    if degraded and not quick:
        raise RuntimeError(
            f"no TPU (backend is {backend!r}): the full-size benchmark "
            "measures the chip; --tiny / --quick are the CPU contract runs")
    if args.macro_only:
        # the CI smoke path (make bench-macro): only the open-loop
        # macrobench, headline metric = the sweep's knee estimate
        _macro_phase(result, quick, args.tiny)
        macro = result["macro"]
        result["metric"] = (
            "open-loop macrobench goodput knee (offered op/s)"
            + (" [DEGRADED: cpu]" if degraded else ""))
        result["value"] = macro.get("knee_rps")
        result["unit"] = "op/s"
        result["vs_baseline"] = None
        return
    if args.tiny:
        n_pods, n_users, n_ns, n_groups, n_rels = 200, 100, 10, 10, 3_000
        args.trials = min(args.trials, 5)
    elif quick:
        n_pods, n_users, n_ns, n_groups, n_rels = 2_000, 500, 50, 50, 50_000
    else:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            100_000, 10_000, 1_000, 1_000, 10_000_000)

    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels)
    result["n_pods"], result["n_rels"] = n_pods, total

    t0 = time.perf_counter()
    cg = e.compiled()
    compile_s = time.perf_counter() - t0
    log(f"compile_graph: {compile_s:.1f}s (M={cg.M} slots, "
        f"E={cg.n_edges} edges)")
    result["graph_compile_s"] = round(compile_s, 2)

    # -- p50 list-filter latency: one user's visibility mask over all pods --
    rng = np.random.default_rng(1)
    subjects = [f"u{rng.integers(n_users)}" for _ in range(args.trials)]
    t0 = time.perf_counter()
    mask, _ = e.lookup_resources_mask("pod", "view", "user", subjects[0])
    log(f"warmup (jit compile + run): {time.perf_counter() - t0:.1f}s; "
        f"visible={int(mask.sum())}/{n_pods}")
    # per-stage attribution window: everything from here through the
    # repeat-traffic section lands in result["stages"] (p50/p99 per
    # stage from the span-backed histograms, warmup excluded)
    stage0 = _stage_snapshot()
    profiling = False
    if args.profile_dir:
        # device timeline for the measured queries (the program's stages
        # are annotated "sdbkp:<stage>", obs/trace.py; the fixpoint is the
        # module jit_sdbkp_fixpoint); view with tensorboard or xprof
        import jax

        try:
            jax.profiler.start_trace(args.profile_dir)
            profiling = True
            log(f"jax profiler trace -> {args.profile_dir}")
        except Exception as ex:  # noqa: BLE001 - profiling is best-effort
            log(f"profiler start failed (non-fatal): {ex}")
    # p99-tail diagnosis (VERDICT r3 weak #2: an unexplained 1.7x tail):
    # per-trial latencies plus the HOST-side suspects sampled around the
    # loop — full GEN-2 GC collections (gen-0/1 fire constantly and cost
    # microseconds; only gen-2 pauses reach milliseconds) and graph
    # recompiles/incremental updates. Device-side suspects (XLA
    # respecialization) are not observable host-side: the --profile-dir
    # trace is the tool for those.
    import gc

    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    def _gen2():
        return gc.get_stats()[2]["collections"]

    gc2_before = _gen2()
    compiles_before = metrics.counter("engine_graph_compiles_total").value
    incr_before = metrics.counter(
        "engine_graph_incremental_updates_total").value
    lat = []
    gc_flagged = 0
    for u in subjects:
        g0 = _gen2()
        t0 = time.perf_counter()
        mask, _ = e.lookup_resources_mask("pod", "view", "user", u)
        lat.append((time.perf_counter() - t0) * 1e3)
        if _gen2() != g0:
            gc_flagged += 1
    if profiling:
        import jax

        jax.profiler.stop_trace()
    p50_wall = float(np.percentile(lat, 50))
    p99_wall = float(np.percentile(lat, 99))
    log(f"list-filter latency over {len(lat)} trials: "
        f"p50_wall={p50_wall:.2f}ms p99_wall={p99_wall:.2f}ms")
    slowest = sorted(range(len(lat)), key=lambda i: -lat[i])[:3]
    log(f"tail diagnosis: slowest trials "
        f"{[(i, round(lat[i], 1)) for i in slowest]} (ms); "
        f"{gc_flagged}/{len(lat)} trials saw a gen-2 GC collection "
        f"({_gen2() - gc2_before} total); graph recompiles = "
        f"{int(metrics.counter('engine_graph_compiles_total').value - compiles_before)}, "
        f"incremental updates = "
        f"{int(metrics.counter('engine_graph_incremental_updates_total').value - incr_before)} "
        f"during the loop (device-side suspects: see --profile-dir)")
    result["lat_ms_trials"] = [round(x, 2) for x in lat]
    result["tail_gc_flagged_trials"] = gc_flagged

    # Dispatch floor: wall p50 of a no-op jitted scalar round trip. It
    # bounds EVERY synchronous device query from below, ours or anyone's.
    # Reported so the wall headline is legible: p50_wall_minus_floor_ms
    # is what the framework itself adds.
    floor = _dispatch_floor_ms()
    minus_floor = max(p50_wall - floor, 0.0)
    result["dispatch_floor_ms"] = round(floor, 3)
    result["p50_wall_minus_floor_ms"] = round(minus_floor, 3)
    # the 50ms BASELINE target describes chip+framework latency, so the
    # floor-excluded ratio is reported alongside (never as `value`).
    # Residuals below measurement jitter would publish noise as a huge
    # ratio, so they report nothing instead.
    if minus_floor >= 0.25:
        result["vs_baseline_excl_transport"] = round(
            BASELINE_TARGET_MS / minus_floor, 2)
    log(f"dispatch floor (no-op jit round trip): {floor:.2f}ms; "
        f"p50 minus floor = {minus_floor:.2f}ms")

    # The headline value is the MEASURED wall p50 (vs_baseline divides the
    # 50ms BASELINE target by it). The chained-dispatch slope — per-query
    # device compute with fixed dispatch overhead cancelled — is reported
    # as a separate field, never as the headline.
    result["metric"] = (
        f"p50 list-filter latency (wall), {n_pods} pods @ {total} rels, "
        f"1 chip" + (" [DEGRADED: cpu]" if degraded else ""))
    result["value"] = round(p50_wall, 3)
    result["unit"] = "ms"
    result["vs_baseline"] = round(BASELINE_TARGET_MS / p50_wall, 2)
    result["p50_wall_ms"] = round(p50_wall, 3)
    result["p99_wall_ms"] = round(p99_wall, 3)

    # fixpoint depth for this query shape (dispatch-depth analog)
    objs = e._objects_by_name()
    seeds = np.asarray(
        [cg.encode_subject("user", subjects[0], None, objs)], dtype=np.int32)
    off = cg.offset_of("pod", "view")
    n = cg.type_sizes["pod"]
    qf = cg.query_async(seeds, off + np.arange(n, dtype=np.int32),
                        np.zeros(n, dtype=np.int32))
    qf.result()
    iters = qf.iterations()
    result["fixpoint_iters"] = iters

    # -- fused-concurrency amortization on the HEADLINE shape --
    # The 50ms target describes a serving fleet, not a lone caller: with
    # cross-request batching on (proxy --lookup-batch-window), concurrent
    # same-type list prefilters fuse up to 8 subjects per fixpoint whose
    # grid extraction is one dynamic_slice. Measured here on the same 10M
    # graph so the driver-captured JSON carries the deployment number.
    try:
        conc_n = 16 if quick else 32
        e.enable_lookup_batching()
        conc_subs = [subjects[i % len(subjects)] for i in range(conc_n)]

        def run_conc_headline() -> float:
            t0 = time.perf_counter()
            futs = [e.lookup_resources_mask_async("pod", "view", "user", u)
                    for u in conc_subs]
            for f in futs:
                f.result()
            return (time.perf_counter() - t0) * 1e3

        run_conc_headline()  # warm the fused-grid (B=8) trace
        conc_ms = sorted(run_conc_headline() for _ in range(3))[1]
        amort = conc_ms / conc_n
        log(f"fused concurrency: {conc_n} concurrent pod-list queries "
            f"(batch window 2ms) in {conc_ms:.1f}ms = {amort:.2f}ms/query "
            f"amortized")
        result["concurrent_queries"] = conc_n
        result["concurrent_amortized_ms_per_query"] = round(amort, 3)
        result["vs_baseline_concurrent"] = round(BASELINE_TARGET_MS / amort, 2)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        log(f"fused-concurrency section failed (non-fatal): {ex}")
    finally:
        e.disable_lookup_batching()

    try:
        chain_est, p50_w1, p50_wk, k = _chained_device_estimate(
            e, subjects, trials=max(args.trials // 2, 5))
        log(f"chained-dispatch slope: wall(1)={p50_w1:.2f}ms "
            f"wall({k})={p50_wk:.2f}ms -> {chain_est:.2f}ms/query "
            f"device time")
        result["device_ms_estimate"] = round(chain_est, 3)
        # roofline: bytes touched per hop x hops / device time
        hb = cg.hop_bytes(batch=1)
        if chain_est > 0:
            tail = hb.get("tail_once", 0)
            streamed = hb["total"] * iters + tail
            eff_gbps = streamed / (chain_est * 1e-3) / 1e9
            # v5e HBM ~819 GB/s; v4 ~1228; CPU n/a — report raw GB/s and
            # let the reader place it on the roofline for the actual chip
            log(f"roofline: {hb['total'] / 1e6:.1f} MB/core-hop x {iters} "
                f"iters + {tail / 1e6:.0f} MB acyclic tail (once) = "
                f"{streamed / 1e6:.0f} MB streamed -> "
                f"{eff_gbps:.0f} GB/s effective "
                f"(core residual {hb['residual'] / 1e6:.1f} MB, core "
                f"blocks {hb['blocks'] / 1e6:.1f} MB per iter)")
            result["core_hop_mb"] = round(hb["total"] / 1e6, 1)
            result["tail_once_mb"] = round(tail / 1e6, 1)
            result["effective_gbps"] = round(eff_gbps, 1)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        log(f"chained-dispatch estimate failed (non-fatal): {ex}")

    # -- bulk-check throughput --
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem

    B, per = (8, 64) if quick else (64, 1024)
    items = [
        CheckItem("pod", f"ns/p{rng.integers(n_pods)}", "view",
                  "user", f"u{b}")
        for b in rng.integers(n_users, size=B)
        for _ in range(per)
    ]
    e.check_bulk(items[: B * per])  # warmup shape
    # p50 over several trials: a single trial spans 2-3x on this host
    # (bench_results/bulkcheck_regression_r5.md — the r3->r4 "regression"
    # was one slow trial), so one sample is not a measurement.
    bulk_trials = 5 if quick else 7
    bulk_rates = []
    for _ in range(bulk_trials):
        t0 = time.perf_counter()
        e.check_bulk(items)
        dt = time.perf_counter() - t0
        bulk_rates.append(len(items) / dt)
    bulk_rates.sort()
    checks_per_s = bulk_rates[len(bulk_rates) // 2]
    log(f"bulk check: {len(items)} checks, p50 over {bulk_trials} trials "
        f"= {checks_per_s:,.0f} checks/s/chip "
        f"(min {bulk_rates[0]:,.0f}, max {bulk_rates[-1]:,.0f})")
    result["checks_per_s_per_chip"] = round(checks_per_s)
    result["checks_per_s_min"] = round(bulk_rates[0])

    # -- interleaved write -> fully-consistent read (delta overlay) --
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.utils.metrics import (
        snapshot_delta_quantile,
    )

    wr = min(args.trials, 11)
    # the first write after bulk_load pays the store-index build
    # (vectorized hash + native radix sort, engine/store.py), and its
    # read pays the ONE unavoidable full recompile (bulk-loaded history
    # isn't in the watch log, so the overlay can't absorb it). Both are
    # reported separately; the measured loop below is the STEADY-STATE
    # write-churn path, which must run recompile-free on the overlay.
    t0 = time.perf_counter()
    e.write_relationships([WriteOp("touch", Relationship(
        "pod", f"ns/p{int(rng.integers(n_pods))}", "viewer",
        "user", f"u{int(rng.integers(n_users))}"))])
    t_first_write = time.perf_counter() - t0
    e.lookup_resources_mask("pod", "view", "user", subjects[0])
    # one warm overlay append outside the measurement: the first append
    # against a fresh base jit-compiles the O(write) device scatters
    # (dynamic_update_slice shapes), a once-per-process cost that is not
    # part of the steady state being claimed
    e.write_relationships([WriteOp("touch", Relationship(
        "pod", f"ns/p{int(rng.integers(n_pods))}", "viewer",
        "user", f"u{int(rng.integers(n_users))}"))])
    e.lookup_resources_mask("pod", "view", "user", subjects[0])
    # tail diagnosis for THIS phase (the read-only list-filter loop above
    # trivially reports 0 for both counters — the write path is where
    # they move): recompile / overlay-append counts plus the per-write
    # stage split (journal = store mutation + WAL, overlay-append = the
    # O(write) incremental graph fold, dispatch = the fully-consistent
    # read's device round trip)
    compiles_b = metrics.counter("engine_graph_compiles_total").value
    incr_b = metrics.counter("engine_graph_incremental_updates_total").value
    journal_b = metrics.hist_snapshot("store_write_seconds")
    overlay_b = metrics.hist_snapshot("engine_graph_incremental_seconds")
    wlat = []
    write_ms = []
    for i in range(wr):
        t0 = time.perf_counter()
        e.write_relationships([WriteOp("touch", Relationship(
            "pod", f"ns/p{int(rng.integers(n_pods))}", "viewer",
            "user", f"u{int(rng.integers(n_users))}"))])
        write_ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        e.lookup_resources_mask("pod", "view", "user",
                                subjects[i % len(subjects)])
        wlat.append((time.perf_counter() - t0) * 1e3)
    p50_aw = float(np.percentile(wlat, 50))
    raw_recompiles = int(
        metrics.counter("engine_graph_compiles_total").value - compiles_b)
    raw_incr = int(metrics.counter(
        "engine_graph_incremental_updates_total").value - incr_b)
    journal_a = metrics.hist_snapshot("store_write_seconds")
    overlay_a = metrics.hist_snapshot("engine_graph_incremental_seconds")
    breakdown = {"write_p50_ms": round(float(np.percentile(write_ms, 50)),
                                       3),
                 "dispatch_p50_ms": round(p50_aw, 3)}
    for k, b, a in (("journal", journal_b, journal_a),
                    ("overlay_append", overlay_b, overlay_a)):
        dn = (a["n"] if a else 0) - (b["n"] if b else 0)
        if dn > 0:
            q = snapshot_delta_quantile(b, a, 0.5)
            if q is not None:
                breakdown[f"{k}_p50_ms"] = round(q * 1e3, 3)
            breakdown[f"{k}_n"] = dn
    log(f"fully-consistent read after write: p50={p50_aw:.2f}ms "
        f"over {wr} write->read pairs; first write (index build) = "
        f"{t_first_write * 1e3:.0f}ms")
    log(f"tail diagnosis (read-after-write): graph recompiles = "
        f"{raw_recompiles}, incremental overlay updates = {raw_incr} "
        f"across {wr} writes; per-write breakdown "
        f"journal={breakdown.get('journal_p50_ms', '?')}ms "
        f"overlay-append={breakdown.get('overlay_append_p50_ms', '?')}ms "
        f"dispatch={breakdown['dispatch_p50_ms']}ms (p50)")
    result["p50_read_after_write_ms"] = round(p50_aw, 3)
    result["first_write_after_bulk_ms"] = round(t_first_write * 1e3, 1)
    result["read_after_write"] = {
        "recompiles": raw_recompiles,
        "incremental_updates": raw_incr,
        "write_breakdown": breakdown,
    }

    # -- repeat-traffic: decision-cache cold vs warm p50 + hit rate --
    # The serving-curve claim (ISSUE 2): repeat-heavy traffic (watch
    # fan-out, dashboard polling, fleet lists by one service account)
    # costs O(distinct queries per revision) dispatches, not O(requests).
    # Cold = first touch of each subject at this revision (full dispatch
    # through the cache's miss path); warm = the same subjects again.
    try:
        from spicedb_kubeapi_proxy_tpu.utils.metrics import (
            metrics as _metrics,
        )

        e.enable_decision_cache()
        rep_subs = list(dict.fromkeys(subjects))[:8]
        cold = []
        for u in rep_subs:
            t0 = time.perf_counter()
            e.lookup_resources_mask("pod", "view", "user", u)
            cold.append((time.perf_counter() - t0) * 1e3)
        hits0 = _metrics.counter("engine_decision_cache_hits_total",
                                 kind="lookup").value
        warm = []
        rounds = 3
        for _ in range(rounds):
            for u in rep_subs:
                t0 = time.perf_counter()
                e.lookup_resources_mask("pod", "view", "user", u)
                warm.append((time.perf_counter() - t0) * 1e3)
        hits = _metrics.counter("engine_decision_cache_hits_total",
                                kind="lookup").value - hits0
        hit_rate = hits / len(warm) if warm else 0.0
        cold_p50 = float(np.percentile(cold, 50))
        warm_p50 = float(np.percentile(warm, 50))
        log(f"repeat-traffic (decision cache): cold p50={cold_p50:.2f}ms, "
            f"warm (cached) p50={warm_p50:.3f}ms, hit rate="
            f"{hit_rate:.2f} over {len(warm)} repeats of "
            f"{len(rep_subs)} queries")
        result["repeat_cold_p50_ms"] = round(cold_p50, 3)
        result["repeat_warm_p50_ms"] = round(warm_p50, 4)
        result["repeat_hit_rate"] = round(hit_rate, 3)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        log(f"repeat-traffic section failed (non-fatal): {ex}")
    finally:
        e.disable_decision_cache()

    _record_stage_breakdown(result, "stages", stage0)

    # -- restart recovery: WAL replay throughput + time-to-ready --
    # Simulated crash (the --data-dir durability story, persistence/):
    # journal a write workload, abandon the process state WITHOUT a
    # checkpoint, and measure a cold store recovering from the WAL tail —
    # records/sec of replay and wall time until the store serves again.
    try:
        import shutil
        import tempfile

        from spicedb_kubeapi_proxy_tpu.engine.store import Store
        from spicedb_kubeapi_proxy_tpu.persistence import (
            Persistence,
            recover,
        )

        data_dir = tempfile.mkdtemp(prefix="bench-recovery-")
        try:
            src = Store()
            pers = Persistence.open(src, data_dir, wal_fsync="off",
                                    auto_checkpoint=False)
            n_recs = 2_000 if quick else 20_000
            for i in range(n_recs):
                src.write([WriteOp("touch", Relationship(
                    "pod", f"ns/p{i % max(n_pods, 1)}", "viewer",
                    "user", f"u{i % 997}"))])
            pers.wal.sync()  # the crash point: fsynced log, no checkpoint
            pers.close(final_checkpoint=False)
            t0 = time.perf_counter()
            cold = Store()
            res = recover(cold, data_dir)
            ready_s = time.perf_counter() - t0
            assert res.replayed_records == n_recs and len(cold) > 0
            rate = n_recs / max(ready_s, 1e-9)
            log(f"restart recovery: replayed {n_recs} WAL records in "
                f"{ready_s * 1e3:.0f}ms ({rate:.0f} records/s "
                "time-to-ready, no snapshot)")
            result["recovery_replayed_records"] = n_recs
            result["recovery_records_per_s"] = round(rate)
            result["recovery_time_to_ready_s"] = round(ready_s, 3)
        finally:
            shutil.rmtree(data_dir, ignore_errors=True)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        log(f"restart-recovery section failed (non-fatal): {ex}")

    # -- leader failover: SIGKILL the leader under write load --
    # The robustness headline (ISSUE 4): a replicated engine set
    # (--peers, parallel/failover.py) loses its leader mid-traffic; the
    # follower promotes with a fenced term and the client fails over.
    # Reported: wall time from the kill to the first post-failover ack,
    # plus how the window's requests split between fail-closed errors
    # (the proxy's 503 family) and successes. Skipped on --tiny (the
    # contract-test smoke must not pay two engine-host boots).
    if not args.tiny:
        try:
            _failover_phase(result, quick)
        except Exception as ex:  # noqa: BLE001 - aux measurement only
            log(f"failover section failed (non-fatal): {ex}")

    # -- admission control: overload behavior at 2x offered load --
    # (ISSUE 5 acceptance: goodput, per-class p99, per-tenant fairness,
    # shed accounting.) Skipped on --tiny like the failover phase.
    if not args.tiny:
        try:
            _admission_phase(result, quick)
        except Exception as ex:  # noqa: BLE001 - aux measurement only
            log(f"admission section failed (non-fatal): {ex}")

    # -- device-side caveat evaluation (ISSUE 9): caveated-mix cold/warm
    # check p50 with and without request context. Runs at EVERY scale
    # including --tiny (the result schema is contract-test-pinned).
    try:
        _caveat_phase(result, quick)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        log(f"caveat section failed (non-fatal): {ex}")

    # -- mesh-native hot path (ISSUE 15): caveats on-mesh + K-step fused
    # fixpoint at 1 vs 2 vs 8 devices over a caveated mix. Runs at EVERY
    # scale including --tiny (contract-pinned); CPU-only hosts measure
    # whatever device counts exist (the run-level degraded label
    # carries the provenance) and the full run records the
    # 100k-pod/10M-rel mesh point.
    try:
        _mesh_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"mesh section failed (non-fatal): {ex}")

    # -- masked-semiring SpMM core (ISSUE 17): forced pull vs push vs
    # auto over the caveated mix at EVERY scale (contract-pinned) —
    # the same-revision dense-phase baseline comes from the force-mode
    # knob, not a separate checkout
    try:
        _semiring_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"semiring section failed (non-fatal): {ex}")

    # -- tiered graph storage (ISSUE 18): all-resident vs 50%-budget
    # hot-working-set p50 (gate: tools/tiered_gate.py), plus a
    # beyond-budget point with cold-start parity and miss stalls. Runs
    # at EVERY scale (contract-pinned); full runs add the
    # 100M-relationship beyond-memory point.
    try:
        _tiered_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"tiered section failed (non-fatal): {ex}")

    # -- scale-out shard scaling (ROADMAP item 4 / ISSUE 11): the same
    # tuples behind 1 vs 2 vs 4 engine groups on loopback — single-shard
    # check p50 (counter-verified no-scatter), scatter-lookup p50, mixed
    # goodput. Runs at EVERY scale including --tiny (contract-pinned).
    try:
        _shard_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"shard section failed (non-fatal): {ex}")

    # -- online shard rebalancing (ISSUE 14): goodput on non-moving
    # slices during a live 3->4 group move, paused-vs-running mover
    # windows interleaved. Runs at EVERY scale (contract-pinned).
    try:
        _rebalance_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"rebalance section failed (non-fatal): {ex}")

    # -- elastic scale-out (ISSUE 20): frontier-exchange parity on a
    # cross-namespace reference schema WITHOUT replication (boundary
    # wire bytes + rounds recorded), then an autoscaler-applied 3->2
    # shrink under load with paused-vs-running goodput windows. Runs at
    # EVERY scale including --tiny (contract-pinned).
    try:
        _autoscale_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"autoscale section failed (non-fatal): {ex}")

    # -- live schema migration (ISSUE 19): additive + rewriting targets
    # applied under a sustained check/write mix — time-to-cut, cut
    # freeze, backfill volume, and check p50 during-vs-before. Runs at
    # EVERY scale including --tiny (contract-pinned).
    try:
        _migration_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"migration section failed (non-fatal): {ex}")

    # -- open-loop trace-shaped macrobench (ROADMAP item 5) --
    # Runs at EVERY scale including --tiny: the macro result schema is
    # contract-test-pinned, and the sweep is the harness later
    # engine-scaling PRs are judged against.
    try:
        _macro_phase(result, quick, args.tiny)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"macro section failed (non-fatal): {ex}")

    # -- macro with a live schema migration (ISSUE 19): the SAME-SEED
    # sweep re-run with a rewriting migration (caveat attached to
    # namespace#viewer) held open across every measured point, cut at
    # the end, folded into macro.migration.knee_ratio vs the baseline
    # just recorded. Runs at EVERY scale (contract-pinned).
    try:
        if "macro" in result:
            _macro_phase(result, quick, args.tiny,
                         result_key="_macro_migration",
                         migrate_live=True)
            _fold_macro_migration(result)
    except Exception as ex:  # noqa: BLE001 - aux measurement only
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"macro migration sub-run failed (non-fatal): {ex}")
    if not quick:
        # second scale point (full runs only): the same trace at 10k
        # namespaces, so the overlay-on/off goodput delta is recorded at
        # 2k AND 10k scale (BENCH captures whether the write-path win
        # survives a 5x larger graph)
        try:
            _macro_phase(result, quick, args.tiny,
                         result_key="macro_10k", n_ns_override=10_000)
        except Exception as ex:  # noqa: BLE001 - aux measurement only
            log(f"macro 10k scale point failed (non-fatal): {ex}")

    if args.remote_compare:
        # remote (tcp:// packed-bitmask wire) vs in-process list filter:
        # the directive-3 acceptance measurement — the remote hot path
        # should cost ~1 loopback RTT + one constant-size bitmask frame
        # (~16KB at a bucket-padded 100k-object space) over in-process,
        # NOT a multi-MB JSON id list
        import asyncio

        from spicedb_kubeapi_proxy_tpu.engine.remote import (
            EngineServer,
            RemoteEngine,
        )

        def remote_ids(remote, u):
            # pin the MASK wire: lookup_resources_mask raises instead of
            # silently falling back to the legacy JSON id-list op, so a
            # broken mask path can never masquerade as a measurement of it
            mask, interner = remote.lookup_resources_mask(
                "pod", "view", "user", u)
            if mask is None:
                return []
            return [interner.string(i)
                    for i in np.flatnonzero(mask).tolist()
                    if i < len(interner)]

        async def remote_compare():
            srv = EngineServer(e)
            port = await srv.start()
            remote = RemoteEngine("127.0.0.1", port)
            try:
                # warm: jit + id-table sync (the one-time transfer the
                # per-request path no longer pays)
                t0 = time.perf_counter()
                ids = await asyncio.to_thread(remote_ids, remote,
                                              subjects[0])
                warm_s = time.perf_counter() - t0
                # the ACTUAL wire frame for this lookup (meta + payload)
                meta, payload = await asyncio.to_thread(
                    remote._call_any, "lookup_mask", resource_type="pod",
                    permission="view", subject_type="user",
                    subject_id=subjects[0], subject_relation=None,
                    now=None)
                frame_b = 9 + len(json.dumps(meta)) + len(payload)
                lat_r, lat_l = [], []
                for u in subjects:
                    t0 = time.perf_counter()
                    await asyncio.to_thread(remote_ids, remote, u)
                    lat_r.append((time.perf_counter() - t0) * 1e3)
                for u in subjects:
                    t0 = time.perf_counter()
                    e.lookup_resources("pod", "view", "user", u)
                    lat_l.append((time.perf_counter() - t0) * 1e3)
                return len(ids), warm_s, frame_b, lat_r, lat_l
            finally:
                remote.close()
                await srv.stop()

        try:
            n_ids, warm_s, frame_b, lat_r, lat_l = \
                asyncio.run(remote_compare())
            r50 = float(np.percentile(lat_r, 50))
            l50 = float(np.percentile(lat_l, 50))
            log(f"remote-compare: in-process p50={l50:.2f}ms, "
                f"tcp:// p50={r50:.2f}ms (delta {r50 - l50:+.2f}ms; "
                f"measured mask frame {frame_b / 1024:.1f}KB, "
                f"{n_ids} allowed ids, warm sync {warm_s * 1e3:.0f}ms)")
            result["remote_list_filter_p50_ms"] = round(r50, 3)
            result["inproc_list_filter_p50_ms"] = round(l50, 3)
            result["remote_mask_frame_kb"] = round(frame_b / 1024, 1)
        except Exception as ex:  # noqa: BLE001 - aux measurement only
            log(f"remote-compare failed (non-fatal): {ex}")

    if args.suite:
        run_suite(quick, result)


_FAILOVER_WORKER = r"""
import os, sys
peer_id, port0, port1, data_dir, repo = sys.argv[1:6]
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, repo)
import jax
jax.config.update("jax_platforms", "cpu")
from spicedb_kubeapi_proxy_tpu.engine.remote import main
sys.exit(main([
    "--peers", "127.0.0.1:%s,127.0.0.1:%s" % (port0, port1),
    "--peer-id", peer_id,
    "--bind-port", port0 if peer_id == "0" else port1,
    "--token", "bench-fo", "--engine-insecure",
    "--data-dir", data_dir, "--wal-fsync", "always",
    "--mirror-heartbeat-seconds", "0.25",
    "--failover-boot-grace", "30",
]))
"""


def _failover_phase(result: dict, quick: bool) -> None:
    """Kill-the-leader under load: two CPU engine-host subprocesses in a
    --peers replication set, a FailoverEngine client writing at a fixed
    cadence, SIGKILL on the leader, and the wall-clock until writes ack
    again. Always CPU subprocesses — the phase measures failover
    machinery, and must not contend for the chip the headline owns."""
    import shutil
    import socket as _socket
    import tempfile
    import threading as _threading

    from spicedb_kubeapi_proxy_tpu.engine import WriteOp
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        FailoverEngine,
        RemoteEngine,
    )
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.utils.resilience import (
        DependencyUnavailable,
    )

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="bench-failover-")
    script = os.path.join(tmp, "worker.py")
    with open(script, "w") as f:
        f.write(_FAILOVER_WORKER)
    port0, port1 = free_port(), free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.abspath(__file__))

    def boot(pid):
        return subprocess.Popen(
            [sys.executable, script, str(pid), str(port0), str(port1),
             os.path.join(tmp, f"data{pid}"), repo],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=env, cwd=repo)

    def leader_port(budget=90.0):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            for port in (port0, port1):
                probe = RemoteEngine("127.0.0.1", port, token="bench-fo",
                                     timeout=2.0, connect_timeout=2.0,
                                     retries=0)
                try:
                    if probe.failover_state()["role"] == "leader":
                        return port
                except Exception:  # noqa: BLE001 - still booting
                    pass
                finally:
                    probe.close()
            time.sleep(0.3)
        raise RuntimeError("failover bench: no leader elected")

    procs = {0: boot(0), 1: boot(1)}
    client = None
    try:
        lport = leader_port()
        client = FailoverEngine(
            [("127.0.0.1", port0), ("127.0.0.1", port1)],
            token="bench-fo", connect_timeout=2.0, timeout=20.0,
            retries=0, probe_timeout=2.0, resolve_deadline=45.0)
        acked, failed_closed = [], [0]
        stop = _threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                try:
                    client.write_relationships([WriteOp(
                        "touch", Relationship(
                            "namespace", f"fo{i}", "creator", "user",
                            "bench", None, None))])
                    acked.append(time.monotonic())
                except (DependencyUnavailable, OSError):
                    failed_closed[0] += 1  # the proxy's 503 family
                i += 1
                time.sleep(0.02)

        t = _threading.Thread(target=writer, daemon=True)
        t.start()
        warm = 2.0 if quick else 5.0
        time.sleep(warm)
        if not acked:
            raise RuntimeError("failover bench: no writes acked pre-kill")
        pre_kill_acked = len(acked)
        victim = 0 if lport == port0 else 1
        t_kill = time.monotonic()
        procs[victim].kill()
        deadline = time.monotonic() + 60
        while (not acked or acked[-1] <= t_kill) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        stop.set()
        t.join(30)
        post = [a for a in acked if a > t_kill]
        if not post:
            raise RuntimeError("failover bench: writes never resumed")
        ready_s = post[0] - t_kill
        log(f"leader failover: time-to-ready {ready_s * 1e3:.0f}ms after "
            f"SIGKILL ({pre_kill_acked} acks pre-kill, {len(post)} post, "
            f"{failed_closed[0]} requests failed closed in the window, "
            "0 dropped silently)")
        result["failover_time_to_ready_s"] = round(ready_s, 3)
        result["failover_requests_failed_closed"] = failed_closed[0]
        result["failover_requests_acked_post"] = len(post)
    finally:
        if client is not None:
            client.close()
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _admission_phase(result: dict, quick: bool) -> None:
    """Overload behavior at 2x offered load, admission ON vs OFF
    (ISSUE 5 acceptance): one storm tenant offers 10x each normal
    tenant's load; admission ON must deliver higher within-SLO goodput,
    a bounded check p99, a per-tenant fairness ratio >= 0.5, and every
    rejection accounted in admission_shed_total{class=...} with a
    Retry-After and a bounded wait (never a hang)."""
    import threading as _th

    from spicedb_kubeapi_proxy_tpu.admission import (
        BULK_CHECK,
        CHECK,
        LOOKUP_PREFILTER,
        AdmissionController,
        AdmissionRejected,
    )
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics as _m

    rng = np.random.default_rng(7)
    n_ns, n_users = (400, 100) if quick else (2000, 400)
    schema = parse_schema("""
definition user {}
definition namespace {
  relation viewer: user
  permission view = viewer
}
""")
    cols = {k: [] for k in ("resource_type", "resource_id", "relation",
                            "subject_type", "subject_id", "subject_relation")}
    nss = np.char.add("ns", np.arange(n_ns).astype(str))
    m = 8 * n_ns
    cols["resource_type"].append(np.full(m, "namespace"))
    cols["resource_id"].append(nss[rng.integers(n_ns, size=m)])
    cols["relation"].append(np.full(m, "viewer"))
    cols["subject_type"].append(np.full(m, "user"))
    cols["subject_id"].append(
        np.char.add("u", rng.integers(n_users, size=m).astype(str)))
    cols["subject_relation"].append(np.full(m, ""))
    e = Engine(schema=schema)
    e.bulk_load({k: np.concatenate(v) for k, v in cols.items()})

    def op_check(i):
        e.check_bulk([CheckItem("namespace", f"ns{i % n_ns}", "view",
                                "user", f"u{i % n_users}")])

    def op_bulk(i):
        e.check_bulk([CheckItem("namespace", f"ns{(i + j) % n_ns}", "view",
                                "user", f"u{i % n_users}")
                      for j in range(32)])

    def op_lookup(i):
        e.lookup_resources_mask("namespace", "view", "user",
                                f"u{i % n_users}")

    # 70% checks / 15% bulk checks / 15% list lookups
    ops = ([(CHECK, op_check)] * 14 + [(BULK_CHECK, op_bulk)] * 3
           + [(LOOKUP_PREFILTER, op_lookup)] * 3)
    op_check(0), op_bulk(0), op_lookup(0)  # warm all three jit shapes

    # -- capacity probe: closed loop, then offer 2x of it --------------------
    def closed_loop(dur: float, nthreads: int = 8):
        stop = time.perf_counter() + dur
        lat: list = []
        lock = _th.Lock()

        def worker(w):
            i = w
            while time.perf_counter() < stop:
                cls, op = ops[i % len(ops)]
                t0 = time.perf_counter()
                op(i)
                with lock:
                    lat.append((cls.name, time.perf_counter() - t0))
                i += nthreads

        ts = [_th.Thread(target=worker, args=(w,)) for w in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return len(lat) / dur, lat

    # The load is CLOSED-LOOP per tenant (each thread issues its next
    # request as soon as the previous completes): the overload factor is
    # then structural — 28 worker threads against a knee measured at 8 —
    # instead of riding a rate estimate that a noisy shared host skews
    # several-fold between windows. The storm tenant runs 20 threads vs
    # 2 per normal tenant: 10x the offered load of each of the rest.
    closed_loop(0.4)  # settle: background index build, jit caches
    cap_rps, base_lat = closed_loop(1.0 if quick else 1.5)
    checks = sorted(dt for c, dt in base_lat if c == "check") or [0.005]
    base_p50 = checks[len(checks) // 2]
    slo = max(0.05, 4 * base_p50)
    n_normal = 4
    tenants = [(f"tenant{i}", 2) for i in range(n_normal)]
    tenants.append(("storm", 20))
    n_threads = sum(k for _, k in tenants)
    log(f"[admission] capacity ~{cap_rps:.0f} req/s at 8 threads, SLO "
        f"{slo * 1e3:.0f}ms; overload = {n_threads} closed-loop threads "
        "(storm tenant at 10x the rest)")

    avg_weight = sum(c.weight for c, _ in ops) / len(ops)
    unit_cap = cap_rps * avg_weight
    fair_share = unit_cap / len(tenants)  # cost units/s per tenant

    def run(ctrl, dur: float):
        start = time.perf_counter()
        stop_at = start + dur
        lock = _th.Lock()
        stats = {name: {"good": 0, "done": 0, "shed": 0}
                 for name, _ in tenants}
        lat_by_class: dict = {}
        shed_waits: list = []
        retry_after_missing = [0]

        def tenant_worker(name, seed):
            n = seed
            while time.perf_counter() < stop_at:
                cls, op = ops[n % len(ops)]
                n += 1
                t0 = time.perf_counter()
                try:
                    ticket = ctrl.acquire(name, cls) if ctrl else None
                    try:
                        op(n)
                    finally:
                        if ticket is not None:
                            ticket.release()
                    dt = time.perf_counter() - t0
                    with lock:
                        stats[name]["done"] += 1
                        if dt <= slo:
                            stats[name]["good"] += 1
                        lat_by_class.setdefault(cls.name, []).append(dt)
                except AdmissionRejected as ex:
                    wait = time.perf_counter() - t0
                    with lock:
                        stats[name]["shed"] += 1
                        shed_waits.append(wait)
                        if not ex.retry_after or ex.retry_after <= 0:
                            retry_after_missing[0] += 1

        threads = [_th.Thread(target=tenant_worker, args=(name, w * 37))
                   for name, k in tenants for w in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - start
        return stats, lat_by_class, shed_waits, retry_after_missing[0], wall

    def summarize(label, stats, lat_by_class, wall):
        good = sum(s["good"] for s in stats.values())
        per_tenant = [s["good"] for s in stats.values()]
        # fairness ratio: min/max of per-tenant COMPLETED service (the
        # share of engine time each tenant received). Every tenant is
        # backlogged (closed loop), so a fair scheduler serves them
        # near-equally (ratio -> 1) while an unguarded dispatch pool
        # serves them by thread count (ratio -> 2/20). Within-SLO
        # attainment is the goodput/p99 story, reported separately — a
        # storm whose requests wait longer is the scheduler WORKING
        per_done = [s["done"] for s in stats.values()]
        fairness = (min(per_done) / max(per_done)) \
            if max(per_done) else 0.0
        cl = sorted(lat_by_class.get("check", [0.0]))
        p99 = cl[min(len(cl) - 1, int(len(cl) * 0.99))] * 1e3
        shed = sum(s["shed"] for s in stats.values())
        offered = sum(s["done"] + s["shed"] for s in stats.values()) / wall
        log(f"[admission {label}] goodput={good / wall:.0f}/s of "
            f"{offered:.0f}/s offered (SLO {slo * 1e3:.0f}ms), "
            f"check p99={p99:.1f}ms, fairness={fairness:.2f} "
            f"(per-tenant good {per_tenant}, done "
            f"{[s['done'] for s in stats.values()]}), shed={shed}")
        return good / wall, p99, fairness, shed, offered

    dur = 2.5 if quick else 5.0
    shed_before = sum(
        _m.counter("admission_shed_total", **{"class": c}).value
        for c in ("check", "bulk-check", "lookup-prefilter",
                  "watch-recompute", "write-dtx"))
    # the limit stays CLAMPED near the closed-loop knee (the capacity
    # probe ran 8 threads, so ~8 ops of average weight saturate the
    # engine): under 2x offered load the queue is then never empty, every
    # grant goes through the fair scheduler, and admitted ops run near
    # baseline latency instead of contending 24-wide
    # decay SLOWER than the fair share and cap high: the capacity
    # estimate is noisy on a shared CPU host, and a too-generous refill
    # would zero every tenant's debt (collapsing the fair order to FIFO,
    # which the storm wins by volume). Low decay only lengthens the
    # storm's memory — ordering is work-conserving, so it never idles
    # capacity
    ctrl = AdmissionController(
        initial_concurrency=16.0, min_concurrency=8.0,
        max_concurrency=48.0,
        tenant_rate=fair_share / 4, tenant_burst=unit_cap * 2,
        tenant_depth=32, global_depth=128,
        queue_timeout=max(0.05, slo * 0.5))
    stage0 = _stage_snapshot()
    stats_on, lat_on, shed_waits, ra_missing, wall_on = run(ctrl, dur)
    _record_stage_breakdown(result, "admission_stages", stage0)
    good_on, p99_on, fair_on, shed_on, offered_on = summarize(
        "ON", stats_on, lat_on, wall_on)
    shed_after = sum(
        _m.counter("admission_shed_total", **{"class": c}).value
        for c in ("check", "bulk-check", "lookup-prefilter",
                  "watch-recompute", "write-dtx"))

    stats_off, lat_off, _, _, wall_off = run(None, dur)
    good_off, p99_off, fair_off, _, _ = summarize(
        "OFF", stats_off, lat_off, wall_off)

    max_wait = max(shed_waits) * 1e3 if shed_waits else 0.0
    accounted = int(shed_after - shed_before) == shed_on
    log(f"[admission] shed accounting: metric delta "
        f"{int(shed_after - shed_before)} vs {shed_on} client rejections "
        f"({'OK' if accounted else 'MISMATCH'}); max shed wait "
        f"{max_wait:.0f}ms; {ra_missing} rejections lacked Retry-After")
    result["admission_capacity_rps"] = round(cap_rps)
    result["admission_offered_rps"] = round(offered_on)
    result["admission_slo_ms"] = round(slo * 1e3, 1)
    result["admission_goodput_on"] = round(good_on, 1)
    result["admission_goodput_off"] = round(good_off, 1)
    result["admission_check_p99_ms_on"] = round(p99_on, 2)
    result["admission_check_p99_ms_off"] = round(p99_off, 2)
    result["admission_fairness_on"] = round(fair_on, 3)
    result["admission_fairness_off"] = round(fair_off, 3)
    result["admission_shed"] = shed_on
    result["admission_shed_accounted"] = accounted
    result["admission_max_shed_wait_ms"] = round(max_wait, 1)


_MACRO_SCHEMA = """
definition user {}
definition group {
  relation member: user
}
definition namespace {
  relation viewer: user | user:* | group#member
  permission view = viewer
}
"""

# The macro migration target (ISSUE 19): _MACRO_SCHEMA with a caveat
# attached to the live namespace#viewer relation — a REWRITING change
# whose affected closure is every stored viewer grant, so the in-sweep
# backfill and dual window carry real volume.
_MACRO_MIG_SCHEMA = _MACRO_SCHEMA.replace(
    "definition user {}",
    "caveat macro_probation(level int) {\n"
    "  level < 3\n"
    "}\n\n"
    "definition user {}").replace(
    "  relation viewer: user | user:* | group#member\n",
    "  relation viewer: user | user:* | group#member"
    " | user with macro_probation\n")

_MACRO_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: macro-ns-list-watch
match:
  - apiVersion: v1
    resource: namespaces
    verbs: [list, watch]
prefilter:
  - fromObjectIDNameExpr: "{{resourceId}}"
    lookupMatchingResources:
      tpl: "namespace:$#view@user:{{user.name}}"
"""


class _WatchStreamHarness:
    """Concurrent watch streams through the fused watch hub, drivable
    from loadgen worker THREADS: the hub and its watchers live on a
    dedicated asyncio loop thread (the serving shape — the proxy's hub
    runs on its event loop while engine work happens on executors).
    ``open()`` registers one more stream; beyond ``max_streams`` the
    oldest is recycled so a storm holds a bounded high-water population
    instead of leaking forever."""

    def __init__(self, engine, max_streams: int):
        import asyncio

        from spicedb_kubeapi_proxy_tpu.authz.watchhub import WatchHub
        from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import (
            parse_request_info,
        )
        from spicedb_kubeapi_proxy_tpu.rules.input import (
            ResolveInput,
            UserInfo,
        )
        from spicedb_kubeapi_proxy_tpu.rules.matcher import (
            MapMatcher,
            RequestMeta,
        )

        self.max_streams = max_streams
        self.opened = 0
        self._handles: list = []
        self._info = parse_request_info("GET", "/api/v1/namespaces",
                                        {"watch": ["true"]})
        matcher = MapMatcher.from_yaml(_MACRO_RULES)
        rules = matcher.match(RequestMeta.from_request(self._info))
        self._pf = next(p for r in rules for p in r.pre_filters)
        self._ResolveInput, self._UserInfo = ResolveInput, UserInfo
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="macro-watch-loop",
            daemon=True)
        self._thread.start()
        self.hub = WatchHub(engine, poll_interval=0.02)

    def open(self, user: str, timeout: float = 10.0) -> None:
        import asyncio

        fut = asyncio.run_coroutine_threadsafe(self._open(user),
                                               self._loop)
        fut.result(timeout=timeout)
        self.opened += 1

    async def _open(self, user: str) -> None:
        input = self._ResolveInput.create(
            self._info, self._UserInfo(name=user))
        handle = await self.hub.register(self._pf, input)
        self._handles.append(handle)
        if len(self._handles) > self.max_streams:
            await self.hub.unregister(self._handles.pop(0))

    @property
    def live_streams(self) -> int:
        return len(self._handles)

    def close(self) -> None:
        import asyncio

        async def teardown():
            for h in self._handles:
                try:
                    await self.hub.unregister(h)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            self._handles.clear()

        try:
            asyncio.run_coroutine_threadsafe(
                teardown(), self._loop).result(timeout=15)
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass

        async def cancel_stragglers():
            # the hub's pump/source tasks wind down via unregister, but
            # an in-flight wait may still be parked: cancel whatever is
            # left so stopping the loop doesn't warn about pending tasks
            me = asyncio.current_task()
            rest = [t for t in asyncio.all_tasks() if t is not me]
            for t in rest:
                t.cancel()
            await asyncio.gather(*rest, return_exceptions=True)

        try:
            asyncio.run_coroutine_threadsafe(
                cancel_stragglers(), self._loop).result(timeout=5)
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        if not self._thread.is_alive():
            self._loop.close()  # release the selector/self-pipe fds


def _caveat_phase(result: dict, quick: bool) -> None:
    """Conditional grants (ISSUE 9): a caveated-mix graph — 30% of the
    viewer tuples carry an IP-allowlist caveat — measured for cold and
    warm (decision-cached) bulk-check p50 WITH a satisfying request
    context, WITHOUT context (missing-context fail-closed denies), and
    against the uncaveated baseline. The acceptance bar is the
    caveated/uncaveated cold ratio (the caveat VM rides the same
    dispatch as the fixpoint, so it should be well under 1.5x)."""
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    n_docs = 256 if quick else 2048
    share = 0.3
    n_cav = int(n_docs * share)
    e = Engine(bootstrap="""
schema: |-
  caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }
  definition user {}
  definition doc {
    relation viewer: user | user with ip_allowlist
    permission view = viewer
  }
relationships: ""
""")
    names = np.char.add("d", np.arange(n_docs).astype(str))
    ctx_json = '{"allowed":["10.0.0.0/8","192.168.0.0/16"]}'
    e.bulk_load({
        "resource_type": np.full(n_docs, "doc"),
        "resource_id": names,
        "relation": np.full(n_docs, "viewer"),
        "subject_type": np.full(n_docs, "user"),
        "subject_id": np.full(n_docs, "alice"),
        "caveat": np.where(np.arange(n_docs) < n_cav,
                           "ip_allowlist", ""),
        "caveat_context": np.where(np.arange(n_docs) < n_cav,
                                   ctx_json, ""),
    })
    items_cav = [CheckItem("doc", f"d{i}", "view", "user", "alice")
                 for i in range(n_cav)]
    items_unc = [CheckItem("doc", f"d{i}", "view", "user", "alice")
                 for i in range(n_cav, 2 * n_cav)]
    req_ctx = {"ip": "10.1.2.3"}
    # correctness spot check + jit warmup (compiles happen HERE, not in
    # the timed loops)
    assert all(e.check_bulk(items_cav, context=req_ctx))
    assert all(e.check_bulk(items_unc))
    miss0 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    assert not any(e.check_bulk(items_cav))  # missing ctx: fail closed
    denied_missing = metrics.counter(
        "engine_caveat_denied_missing_context_total").value - miss0

    def p50(fn, trials=9):
        lat = []
        for _ in range(trials):
            t0 = time.perf_counter()
            fn()
            lat.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(lat, 50))

    cold_unc = p50(lambda: e.check_bulk(items_unc))
    cold_ctx = p50(lambda: e.check_bulk(items_cav, context=req_ctx))
    cold_noctx = p50(lambda: e.check_bulk(items_cav))
    e.enable_decision_cache()
    e.check_bulk(items_cav, context=req_ctx)  # prime
    e.check_bulk(items_unc)
    warm_ctx = p50(lambda: e.check_bulk(items_cav, context=req_ctx))
    warm_unc = p50(lambda: e.check_bulk(items_unc))
    e.disable_decision_cache()
    ratio = cold_ctx / max(cold_unc, 1e-9)
    result["caveats"] = {
        "n_tuples": int(n_docs),
        "caveated_share": share,
        "check_p50_uncaveated_ms": round(cold_unc, 3),
        "check_p50_caveated_ctx_ms": round(cold_ctx, 3),
        "check_p50_caveated_noctx_ms": round(cold_noctx, 3),
        "warm_p50_caveated_ctx_ms": round(warm_ctx, 4),
        "warm_p50_uncaveated_ms": round(warm_unc, 4),
        "caveated_over_uncaveated": round(ratio, 3),
        "missing_context_denials": int(denied_missing),
    }
    log(f"caveat mix: {n_docs} tuples ({share:.0%} caveated) "
        f"cold ctx p50 {cold_ctx:.2f}ms vs uncaveated {cold_unc:.2f}ms "
        f"(ratio {ratio:.2f}x), warm ctx {warm_ctx:.3f}ms")


def _mesh_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Mesh-native hot path (ISSUE 15): the caveated-mix graph served
    through ``Engine(mesh=...)`` at 1 vs 2 vs 8 devices (whatever the
    host actually has — CPU CI forces 8 virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; a bare
    CPU-only host measures its single device and labels the topology,
    riding the run-level ``[DEGRADED: cpu]`` convention instead of
    re-probing hardware). Per device count: list-filter p50 WITH request
    context, the K-step fused fixpoint's convergence-collective count
    (vs the single-device iteration count = the pre-fuse per-hop
    collectives), and a steady-churn window (caveated + plain touches,
    reused contexts) that must stay recompile-free on the resident
    shards. ``engine_caveat_mesh_fallback_total`` must not move: the
    caveat VM runs INSIDE the shard_map body now."""
    import jax

    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.parallel import make_mesh
    from spicedb_kubeapi_proxy_tpu.parallel.mesh import mesh_topology
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    devs = jax.devices()
    counts = [c for c in (1, 2, 8) if c <= len(devs)]
    if tiny:
        n_pods, n_users, n_ns, n_groups, n_rels = 200, 100, 10, 10, 3_000
        trials, churn = 3, 3
    elif quick:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            2_000, 500, 50, 50, 50_000)
        trials, churn = 5, 4
    else:
        # ROADMAP item 1's scale point: the headline 100k-pod / 10M-rel
        # build itself, with the caveated mix — measured, not claimed
        n_pods, n_users, n_ns, n_groups, n_rels = (
            100_000, 10_000, 1_000, 1_000, 10_000_000)
        trials, churn = 9, 6
    share = 0.3
    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels,
                            seed=2, cav_share=share, schema=MESH_SCHEMA)
    rng = np.random.default_rng(5)
    req_ctx = {"ip": "10.1.2.3"}
    cg = e.compiled()
    assert cg.caveats is not None and cg.caveats.metas, \
        "mesh phase needs a caveated graph"
    objs = e._objects_by_name()
    u0 = f"u{int(rng.integers(n_users))}"
    off = cg.offset_of("pod", "view")
    nq = cg.type_sizes["pod"]
    seeds = np.asarray([cg.encode_subject("user", u0, None, objs)],
                       dtype=np.int32)
    qs = off + np.arange(nq, dtype=np.int32)
    qb = np.zeros(nq, dtype=np.int32)
    fut = cg.query_async(seeds, qs, qb, context=req_ctx)
    fut.result()
    # the pre-fuse baseline at build (informational; each device-count
    # point re-measures against ITS revision — churn can add hops)
    iters_single = fut.iterations()

    fb0 = metrics.counter("engine_caveat_mesh_fallback_total").value
    points = {}
    for c in counts:
        mesh = make_mesh(c, devices=devs[:c])
        topo = mesh_topology(mesh)
        e.mesh = mesh
        e._sharded = None
        # warm: sharded build + shard_map jit compile + grid cache
        e.lookup_resources_mask("pod", "view", "user", u0,
                                context=req_ctx)
        # one write->read pair OUTSIDE the churn window: the first write
        # after bulk_load pays the store-index build and its read the
        # one unavoidable full recompile (bulk-loaded history isn't in
        # the watch log), plus the first overlay-append scatter compile
        e.write_relationships([WriteOp("touch", Relationship(
            "pod", f"ns/p{int(rng.integers(n_pods))}", "viewer",
            "user", f"u{int(rng.integers(n_users))}", None, None,
            "ip_allowlist", MESH_CTXS[0]))])
        e.lookup_resources_mask("pod", "view", "user", u0,
                                context=req_ctx)
        lat = []
        for _ in range(trials):
            u = f"u{int(rng.integers(n_users))}"
            t0 = time.perf_counter()
            e.lookup_resources_mask("pod", "view", "user", u,
                                    context=req_ctx)
            lat.append((time.perf_counter() - t0) * 1e3)
        p50 = float(np.percentile(lat, 50))
        # the one-per-hop baseline is re-measured at the SAME revision
        # the mesh conv-check query reads: the warm writes above may
        # have advanced the graph (a touch can extend the group chain
        # by a hop), and a stale pre-write baseline would undercount —
        # flaking the relative pin instead of measuring the reduction
        cg_now = e.compiled()
        objs_now = e._objects_by_name()
        seeds_now = np.asarray(
            [cg_now.encode_subject("user", u0, None, objs_now)],
            dtype=np.int32)
        off_now = cg_now.offset_of("pod", "view")
        nq_now = cg_now.type_sizes["pod"]
        qs_now = off_now + np.arange(nq_now, dtype=np.int32)
        qb_now = np.zeros(nq_now, dtype=np.int32)
        sfut = cg_now.query_async(seeds_now, qs_now, qb_now,
                                  context=req_ctx)
        sfut.result()
        iters_pt = sfut.iterations()
        sg = e._backend(cg_now)
        qf = sg.query_async(seeds_now, qs_now, qb_now, context=req_ctx)
        qf.result()
        checks = qf.conv_checks()
        # steady churn: caveated (reused stored contexts) + plain
        # touches with a fully-consistent mesh read after each — the
        # resident shards absorb everything (zero graph recompiles)
        compiles0 = metrics.counter("engine_graph_compiles_total").value
        upd0 = metrics.counter("engine_sharded_updates_total").value
        for i in range(churn):
            cav = i % 2 == 0
            e.write_relationships([WriteOp("touch", Relationship(
                "pod", f"ns/p{int(rng.integers(n_pods))}", "viewer",
                "user", f"u{int(rng.integers(n_users))}", None, None,
                "ip_allowlist" if cav else None,
                MESH_CTXS[i % len(MESH_CTXS)] if cav else None))])
            e.lookup_resources_mask("pod", "view", "user", u0,
                                    context=req_ctx)
        recompiles = int(metrics.counter(
            "engine_graph_compiles_total").value - compiles0)
        updates = int(metrics.counter(
            "engine_sharded_updates_total").value - upd0)
        points[str(c)] = {
            "devices": topo["devices"],
            "data": topo["data"],
            "graph": topo["graph"],
            "platform": topo["platform"],
            "list_p50_ms": round(p50, 3),
            "k_steps": int(sg.k_steps),
            "conv_checks": int(checks),
            "conv_checks_before": int(iters_pt),
            "churn_recompiles": recompiles,
            "churn_sharded_updates": updates,
        }
        log(f"mesh {c}d (data={mesh.shape['data']},"
            f"graph={mesh.shape['graph']}): list p50 {p50:.2f}ms, "
            f"conv collectives {checks} (K={sg.k_steps}; one-per-hop "
            f"baseline {iters_pt}), churn recompiles {recompiles}, "
            f"sharded updates {updates}")
    e.mesh = None
    e._sharded = None
    fallbacks = int(metrics.counter(
        "engine_caveat_mesh_fallback_total").value - fb0)
    result["mesh"] = {
        "backend": result.get("backend"),
        "devices_available": len(devs),
        "device_counts": counts,
        "n_pods": n_pods,
        "n_rels": total,
        "caveated_share": share,
        "fixpoint_iters_single": int(iters_single),
        "caveat_mesh_fallbacks": fallbacks,
        "points": points,
    }
    log(f"mesh phase: {total} rels ({share:.0%} caveated), device axis "
        f"{counts}, caveat mesh fallbacks {fallbacks}")


def _semiring_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Masked-semiring SpMM core (ISSUE 17): the caveated-mix graph's
    dense phase measured under every mode of the one propagation
    primitive — forced ``pull`` (the pre-semiring dense baseline, SAME
    revision via the force-mode knob), forced ``push`` (bit-packed
    contraction), and ``auto`` (the occupancy-switched ``lax.cond``).
    Per mode: bulk-check p50, list-filter p50, and the per-iteration
    push-vs-pull choices the fixpoint actually made (``push_steps`` out
    of ``iterations``). A second section pins the Pallas-vs-lax delta
    on the forced-pull dense path by flipping the ``SemiringDenseKernel``
    gate between freshly-traced dispatches; on a CPU host the MXU kernel
    never engages (both sides are the lax fallback), so the point is
    recorded with the run-level ``[DEGRADED: cpu]`` provenance instead
    of a fabricated speedup."""
    import jax

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.ops import bitprop, semiring
    from spicedb_kubeapi_proxy_tpu.utils.features import features
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    if tiny:
        n_pods, n_users, n_ns, n_groups, n_rels = 200, 100, 10, 10, 3_000
        trials, n_checks = 3, 64
    elif quick:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            2_000, 500, 50, 50, 50_000)
        trials, n_checks = 5, 512
    else:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            100_000, 10_000, 1_000, 1_000, 10_000_000)
        trials, n_checks = 9, 2048
    share = 0.3
    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels,
                            seed=3, cav_share=share, schema=MESH_SCHEMA)
    rng = np.random.default_rng(7)
    req_ctx = {"ip": "10.1.2.3"}
    items = [CheckItem("pod", f"ns/p{int(p)}", "view", "user", f"u{int(u)}")
             for p, u in zip(rng.integers(n_pods, size=n_checks),
                             rng.integers(n_users, size=n_checks))]
    u0 = f"u{int(rng.integers(n_users))}"
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds = np.asarray([cg.encode_subject("user", u0, None, objs)],
                       dtype=np.int32)
    off = cg.offset_of("pod", "view")
    nq = cg.type_sizes["pod"]
    qs = off + np.arange(nq, dtype=np.int32)
    qb = np.zeros(nq, dtype=np.int32)

    def p50(fn, n=trials):
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            lat.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(lat, 50))

    def list_once():
        e.lookup_resources_mask("pod", "view", "user", u0,
                                context=req_ctx)

    modes = {}
    for mode in ("pull", "push", "auto"):
        with semiring.force_mode(mode):
            # warm: the per-mode jitted run entry compiles HERE
            e.check_bulk(items, context=req_ctx)
            list_once()
            check_p50 = p50(lambda: e.check_bulk(items, context=req_ctx))
            list_p50 = p50(list_once)
            # a direct dispatch exposes the per-iteration mode choices
            fut = cg.query_async(seeds, qs, qb, context=req_ctx)
            fut.result()
            iters = int(fut.iterations())
            push = int(fut.push_steps())
            modes[mode] = {
                "check_p50_ms": round(check_p50, 3),
                "list_p50_ms": round(list_p50, 3),
                "iterations": iters,
                "push_steps": push,
                "pull_steps": int(max(iters - push, 0)),
            }
            log(f"semiring {mode}: check p50 {check_p50:.2f}ms, "
                f"list p50 {list_p50:.2f}ms, "
                f"steps push={push}/pull={max(iters - push, 0)} "
                f"of {iters}")
    degraded = jax.default_backend() != "tpu"
    # mode correctness spot check rides the bench too: the three forced
    # modes must answer bulk-check identically on this revision
    with semiring.force_mode("pull"):
        want = e.check_bulk(items, context=req_ctx)
    for m in ("push", "auto"):
        with semiring.force_mode(m):
            assert e.check_bulk(items, context=req_ctx) == want, m
    # Pallas-vs-lax on the forced-pull dense path: drop the cached
    # per-mode run entry so each side re-traces under its gate state
    d = cg._dev()

    def fresh_pull_p50():
        d.pop(("run", "pull"), None)
        with semiring.force_mode("pull"):
            list_once()  # compile
            return p50(list_once)

    pallas_engaged = bool(bitprop.dense_kernel_enabled())
    lat_kernel = fresh_pull_p50()
    features.set("SemiringDenseKernel", False)
    try:
        lat_lax = fresh_pull_p50()
    finally:
        features.reset()
        d.pop(("run", "pull"), None)
    pallas_delta = lat_lax / max(lat_kernel, 1e-9)
    base = modes["pull"]
    speedup_push = base["check_p50_ms"] / max(modes["push"]["check_p50_ms"],
                                              1e-9)
    speedup_auto = base["check_p50_ms"] / max(modes["auto"]["check_p50_ms"],
                                              1e-9)
    result["semiring"] = {
        "backend": result.get("backend"),
        "n_pods": n_pods,
        "n_rels": total,
        "caveated_share": share,
        "bulk_checks": n_checks,
        "crossover": float(getattr(cg, "spmm_crossover", 1.0)),
        # registry view of the same dispatch telemetry: the published
        # crossover gauge plus the cumulative per-dispatch mode choices
        # (engine._note_fixpoint_telemetry feeds these counters)
        "crossover_gauge": float(
            metrics.gauge("engine_semiring_crossover").value),
        "push_steps_total": int(metrics.counter(
            "engine_semiring_push_steps_total").value),
        "pull_steps_total": int(metrics.counter(
            "engine_semiring_pull_steps_total").value),
        "modes": modes,
        "dense_speedup_push_vs_pull": round(speedup_push, 3),
        "dense_speedup_auto_vs_pull": round(speedup_auto, 3),
        "pallas_engaged": pallas_engaged,
        "pallas_list_p50_ms": round(lat_kernel, 3),
        "lax_list_p50_ms": round(lat_lax, 3),
        "pallas_over_lax": round(pallas_delta, 3),
        "provenance": "[DEGRADED: cpu]" if degraded else "tpu",
    }
    log(f"semiring phase: {total} rels, dense-phase speedup "
        f"push {speedup_push:.2f}x / auto {speedup_auto:.2f}x vs forced "
        f"pull, pallas/lax {pallas_delta:.2f}x "
        f"(kernel {'on' if pallas_engaged else 'off — lax both sides'})"
        + (" [DEGRADED: cpu]" if degraded else ""))


def _tiered_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Tiered graph storage (ISSUE 18): the same graph measured
    all-resident and then under a device budget of ~50% of its dense
    block bytes (storage/tiers.py). The hot working set — repeated
    pod.view traffic — streams in on first demand, gets admitted, and
    steady-state p50 is pinned against the all-resident baseline
    (tools/tiered_gate.py enforces the <= 1.3x ratio in bench-smoke).
    A second, beyond-budget point shrinks the budget far below the
    working set so every dispatch pays the miss-stall path: cold-start
    latency, oracle parity, and a non-empty
    ``engine_tier_miss_stall_seconds`` histogram are recorded. Full
    runs add the 100M-relationship point — a graph whose dense blocks
    exceed any realistic single-device budget — at the same schema.
    On a CPU host the 'device' tier is host RAM too, so the point is
    recorded with the run-level ``[DEGRADED: cpu]`` provenance."""
    import jax

    import spicedb_kubeapi_proxy_tpu.ops.reachability as reach
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    if tiny:
        n_pods, n_users, n_ns, n_groups, n_rels = 200, 100, 10, 10, 3_000
        trials, n_checks = 3, 64
    elif quick:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            2_000, 500, 50, 50, 50_000)
        trials, n_checks = 5, 256
    else:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            100_000, 10_000, 1_000, 1_000, 10_000_000)
        trials, n_checks = 9, 1024
    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels,
                            seed=5)
    rng = np.random.default_rng(13)
    # the HOT working set: repeated pod.view checks over a confined pod
    # slice — demand closure activates only the blocks this traffic can
    # reach, so the rest of the graph never earns device bytes
    hot_pods = rng.integers(max(n_pods // 4, 1), size=n_checks)
    hot_users = rng.integers(n_users, size=n_checks)
    items = [CheckItem("pod", f"ns/p{int(p)}", "view", "user", f"u{int(u)}")
             for p, u in zip(hot_pods, hot_users)]

    def p50(fn, n=trials):
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            lat.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(lat, 50))

    # all-resident baseline — SAME revision, classic placement
    want = e.check_bulk(items)  # warm + oracle answers
    resident_p50 = p50(lambda: e.check_bulk(items))
    cg = e.compiled()

    def stall_count():
        snap = metrics.hist_snapshot("engine_tier_miss_stall_seconds")
        return int(sum(snap["counts"])) if snap else 0

    # size the budget off the real per-block footprint: enable with an
    # unbounded budget once to take the census AND measure the hot
    # working set (one warm pass admits exactly the demanded blocks),
    # then re-enable at ~50% of the graph — floored at the working set
    # so the hot slice genuinely fits (block granularity can make one
    # block the whole graph at small scales)
    census = cg.enable_tiering(budget_bytes=1 << 62)
    graph_bytes = census.total_bytes()
    e.check_bulk(items)
    demand_bytes = census.hot_bytes()
    from spicedb_kubeapi_proxy_tpu.storage.tiers import HEADROOM
    budget = max(graph_bytes // 2, int(demand_bytes / HEADROOM) + 1)
    tier = cg.enable_tiering(budget_bytes=budget)
    stalls0 = stall_count()
    t0 = time.perf_counter()
    got = e.check_bulk(items)  # cold start: demand-misses stream in
    cold_ms = (time.perf_counter() - t0) * 1e3
    parity_ok = bool(got == want)
    e.check_bulk(items)  # steady state from here: hot set admitted
    builds0 = reach._TRACE_BUILDS
    tiered_p50 = p50(lambda: e.check_bulk(items))
    zero_recompiles = bool(reach._TRACE_BUILDS == builds0)
    ratio = tiered_p50 / max(resident_p50, 1e-9)
    st = tier.stats()
    tier.publish_gauges()
    log(f"tiered: graph {graph_bytes}B, budget {budget}B, resident p50 "
        f"{resident_p50:.2f}ms, tiered p50 {tiered_p50:.2f}ms "
        f"({ratio:.2f}x), cold start {cold_ms:.1f}ms, "
        f"hot {st['hot_blocks']}/{st['blocks']} blocks, "
        f"recompiles={'none' if zero_recompiles else 'SOME'}")

    def beyond_point(engine, bb_items, bb_budget, bb_rels):
        """One beyond-budget sample: budget far under the working set,
        so the cold start AND steady traffic pay miss stalls."""
        bb_want = engine.check_bulk(bb_items)  # oracle before tiering
        cgx = engine.compiled()
        cgx.enable_tiering(budget_bytes=bb_budget)
        s0 = stall_count()
        tb = time.perf_counter()
        bb_got = engine.check_bulk(bb_items)
        bb_cold = (time.perf_counter() - tb) * 1e3
        engine.check_bulk(bb_items)  # steady point still streams
        return {
            "budget_bytes": int(bb_budget),
            "n_rels": int(bb_rels),
            "cold_start_ms": round(bb_cold, 3),
            "parity_ok": bool(bb_got == bb_want),
            "miss_stalls": stall_count() - s0,
        }

    if tiny or quick:
        beyond = beyond_point(e, items, max(graph_bytes // 100, 1), total)
    else:
        # the 100M-relationship point: dense blocks beyond any single
        # device's budget — a fresh engine so the headline numbers above
        # stay uncontaminated by its footprint
        be, btotal = build_engine(1_000_000, 100_000, 10_000, 10_000,
                                  100_000_000, seed=6)
        bb_items = [CheckItem("pod", f"ns/p{int(p)}", "view", "user",
                              f"u{int(u)}")
                    for p, u in zip(rng.integers(1_000_000, size=n_checks),
                                    rng.integers(100_000, size=n_checks))]
        be.check_bulk(bb_items)  # compile before the census
        bcg = be.compiled()
        bcg.enable_tiering(budget_bytes=1 << 62)
        bgb = bcg.tier.total_bytes()
        beyond = beyond_point(be, bb_items, max(bgb // 100, 1), btotal)
    log(f"tiered beyond-budget: cold start {beyond['cold_start_ms']:.1f}ms"
        f" over {beyond['n_rels']} rels, {beyond['miss_stalls']} miss "
        f"stalls, parity {'ok' if beyond['parity_ok'] else 'BROKEN'}")

    degraded = jax.default_backend() != "tpu"
    result["tiered"] = {
        "backend": result.get("backend"),
        "n_pods": n_pods,
        "n_rels": total,
        "graph_bytes": int(graph_bytes),
        "budget_bytes": int(budget),
        "resident_check_p50_ms": round(resident_p50, 3),
        "tiered_check_p50_ms": round(tiered_p50, 3),
        "tiered_over_resident": round(ratio, 3),
        "cold_start_ms": round(cold_ms, 3),
        "parity_ok": parity_ok,
        "zero_recompiles": zero_recompiles,
        "miss_stalls": stall_count() - stalls0,
        "hot_blocks": int(st["hot_blocks"]),
        "cold_blocks": int(st["cold_blocks"]),
        "hot_bytes": int(st["hot_bytes"]),
        "cold_bytes": int(st["cold_bytes"]),
        "beyond_budget": beyond,
        "provenance": "[DEGRADED: cpu]" if degraded else "tpu",
    }


_SHARD_SCHEMA = """
use expiration

definition user {}

definition group {
  relation member: user
}

definition namespace {
  relation viewer: user | group#member
  permission view = viewer
}

definition pod {
  relation namespace: namespace
  relation viewer: user
  permission view = viewer + namespace->view
}
"""


def _shard_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Scale-out scaling curve (ROADMAP item 4 / ISSUE 11): the SAME
    tuple set served by 1 vs 2 vs 4 engine groups over loopback TCP
    (one EngineServer per group, the scatter-gather planner in front).
    Reported per group count: single-shard check p50 (must route with
    NO scatter — per-shard op counters prove it), scatter-gathered
    lookup p50, and closed-loop mixed goodput. In-process asyncio
    servers: the phase measures planner + wire overhead and the scaling
    shape, not process boot. Full (non-quick) runs add a 10x scale
    point (~20k namespaces / ~500k relationships) so shard scaling is
    measured, not claimed."""
    if tiny:
        base = (12, 2, 8, 24, 6, 0.8)
    elif quick:
        base = (48, 4, 24, 80, 16, 1.5)
    else:
        base = (200, 8, 64, 200, 40, 3.0)

    result["shard"] = _shard_phase_at_scale(*base)
    if not quick and not tiny:
        # ROADMAP item 1's scale-point demand: shard scaling MEASURED
        # at a 10x point (~20k namespaces / ~500k relationships, 1 vs
        # 2 vs 4 groups), not extrapolated from the small curve. Full
        # runs only — the bulk loads dominate the phase's wall clock.
        try:
            result["shard"]["scale10x"] = _shard_phase_at_scale(
                n_ns=20_000, pods_per_ns=12, n_users=512,
                n_checks=120, n_lookups=8, good_s=3.0)
        except Exception as ex:  # noqa: BLE001 - aux measurement only
            log(f"shard 10x scale point failed (non-fatal): {ex}")



def _shard_phase_at_scale(n_ns: int, pods_per_ns: int, n_users: int,
                          n_checks: int, n_lookups: int,
                          good_s: float) -> dict:
    """One shard scaling point at an arbitrary size; returns the
    per-group-count schema ({1,2,4} groups) plus its sizes."""
    import asyncio
    import threading as _threading

    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.scaleout import (
        ShardMap,
        ShardedEngine,
    )
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    rng = np.random.default_rng(7)
    # one canonical tuple set, partitioned per map below
    ns_viewer = [(f"ns{i}", f"u{int(rng.integers(n_users))}")
                 for i in range(n_ns)]
    pod_rows = []
    for i in range(n_ns):
        for p in range(pods_per_ns):
            pod_rows.append((f"ns{i}/p{p}",
                             f"ns{i}",
                             f"u{int(rng.integers(n_users))}"))
    total_rels = len(ns_viewer) + 2 * len(pod_rows)

    def cols_for(smap, gi):
        cols = {k: [] for k in ("resource_type", "resource_id",
                                "relation", "subject_type",
                                "subject_id", "subject_relation")}

        def add(rt, rid, rl, st, sid):
            cols["resource_type"].append(rt)
            cols["resource_id"].append(rid)
            cols["relation"].append(rl)
            cols["subject_type"].append(st)
            cols["subject_id"].append(sid)
            cols["subject_relation"].append("")

        for ns, u in ns_viewer:  # global: replicated to every group
            add("namespace", ns, "viewer", "user", u)
        for pid, ns, u in pod_rows:
            if smap.shard_of("pod", pid) == gi:
                add("pod", pid, "namespace", "namespace", ns)
                add("pod", pid, "viewer", "user", u)
        return {k: np.asarray(v) for k, v in cols.items()}

    loop = asyncio.new_event_loop()
    loop_thread = _threading.Thread(target=loop.run_forever,
                                    daemon=True)
    loop_thread.start()

    def run_in_loop(coro, timeout=60.0):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(
            timeout)

    def scatter_count():
        tot = 0
        for gi in range(4):
            for op in ("check_bulk",):
                tot += metrics.counter(
                    "scaleout_ops_total", group=str(gi), op=op,
                    mode="scatter").value
        return tot

    groups_out = {}
    single_only = True

    def run_points():
        nonlocal single_only
        for k in (1, 2, 4):
            smap = ShardMap(version=1, groups=tuple(
                (("127.0.0.1", 0),) for _ in range(k)))
            servers, clients = [], []
            planner = None
            try:
                for gi in range(k):
                    eng = Engine(schema=parse_schema(_SHARD_SCHEMA))
                    eng.bulk_load(cols_for(smap, gi))
                    srv = EngineServer(eng)
                    port = run_in_loop(srv.start())
                    servers.append(srv)
                    clients.append(RemoteEngine("127.0.0.1", port))
                planner = ShardedEngine(smap, clients, journal=None)
                # warm every jit shape (per group) outside the timed loops
                planner.check(CheckItem("pod", "ns0/p0", "view",
                                        "user", "u0"))
                planner.lookup_resources("pod", "view", "user", "u0")

                sc0 = scatter_count()
                lat = []
                for i in range(n_checks):
                    pid, ns, u = pod_rows[i % len(pod_rows)]
                    t0 = time.perf_counter()
                    planner.check(CheckItem("pod", pid, "view", "user", u))
                    lat.append((time.perf_counter() - t0) * 1e3)
                check_p50 = float(np.percentile(lat, 50))
                no_scatter = scatter_count() == sc0
                single_only = single_only and no_scatter

                lat = []
                for i in range(n_lookups):
                    t0 = time.perf_counter()
                    planner.lookup_resources("pod", "view", "user",
                                             f"u{i % n_users}")
                    lat.append((time.perf_counter() - t0) * 1e3)
                lookup_p50 = float(np.percentile(lat, 50))

                # closed-loop mixed goodput: 8 threads, ~85% single-shard
                # checks / 15% scatter lookups
                done = [0] * 8
                stop = _threading.Event()

                def worker(wi):
                    j = wi
                    while not stop.is_set():
                        if j % 7 == 0:
                            planner.lookup_resources(
                                "pod", "view", "user", f"u{j % n_users}")
                        else:
                            pid, ns, u = pod_rows[j % len(pod_rows)]
                            planner.check(CheckItem("pod", pid, "view",
                                                    "user", u))
                        done[wi] += 1
                        j += 8

                threads = [_threading.Thread(target=worker, args=(wi,),
                                             daemon=True)
                           for wi in range(8)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                stop.wait(good_s)
                stop.set()
                for t in threads:
                    t.join(10)
                span = time.perf_counter() - t0
                goodput = sum(done) / max(span, 1e-9)
                groups_out[str(k)] = {
                    "check_p50_ms": round(check_p50, 3),
                    "scatter_lookup_p50_ms": round(lookup_p50, 3),
                    "goodput_ops_s": round(goodput, 1),
                    "single_shard_no_scatter": bool(no_scatter),
                }
                log(f"shard {k}g: check p50 {check_p50:.2f}ms "
                    f"(no_scatter={no_scatter}), scatter lookup p50 "
                    f"{lookup_p50:.2f}ms, goodput {goodput:.0f} op/s")
            finally:
                # close the planner (scatter pool + client sockets) and
                # stop the servers even when a measurement throws — a
                # leaked loop thread would keep spinning under every later
                # phase's latency numbers
                if planner is not None:
                    try:
                        planner.close()
                    except Exception:  # noqa: BLE001 - teardown best effort
                        pass
                for srv in servers:
                    try:
                        run_in_loop(srv.stop(), timeout=15.0)
                    except Exception:  # noqa: BLE001 - teardown best effort
                        pass
    try:
        run_points()
    finally:
        # the loop thread must die even when a point raises — a leaked
        # daemon loop would keep spinning under every later phase's
        # latency numbers
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(10)
    return {
        "n_ns": n_ns,
        "n_rels": total_rels,
        "single_shard_no_scatter": bool(single_only),
        "groups": groups_out,
    }


def _rebalance_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Online shard rebalancing (ISSUE 14): a live 3 -> 4 group GROW
    move over loopback TCP engine servers under sustained check load
    on NON-moving slices. Goodput is compared between interleaved
    PAUSED-mover and RUNNING-mover windows (coordinator pause/resume),
    so the ratio isolates the mover's interference from wall-clock
    noise; the phase also records rows moved, slice count, move
    duration, zero-acked-write-loss and the fail-open probe count."""
    import asyncio
    import statistics
    import threading as _threading

    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )
    from spicedb_kubeapi_proxy_tpu.engine.store import (
        RelationshipFilter,
        WriteOp,
    )
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.scaleout import (
        MapTransition,
        ShardMap,
        ShardedEngine,
        plan_moves,
    )

    if tiny:
        n_ns, win_s, n_windows = 24, 0.4, 2
    elif quick:
        n_ns, win_s, n_windows = 48, 0.5, 3
    else:
        n_ns, win_s, n_windows = 200, 0.7, 3

    old = ShardMap(version=1, groups=tuple(
        (("127.0.0.1", 0),) for _ in range(3)))
    new = ShardMap(version=2, groups=tuple(
        (("127.0.0.1", 0),) for _ in range(4)))

    loop = asyncio.new_event_loop()
    loop_thread = _threading.Thread(target=loop.run_forever,
                                    daemon=True)
    loop_thread.start()

    def run_in_loop(coro, timeout=60.0):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(
            timeout)

    servers, clients = [], []
    planner = None
    stop = _threading.Event()
    try:
        for _ in range(4):
            srv = EngineServer(Engine(schema=parse_schema(
                _SHARD_SCHEMA)))
            port = run_in_loop(srv.start())
            servers.append(srv)
            clients.append(RemoteEngine("127.0.0.1", port))
        planner = ShardedEngine(old, clients[:3], journal=None)
        writes = []
        for i in range(n_ns):
            writes.append(WriteOp("create", Relationship(
                "namespace", f"ns{i}", "viewer", "user",
                f"u{i % 8}")))
            writes.append(WriteOp("create", Relationship(
                "pod", f"ns{i}/p0", "namespace", "namespace",
                f"ns{i}")))
            writes.append(WriteOp("create", Relationship(
                "pod", f"ns{i}/p0", "viewer", "user", f"u{i % 8}")))
        planner.write_relationships(writes)
        t = MapTransition(old, new, plan_moves(old, new))
        moving = [f"ns{i}" for i in range(n_ns)
                  if t.slice_for_key(f"ns{i}", "pod") is not None]
        staying = [f"ns{i}" for i in range(n_ns)
                   if t.slice_for_key(f"ns{i}", "pod") is None]
        probes = staying[:8] or staying

        goodput = {"n": 0}
        fail_open = {"n": 0}

        def load_worker(wi):
            j = wi
            while not stop.is_set():
                ns = probes[j % len(probes)]
                try:
                    planner.check(CheckItem("pod", f"{ns}/p0", "view",
                                            "user", f"u{j % 8}"))
                    if planner.check(CheckItem(
                            "pod", f"{ns}/p0", "view", "user",
                            "intruder")):
                        fail_open["n"] += 1
                    goodput["n"] += 2
                except Exception:  # noqa: BLE001 - keep probing
                    # a transient error is a non-completion (it costs
                    # goodput, which is the point of the measurement) —
                    # it must NOT silently kill the probe thread, or the
                    # fail-open pin would pass vacuously
                    pass
                j += 4

        def writer():
            i = 0
            while not stop.is_set():
                ns = moving[i % len(moving)]
                try:
                    planner.write_relationships([WriteOp(
                        "touch", Relationship(
                            "pod", f"{ns}/p0", "viewer", "user",
                            f"mv{i}"))])
                except Exception:  # noqa: BLE001 - unacked: no claim
                    pass
                i += 1
                time.sleep(0.1)

        workers = [_threading.Thread(target=load_worker, args=(wi,),
                                     daemon=True) for wi in range(3)]
        wt = _threading.Thread(target=writer, daemon=True)
        for w in workers:
            w.start()
        wt.start()
        # warm jit shapes + caches before sampling
        time.sleep(0.6)

        t0 = time.perf_counter()
        coord = planner.begin_rebalance(
            new, new_clients={3: clients[3]},
            pace_seconds=0.2, batch_rows=16, poll_seconds=0.25)

        def window():
            goodput["n"] = 0
            w0 = time.monotonic()
            time.sleep(win_s)
            return goodput["n"] / (time.monotonic() - w0)

        time.sleep(0.3)
        paused_w, running_w = [], []
        for _ in range(n_windows):
            if coord._done.is_set():
                break
            coord.pause()
            time.sleep(0.05)
            paused_w.append(window())
            coord.resume()
            time.sleep(0.05)
            if coord._done.is_set():
                break
            running_w.append(window())
        coord.resume()
        ok = coord.wait(120.0)
        move_s = time.perf_counter() - t0
        stop.set()
        wt.join(5)
        for w in workers:
            w.join(5)
        if not ok or coord.error is not None:
            raise RuntimeError(f"mover failed: {coord.error}")

        # zero acked writes lost: every seeded tuple answers at V+1
        lost = 0
        for i in range(n_ns):
            if not planner.check(CheckItem(
                    "pod", f"ns{i}/p0", "view", "user", f"u{i % 8}")):
                lost += 1
        moved_rows = sum(
            1 for i in range(n_ns)
            if new.shard_for(f"ns{i}", "pod") == 3) * 2
        paused = (statistics.median(paused_w) if paused_w else None)
        running = (statistics.median(running_w) if running_w
                   else None)
        ratio = (round(running / paused, 3)
                 if paused and running else None)
        result["rebalance"] = {
            "n_ns": n_ns,
            "slices": len(t.slices),
            "rows_moved": int(moved_rows),
            "move_seconds": round(move_s, 3),
            "goodput_paused_ops_s": (round(paused, 1)
                                     if paused else None),
            "goodput_moving_ops_s": (round(running, 1)
                                     if running else None),
            "goodput_ratio_moving_over_paused": ratio,
            "zero_acked_write_loss": lost == 0,
            "fail_open_probes": int(fail_open["n"]),
        }
        log(f"rebalance: {moved_rows} rows / {len(t.slices)} slices "
            f"in {move_s:.2f}s, goodput paused "
            f"{paused or 0:.0f} vs moving {running or 0:.0f} op/s "
            f"(ratio {ratio}), lost={lost} "
            f"fail_open={fail_open['n']}")
    finally:
        stop.set()
        if planner is not None:
            try:
                planner.close()
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        for srv in servers:
            try:
                run_in_loop(srv.stop(), timeout=15.0)
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(10)


# ISSUE 20's cross-namespace reference schema: `team` is NAMESPACED
# (sharded — one copy, on its owner group) yet referenced as a userset
# subject by `doc` rows living in OTHER namespaces, i.e. usually on
# OTHER shards. Under the PR-11 contract this schema required `team`
# to be cluster-scoped (replicated everywhere); the frontier exchange
# resolves it with only boundary descriptors on the wire.
_FRONTIER_SCHEMA = """
definition user {}

definition team {
  relation member: user
}

definition doc {
  relation owner: team#member
  relation viewer: user
  permission view = viewer + owner
}
"""


def _autoscale_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Elastic scale-out (ISSUE 20): a cross-namespace reference schema
    served WITHOUT replication — frontier-exchange checks/lookups
    verified against an unsharded oracle, per-round boundary wire
    bytes and round counts recorded straight from the planner's
    counters — then an SLO-driven SHRINK (3 -> 2 groups) proposed and
    applied by the real AutoscaleController under sustained load, with
    paused-vs-running goodput windows, zero acked-write loss, and the
    fail-open probe count."""
    import asyncio
    import statistics
    import threading as _threading

    from spicedb_kubeapi_proxy_tpu.autoscale import (
        AutoscaleController,
        AutoscalePolicy,
        PolicyConfig,
        Signals,
    )
    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.engine.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )
    from spicedb_kubeapi_proxy_tpu.engine.store import (
        RelationshipFilter,
        WriteOp,
    )
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.scaleout import (
        FrontierConfig,
        ShardMap,
        ShardedEngine,
    )
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    if tiny:
        n_pairs, win_s, n_windows = 16, 0.4, 2
    elif quick:
        n_pairs, win_s, n_windows = 48, 0.5, 3
    else:
        n_pairs, win_s, n_windows = 160, 0.7, 3

    smap = ShardMap(version=1, groups=tuple(
        (("127.0.0.1", 0),) for _ in range(3)))

    loop = asyncio.new_event_loop()
    loop_thread = _threading.Thread(target=loop.run_forever,
                                    daemon=True)
    loop_thread.start()

    def run_in_loop(coro, timeout=60.0):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(
            timeout)

    def wire(direction):
        return metrics.counter("scaleout_frontier_wire_bytes_total",
                               direction=direction).value

    servers, clients = [], []
    planner = None
    oracle = Engine(schema=parse_schema(_FRONTIER_SCHEMA))
    stop = _threading.Event()
    try:
        for _ in range(3):
            srv = EngineServer(Engine(schema=parse_schema(
                _FRONTIER_SCHEMA)))
            port = run_in_loop(srv.start())
            servers.append(srv)
            clients.append(RemoteEngine("127.0.0.1", port))
        planner = ShardedEngine(smap, clients, journal=None,
                                frontier=FrontierConfig())
        # teams live in the a* namespaces, docs in b* — the owner
        # edge crosses namespaces (and so, usually, shards)
        writes = []
        for i in range(n_pairs):
            writes.append(WriteOp("create", Relationship(
                "team", f"a{i}/t", "member", "user", f"u{i % 8}",
                None)))
            writes.append(WriteOp("create", Relationship(
                "doc", f"b{i}/d", "owner", "team", f"a{i}/t",
                "member")))
            writes.append(WriteOp("create", Relationship(
                "doc", f"b{i}/d", "viewer", "user", f"v{i % 8}",
                None)))
        planner.write_relationships(writes)
        oracle.write_relationships(writes)

        # -- frontier parity vs the unsharded oracle, wire-accounted --
        scatter0, gather0 = wire("scatter"), wire("gather")
        rounds0 = (metrics.hist_snapshot("scaleout_frontier_rounds")
                   or {"n": 0, "max": 0})
        boundary0 = metrics.counter(
            "scaleout_frontier_boundary_tuples_total").value
        parity = 0
        mismatches = 0
        for i in range(min(n_pairs, 32)):
            for subj in (f"u{i % 8}", "intruder"):
                item = CheckItem("doc", f"b{i}/d", "view", "user",
                                 subj)
                if bool(planner.check(item)) == bool(
                        oracle.check(item)):
                    parity += 1
                else:
                    mismatches += 1
        lookup_ok = (sorted(planner.lookup_resources(
            "doc", "view", "user", "u0"))
            == sorted(oracle.lookup_resources(
                "doc", "view", "user", "u0")))
        rounds1 = (metrics.hist_snapshot("scaleout_frontier_rounds")
                   or {"n": 0, "max": 0})
        scatter_bytes = wire("scatter") - scatter0
        gather_bytes = wire("gather") - gather0
        boundary_tuples = metrics.counter(
            "scaleout_frontier_boundary_tuples_total").value - boundary0
        # the no-replication proof: every team tuple has exactly ONE
        # copy fleet-wide (its owner group) — the closure crossed
        # shards via the exchange, not via replicated reference data
        per_group_teams = [
            len(list(c.read_relationships(RelationshipFilter(
                resource_type="team")))) for c in clients]
        single_copy = sum(per_group_teams) == n_pairs

        # -- SLO-driven shrink applied by the real controller ---------
        staying = []
        for i in range(n_pairs):
            if smap.shard_for(f"b{i}", "doc") != 2:
                staying.append(i)
        probes = staying[:8] or list(range(n_pairs))
        goodput = {"n": 0}
        fail_open = {"n": 0}

        def load_worker(wi):
            j = wi
            while not stop.is_set():
                i = probes[j % len(probes)]
                try:
                    planner.check(CheckItem(
                        "doc", f"b{i}/d", "view", "user",
                        f"v{i % 8}"))
                    if planner.check(CheckItem(
                            "doc", f"b{i}/d", "view", "user",
                            "intruder")):
                        fail_open["n"] += 1
                    goodput["n"] += 2
                except Exception:  # noqa: BLE001 - keep probing
                    pass
                j += 3

        workers = [_threading.Thread(target=load_worker, args=(wi,),
                                     daemon=True) for wi in range(3)]
        for w in workers:
            w.start()
        time.sleep(0.4)

        controller = AutoscaleController(
            planner,
            AutoscalePolicy(PolicyConfig(
                min_groups=2, max_groups=4, hysteresis_ticks=2,
                cooldown_seconds=0.0)),
            mode="apply",
            signal_fn=lambda: Signals(
                n_groups=len(planner.groups), occupancy=0.05,
                burn_rate=0.0,
                rebalance_active=(planner.rebalance_status()
                                  is not None),
                gc_pending=any(
                    not t.gc_complete
                    for t in planner._archived_transitions)),
            coordinator_cfg={"pace_seconds": 0.2, "batch_rows": 16,
                             "poll_seconds": 0.25})
        t0 = time.perf_counter()
        ticks = 0
        proposal = None
        while proposal is None and ticks < 10:
            proposal = controller.tick(now=float(ticks))
            ticks += 1
        if proposal is None:
            raise RuntimeError("autoscaler never proposed the shrink")
        coord = planner._coordinator

        def window():
            goodput["n"] = 0
            w0 = time.monotonic()
            time.sleep(win_s)
            return goodput["n"] / (time.monotonic() - w0)

        paused_w, running_w = [], []
        for _ in range(n_windows):
            if coord is None or coord._done.is_set():
                break
            coord.pause()
            time.sleep(0.05)
            paused_w.append(window())
            coord.resume()
            time.sleep(0.05)
            if coord._done.is_set():
                break
            running_w.append(window())
        if coord is not None:
            coord.resume()
            ok = coord.wait(120.0)
            if not ok or coord.error is not None:
                raise RuntimeError(f"shrink mover failed: "
                                   f"{coord.error}")
        move_s = time.perf_counter() - t0
        stop.set()
        for w in workers:
            w.join(5)

        # zero acked writes lost across the shrink: every seeded doc
        # still answers — the DIRECT viewer and the CROSS-SHARD
        # frontier path both
        lost = 0
        for i in range(n_pairs):
            if not planner.check(CheckItem(
                    "doc", f"b{i}/d", "view", "user", f"v{i % 8}")):
                lost += 1
            if not planner.check(CheckItem(
                    "doc", f"b{i}/d", "view", "user", f"u{i % 8}")):
                lost += 1
        paused = (statistics.median(paused_w) if paused_w else None)
        running = (statistics.median(running_w) if running_w
                   else None)
        ratio = (round(running / paused, 3)
                 if paused and running else None)
        result["autoscale"] = {
            "n_teams": n_pairs,
            "n_docs": n_pairs,
            "frontier": {
                "parity_checks": parity,
                "parity_ok": mismatches == 0,
                "lookup_parity_ok": bool(lookup_ok),
                "exchanges": int(rounds1["n"] - rounds0["n"]),
                "rounds_max": int(rounds1["max"] or 0),
                "scatter_bytes": int(scatter_bytes),
                "gather_bytes": int(gather_bytes),
                "boundary_tuples": int(boundary_tuples),
                "reference_single_copy": bool(single_copy),
            },
            "shrink": {
                "proposal_action": proposal.action,
                "ticks_to_fire": ticks,
                "groups_after": len(planner.groups),
                "move_seconds": round(move_s, 3),
                "goodput_paused_ops_s": (round(paused, 1)
                                         if paused else None),
                "goodput_moving_ops_s": (round(running, 1)
                                         if running else None),
                "goodput_ratio_moving_over_paused": ratio,
                "zero_acked_write_loss": lost == 0,
                "fail_open_probes": int(fail_open["n"]),
            },
        }
        fr = result["autoscale"]["frontier"]
        log(f"autoscale: frontier parity {parity} checks "
            f"({mismatches} mismatches), {fr['exchanges']} exchanges "
            f"<= {fr['rounds_max']} rounds, "
            f"{fr['scatter_bytes']}+{fr['gather_bytes']}B boundary "
            f"wire; shrink {proposal.action} after {ticks} ticks in "
            f"{move_s:.2f}s, goodput ratio {ratio}, lost={lost} "
            f"fail_open={fail_open['n']}")
    finally:
        stop.set()
        if planner is not None:
            try:
                planner.close()
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        for srv in servers:
            try:
                run_in_loop(srv.stop(), timeout=15.0)
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(10)


# The two migration targets the phase applies in sequence.  Both are
# BENCH_SCHEMA derivatives built by string surgery so the bench schema
# stays the single source of truth: the ADDITIVE step grows pod with an
# auditor relation + audit permission (no existing relation changes →
# swap-at-a-revision, zero backfill), and the REWRITING step — layered
# on the additive result, since migrations are sequential — attaches a
# caveat to the live pod#viewer relation (allowed-set change on stored
# tuples → journaled backfill of the affected closure).
_MIG_ADDITIVE_SCHEMA = BENCH_SCHEMA.replace(
    "  permission edit = creator\n",
    "  relation auditor: user\n"
    "  permission audit = auditor\n"
    "  permission edit = creator\n")
_MIG_REWRITING_SCHEMA = _MIG_ADDITIVE_SCHEMA.replace(
    "definition user {}",
    "caveat bench_probation(level int) {\n"
    "  level < 3\n"
    "}\n\n"
    "definition user {}").replace(
    "  relation viewer: user\n",
    "  relation viewer: user | user with bench_probation\n")


def _migration_phase(result: dict, quick: bool, tiny: bool) -> None:
    """Live schema migration (ISSUE 19): an additive and then a
    rewriting migration applied to a serving engine under a sustained
    check/write mix at every scale. For each migration the phase records
    end-to-end time-to-cut, the cut freeze, backfilled row count, and
    check p50 DURING the migration window (compile + backfill + dual)
    against the same engine's p50 before any migration — the
    during-vs-before ratio is the number the no-downtime claim rides
    on. The migration holds at dual only long enough to collect the
    during-window samples, then cuts."""
    import jax

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship

    if tiny:
        n_pods, n_users, n_ns, n_groups, n_rels = 200, 100, 10, 10, 3_000
        n_checks, n_during = 64, 24
    elif quick:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            2_000, 500, 50, 50, 50_000)
        n_checks, n_during = 256, 64
    else:
        n_pods, n_users, n_ns, n_groups, n_rels = (
            20_000, 4_000, 400, 400, 2_000_000)
        n_checks, n_during = 512, 128
    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels,
                            seed=11)
    rng = np.random.default_rng(29)
    items = [CheckItem("pod", f"ns/p{int(p)}", "view",
                       "user", f"u{int(u)}")
             for p, u in zip(rng.integers(n_pods, size=n_checks),
                             rng.integers(n_users, size=n_checks))]
    e.check_bulk(items)  # warm the compiled graph

    def one_check(i: int) -> float:
        it = items[i % len(items)]
        t0 = time.perf_counter()
        e.check(it)
        return (time.perf_counter() - t0) * 1e3

    before = [one_check(i) for i in range(n_checks)]
    p50_before = float(np.percentile(before, 50))

    # live write churn for the whole phase: touches pod#viewer rows so
    # the rewriting window has dual-applied writes racing its backfill
    stop = threading.Event()
    writes = {"n": 0}

    def writer():
        i = 0
        while not stop.is_set():
            e.write_relationships([WriteOp("touch", Relationship(
                "pod", f"ns/p{i % n_pods}", "viewer",
                "user", f"u{(i * 7) % n_users}"))])
            writes["n"] += 1
            i += 1
            time.sleep(0.002)

    wt = threading.Thread(target=writer, daemon=True,
                          name="mig-bench-writer")
    wt.start()

    def migrate(schema_text: str, pause: float) -> dict:
        """Run one migration under the live mix: hold at dual until the
        during-window sample budget is met, then cut. Returns the
        per-migration result row."""
        e.begin_schema_migration(schema_text, hold_at_dual=True,
                                 backfill_pause=pause)
        during: list[float] = []
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            st = e.migration_status()
            phase = st["phase"] if st else None
            if phase in ("done", "failed", "aborted"):
                break
            if phase == "dual" and len(during) >= n_during:
                break
            during.append(one_check(len(during)))
        st = e.cut_schema_migration(wait=True)
        row = {
            "classification": st.get("classification"),
            "phase": st.get("phase"),
            "time_to_cut_ms": float(st.get("time_to_cut_ms") or 0.0),
            "freeze_ms": float(st.get("freeze_ms") or 0.0),
            "backfilled": int(st.get("backfilled") or 0),
            "affected": int(st.get("affected") or 0),
            "p50_during_ms": float(np.percentile(during, 50))
            if during else p50_before,
            "during_samples": len(during),
        }
        log(f"migration [{row['classification']}]: phase={row['phase']} "
            f"time_to_cut={row['time_to_cut_ms']:.1f}ms "
            f"freeze={row['freeze_ms']:.2f}ms "
            f"backfilled={row['backfilled']} "
            f"p50 during {row['p50_during_ms']:.3f}ms "
            f"vs before {p50_before:.3f}ms")
        return row

    try:
        additive = migrate(_MIG_ADDITIVE_SCHEMA, pause=0.0)
        # pace the rewriting backfill a little so the during window is a
        # genuine mid-backfill measurement, not an instant flip
        rewriting = migrate(_MIG_REWRITING_SCHEMA,
                            pause=0.005 if tiny else 0.002)
    finally:
        stop.set()
        wt.join(5)

    worst_during = max(additive["p50_during_ms"],
                       rewriting["p50_during_ms"])
    result["migration"] = {
        "n_rels": int(total),
        "writes": int(writes["n"]),
        "p50_before_ms": p50_before,
        "additive": additive,
        "rewriting": rewriting,
        "during_over_before_p50": (worst_during / p50_before
                                   if p50_before > 0 else 1.0),
        "provenance": ("[DEGRADED: cpu]"
                       if jax.default_backend() != "tpu"
                       else "tpu"),
    }


def _macro_phase(result: dict, quick: bool, tiny: bool,
                 result_key: str = "macro",
                 n_ns_override: Optional[int] = None,
                 migrate_live: bool = False) -> None:
    """The open-loop, trace-shaped macrobench (ROADMAP item 5): a mixed-
    op workload (checks, bulk checks, list prefilters, Table filtering,
    LookupSubjects, wildcard grants, write churn, watch streams through
    the fused hub) fired on a Poisson-plus-bursts arrival schedule with
    Zipf tenant skew, swept across offered-load multipliers of a probed
    closed-loop capacity. Emits the goodput-vs-offered-load curve, a
    knee estimate, per-class burst p99/p99.9, per-stage tail attribution
    from the trace ring, and per-class SLO attainment into the result
    JSON — the harness every engine-scaling PR after this one is judged
    against.

    ``migrate_live`` (ISSUE 19) re-runs the same-seed sweep with a
    REWRITING schema migration (caveat attached to the live
    namespace#viewer relation) held open across every measured point —
    backfill races the write churn, the dual window replays it — and
    cut after the sweep. The overlay-on/off comparison is skipped in
    this mode (one variable at a time); the caller folds the resulting
    knee into the baseline's ``migration.knee_ratio``."""
    import hashlib

    from spicedb_kubeapi_proxy_tpu.admission import (
        BULK_CHECK,
        CHECK,
        LOOKUP_PREFILTER,
        WATCH_RECOMPUTE,
        WRITE_DTX,
        AdmissionController,
    )
    from spicedb_kubeapi_proxy_tpu.authz.filterer import filter_body
    from spicedb_kubeapi_proxy_tpu.authz.lookups import AllowedSet
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine
    from spicedb_kubeapi_proxy_tpu.engine.store import WriteOp
    from spicedb_kubeapi_proxy_tpu.loadgen import run_sweep
    from spicedb_kubeapi_proxy_tpu.loadgen.schedule import (
        OP_BULK_CHECK,
        OP_CHECK,
        OP_LIST_PREFILTER,
        OP_LOOKUP_SUBJECTS,
        OP_TABLE,
        OP_WATCH_OPEN,
        OP_WILDCARD,
        OP_WRITE,
        trace_shaped_config,
    )
    from spicedb_kubeapi_proxy_tpu.models import parse_schema
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
    from spicedb_kubeapi_proxy_tpu.obs.slo import (
        SLOMonitor,
        default_objectives,
    )
    from spicedb_kubeapi_proxy_tpu.obs.trace import tracer
    from spicedb_kubeapi_proxy_tpu.rules.input import ResolveInput, UserInfo
    from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import (
        parse_request_info,
    )

    if tiny:
        n_ns, n_users, n_groups = 120, 80, 8
        table_rows, max_streams, dur, workers = 300, 64, 2.0, 16
    elif quick:
        n_ns, n_users, n_groups = 600, 300, 24
        table_rows, max_streams, dur, workers = 1_200, 256, 3.0, 32
    else:
        n_ns, n_users, n_groups = 2_000, 800, 64
        table_rows, max_streams, dur, workers = 5_000, 2_048, 5.0, 64
    if n_ns_override:
        # extra scale point (the full bench runs 2k AND 10k): resource
        # population scales, run shape (duration/workers) stays fixed so
        # the two points differ only in graph scale
        scale = n_ns_override / n_ns
        n_users = int(n_users * scale)
        n_groups = int(n_groups * scale)
        n_ns = n_ns_override
    # workers sized to the host: on a 2-core CI box, 16+ jax-busy
    # threads starve the dispatcher thread and every point reads late
    # (generator noise, not server signal)
    workers = max(4, min(workers, 4 * (os.cpu_count() or 4)))

    rng = np.random.default_rng(11)
    schema = parse_schema(_MACRO_SCHEMA)
    cols = {k: [] for k in ("resource_type", "resource_id", "relation",
                            "subject_type", "subject_id",
                            "subject_relation")}

    def add(rt, rid, rl, st, sid, srl=""):
        m = len(rid)
        cols["resource_type"].append(np.full(m, rt))
        cols["resource_id"].append(np.asarray(rid))
        cols["relation"].append(np.full(m, rl))
        cols["subject_type"].append(np.full(m, st))
        cols["subject_id"].append(np.asarray(sid))
        cols["subject_relation"].append(np.full(m, srl))

    nss = np.char.add("ns", np.arange(n_ns).astype(str))
    users = np.char.add("u", np.arange(n_users).astype(str))
    groups = np.char.add("g", np.arange(n_groups).astype(str))
    m = 8 * n_ns
    add("namespace", nss[rng.integers(n_ns, size=m)], "viewer",
        "user", users[rng.integers(n_users, size=m)])
    gm = 10 * n_groups
    add("group", groups[rng.integers(n_groups, size=gm)], "member",
        "user", users[rng.integers(n_users, size=gm)])
    add("namespace", nss[rng.integers(n_ns, size=n_groups)], "viewer",
        "group", groups, "member")
    # the wildcard slice: ~2% of namespaces are public (user:*) — the
    # still-unexercised grant form the mixed workload drives
    n_wild = max(2, n_ns // 50)
    wild_ns = nss[:n_wild]
    add("namespace", wild_ns, "viewer", "user", np.full(n_wild, "*"))
    e = Engine(schema=schema)
    e.bulk_load({k: np.concatenate(v) for k, v in cols.items()})

    # a Table response body at scale (rows named like the namespaces, so
    # the allowed-set filter drops real rows), built once per run
    table_body = json.dumps({
        "kind": "Table", "apiVersion": "meta.k8s.io/v1",
        "columnDefinitions": [{"name": "Name", "type": "string"}],
        "rows": [{"cells": [f"ns{i}"],
                  "object": {"metadata": {"name": f"ns{i}"}}}
                 for i in range(table_rows)],
    }).encode()
    table_info = parse_request_info("GET", "/api/v1/namespaces", {})
    table_input = ResolveInput.create(table_info, UserInfo(name="macro"))

    # -- op table (the mixed workload) ---------------------------------------
    def op_check(a):
        e.check_bulk([CheckItem("namespace", f"ns{a.ns_key % n_ns}", "view",
                                "user", f"u{a.key % n_users}")])

    def op_bulk(a):
        e.check_bulk([CheckItem("namespace", f"ns{(a.ns_key + j) % n_ns}",
                                "view", "user", f"u{a.key % n_users}")
                      for j in range(32)])

    def op_list(a):
        e.lookup_resources_mask("namespace", "view", "user",
                                f"u{a.key % n_users}")

    def op_table(a):
        ids = e.lookup_resources("namespace", "view", "user",
                                 f"u{a.key % n_users}")
        allowed = AllowedSet()
        for i in ids:
            allowed.add("", i)
        status, _body = filter_body(table_body, allowed, table_input)
        assert status == 200

    def op_lookup_subjects(a):
        e.lookup_subjects("namespace", f"ns{a.ns_key % n_ns}", "view",
                          "user")

    def op_wildcard(a):
        # a public (user:*) namespace must admit ANY subject, including
        # ones holding no direct tuples at all
        ok = e.check_bulk([CheckItem(
            "namespace", str(wild_ns[a.key % n_wild]), "view",
            "user", f"ghost{a.key}")])[0]
        assert ok, "wildcard grant failed"

    def op_write(a):
        e.write_relationships([WriteOp("touch", Relationship(
            "namespace", f"ns{a.ns_key % n_ns}", "viewer",
            "user", f"u{(a.key * 7) % n_users}"))])

    # the watch harness is ROTATED per sweep point (make_config below):
    # streams opened at 0.5x must not ride along as recompute background
    # load for the 3.5x point — each point's stream population is the
    # one its own offered load built
    harness_box = [_WatchStreamHarness(e, max_streams=max_streams)]
    watch_opened = [0]

    def op_watch(a):
        harness_box[0].open(f"u{a.key % n_users}")
        watch_opened[0] += 1

    for op in (op_check, op_bulk, op_list, op_table, op_lookup_subjects,
               op_wildcard, op_write):
        op(type("A", (), {"key": 0, "ns_key": 0})())  # warm every jit shape
    ops_raw = {
        OP_CHECK: op_check, OP_BULK_CHECK: op_bulk,
        OP_LIST_PREFILTER: op_list, OP_TABLE: op_table,
        OP_LOOKUP_SUBJECTS: op_lookup_subjects, OP_WILDCARD: op_wildcard,
        OP_WRITE: op_write, OP_WATCH_OPEN: op_watch,
    }

    # -- capacity probe (closed loop) anchors the offered-load axis ----------
    # The probe runs the REAL op mix (minus watch-open, which mutates
    # the stream population): anchoring to a checks-only rate would put
    # even the 0.5x sweep point past the knee of the heavier mixed
    # workload, and the curve would have no healthy region at all.
    import threading as _th

    from spicedb_kubeapi_proxy_tpu.loadgen.schedule import DEFAULT_MIX

    probe_ops = []
    for name, w in DEFAULT_MIX.items():
        fn = ops_raw[OP_CHECK if name == OP_WATCH_OPEN else name]
        probe_ops.extend([fn] * max(1, round(w * 100)))

    def closed_probe(dur_s: float, nthreads: int = 8) -> float:
        stop = time.perf_counter() + dur_s
        done = [0] * nthreads

        def w(i):
            k = i

            class A:  # minimal arrival stand-in for the op table
                key = 0
                ns_key = 0  # ops route on BOTH (the warmup above does
                # too); without it every probe thread died at its first
                # namespace-keyed op and the capacity anchor was garbage

            while time.perf_counter() < stop:
                A.key = k
                A.ns_key = k
                probe_ops[(k * 131) % len(probe_ops)](A)
                done[i] += 1
                k += nthreads

        ts = [_th.Thread(target=w, args=(i,)) for i in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return sum(done) / dur_s

    closed_probe(0.3)  # settle jit + index
    cap_rps = closed_probe(0.8 if tiny else 1.5)
    # base = 0.25x the probed mix capacity. The trace shape roughly
    # 1.5x-es the average rate over the baseline (bursts), so the
    # (0.5, 1, 2, 3.5) sweep spans ~0.2x..1.4x capacity on average with
    # bursts transiently far past it — healthy points below the knee,
    # genuine overload above, exactly the curve shape the knee estimator
    # needs
    base_rate = max(5.0, cap_rps * 0.25)
    log(f"[macro] closed-loop mixed capacity ~{cap_rps:.0f} op/s at 8 "
        f"threads; base offered rate {base_rate:.0f}/s")

    # SLOs: anchored to the probed baseline, floored so CI jitter does
    # not reclassify a healthy run (values recorded in the result)
    slo_s = {
        OP_CHECK: 0.05, OP_WILDCARD: 0.05, OP_BULK_CHECK: 0.15,
        OP_LIST_PREFILTER: 0.15, OP_TABLE: 0.25,
        OP_LOOKUP_SUBJECTS: 0.5, OP_WRITE: 0.25, OP_WATCH_OPEN: 0.5,
    }

    # one admission controller PER SWEEP POINT (rotated by make_config
    # below): the AIMD limit a 1.4x-overload run ratchets down to must
    # not leak into the next point's healthy-load measurement
    ctrl_box = [None]

    def fresh_ctrl():
        ctrl_box[0] = AdmissionController(
            initial_concurrency=16.0, min_concurrency=8.0,
            max_concurrency=64.0, tenant_rate=cap_rps / 4,
            tenant_burst=cap_rps * 2, tenant_depth=32, global_depth=256,
            queue_timeout=0.25)

    fresh_ctrl()
    op_cls = {
        OP_CHECK: CHECK, OP_WILDCARD: CHECK,
        OP_BULK_CHECK: BULK_CHECK, OP_LOOKUP_SUBJECTS: BULK_CHECK,
        OP_LIST_PREFILTER: LOOKUP_PREFILTER, OP_TABLE: LOOKUP_PREFILTER,
        OP_WRITE: WRITE_DTX, OP_WATCH_OPEN: WATCH_RECOMPUTE,
    }

    from spicedb_kubeapi_proxy_tpu.obs.trace import tracer as _tracer

    def admitted(name, fn):
        # only the homogeneous single-check class feeds the AIMD
        # limiter's latency probe (the engine-host round-6 rule): a
        # mixed feed of 32-item bulks, full-mask lookups, and watch
        # registrations reads op VARIETY as congestion and ratchets the
        # limit to the floor under healthy load
        observe = op_cls[name] is CHECK

        def run(a):
            with _tracer.span("admission_wait"):
                ticket = ctrl_box[0].acquire(a.tenant, op_cls[name])
            try:
                fn(a)
            finally:
                ticket.release(observe=observe)
        return run

    ops = {name: admitted(name, fn) for name, fn in ops_raw.items()}

    seed = 42
    multipliers = (0.5, 1.0, 2.0, 3.5)
    tenants = 6
    peak_streams = [0]

    def make_config(m):
        fresh_ctrl()  # each point starts with an unratcheted limiter
        peak_streams[0] = max(peak_streams[0],
                              harness_box[0].live_streams)
        harness_box[0].close()  # each point's own watch-stream population
        harness_box[0] = _WatchStreamHarness(e, max_streams=max_streams)
        return trace_shaped_config(dur, base_rate * m, tenants=tenants,
                                   seed=seed, burst_multiplier=3.0)

    from spicedb_kubeapi_proxy_tpu.loadgen import OpenLoopDriver
    from spicedb_kubeapi_proxy_tpu.loadgen.schedule import build_schedule

    # everything from the tracer reconfiguration on runs under ONE
    # try/finally: _measure treats a macro failure as non-fatal, so a
    # mid-phase exception must not leave the process-global tracer at
    # sweep settings or leak the watch loop thread into later phases
    prev = (tracer.sample, tracer.slow_s * 1e3,
            tracer._shards[0][1].maxlen * tracer.RING_SHARDS)
    monitor = None
    try:
        # tracing: tail-sampled ring sized for the sweep; slow/shed
        # macro ops are always kept (the attribution evidence)
        tracer.configure(sample=0.01, slow_ms=1e3 * min(slo_s.values()),
                         ring=1024)

        # warmup pass (discarded): every jit shape the mixed schedule
        # can draw compiles here, not inside the first measured point
        warm_cfg = trace_shaped_config(dur * 0.5, base_rate * 0.5,
                                       tenants=tenants, seed=7,
                                       burst_multiplier=3.0)
        OpenLoopDriver(ops, max_workers=workers, slo_s=slo_s,
                       trace_ops=False,
                       drain_timeout=10.0).run(build_schedule(warm_cfg),
                                               duration=warm_cfg.duration)

        # the warmup also drove op_watch: reset the counter AND rotate
        # the harness so the recorded stats (opened, live peak) cover
        # only the measured sweep
        watch_opened[0] = 0
        peak_streams[0] = 0
        harness_box[0].close()
        harness_box[0] = _WatchStreamHarness(e, max_streams=max_streams)

        if migrate_live:
            # the live rewriting migration spans the WHOLE measured
            # sweep: begin after warmup (its jit compiles must not hide
            # inside the migration window), hold at dual so every point
            # runs with dual-applied writes + catch-up replay, cut after
            e.begin_schema_migration(_MACRO_MIG_SCHEMA,
                                     hold_at_dual=True,
                                     backfill_pause=0.005)

        monitor = SLOMonitor(default_objectives(), windows=(30.0, 120.0),
                             tick_seconds=0.5)
        monitor.start()
        sweep = run_sweep(
            make_config,
            ops, multipliers, slo_s, max_workers=workers,
            trace_ops=True, drain_timeout=(8.0 if tiny else 15.0),
            on_point=lambda p: log(
                f"[macro x{p.multiplier}] offered={p.offered_rps:.0f}/s "
                f"completed={p.completed_rps:.0f}/s "
                f"goodput={p.goodput_rps:.0f}/s shed={p.shed_n} "
                f"err={p.error_n} late={p.late_n}"))

        # capture the overlay-ON system's numbers BEFORE the off sweep
        # runs: the deliberately-degraded comparison below must not bleed
        # into the recorded SLO attainment / watch-stream stats
        monitor_objectives = monitor.status()["objectives"]
        watch_opened_on = watch_opened[0]
        peak_streams_on = max(peak_streams[0],
                              harness_box[0].live_streams)

        # -- overlay on/off delta (ISSUE 8) -------------------------------
        # The same trace re-swept with IncrementalGraphUpdates off:
        # every write in the (write-heavy) reconcile burst then forces a
        # full graph re-encode before the next fully-consistent dispatch,
        # so the goodput gap between the two curves is exactly what the
        # device-resident delta overlay buys under sustained churn.
        # Reduced multiplier set — the comparison needs the healthy point
        # and the knee neighborhood, not the whole curve.
        from spicedb_kubeapi_proxy_tpu.utils.features import features

        off_mults = (1.0, 2.0)
        sweep_off = None
        mig_status = None
        if migrate_live:
            # cut INSIDE the measured configuration (tracer still wide
            # open) so the freeze histogram covers the real serving
            # shape, then skip the overlay-off comparison — this run
            # varies exactly one thing vs the baseline sweep
            mig_status = e.cut_schema_migration(wait=True)
        else:
            try:
                features.set("IncrementalGraphUpdates", False)
                # trace_ops matches the main sweep: the two curves must
                # be measured under identical instrumentation, or the
                # ratio reports tracing overhead as an overlay effect.
                # (At --tiny scale on a small CPU box the ratio is
                # smoke, not signal — a 120-namespace re-encode is ~ms;
                # the delta grows with graph scale.)
                sweep_off = run_sweep(
                    make_config, ops, off_mults, slo_s,
                    max_workers=workers, trace_ops=True,
                    drain_timeout=(8.0 if tiny else 15.0),
                    on_point=lambda p: log(
                        f"[macro overlay-off x{p.multiplier}] "
                        f"offered={p.offered_rps:.0f}/s "
                        f"goodput={p.goodput_rps:.0f}/s shed={p.shed_n} "
                        f"err={p.error_n} late={p.late_n}"))
            finally:
                features.set("IncrementalGraphUpdates", True)
    finally:
        if migrate_live:
            # don't leak a held-at-dual migration thread when a sweep
            # point raises — the happy path already cut above
            try:
                _st = e.migration_status()
                if _st and _st.get("phase") not in ("done", "aborted",
                                                    "failed"):
                    e.abort_schema_migration()
            except Exception:  # noqa: BLE001 - teardown best effort
                pass
        if monitor is not None:
            monitor.stop()
        peak_streams[0] = max(peak_streams[0],
                              harness_box[0].live_streams)
        harness_box[0].close()
        tracer.configure(sample=prev[0], slow_ms=prev[1], ring=prev[2])

    top_cfg = trace_shaped_config(dur, base_rate * multipliers[-1],
                                  tenants=tenants, seed=seed,
                                  burst_multiplier=3.0)
    digest = hashlib.sha256(repr([
        (round(a.t, 9), a.op, a.tenant, a.key, a.phase)
        for a in build_schedule(top_cfg)]).encode()).hexdigest()[:16]

    macro = sweep.to_dict()
    macro["seed"] = seed
    macro["schedule_digest"] = digest
    macro["capacity_rps"] = round(cap_rps, 1)
    macro["base_rate_rps"] = round(base_rate, 1)
    macro["scale"] = {"n_ns": n_ns, "n_users": n_users,
                      "n_groups": n_groups}
    if sweep_off is not None:
        off = sweep_off.to_dict()
        on_by_mult = {p["multiplier"]: p for p in macro["curve"]}
        macro["overlay_off"] = {
            "curve": off["curve"],
            "knee_rps": off.get("knee_rps"),
            "goodput_ratio_on_over_off": {
                str(m): round(
                    on_by_mult[m]["goodput_rps"]
                    / max(p_off["goodput_rps"], 1e-9), 2)
                for m in off_mults
                for p_off in [next(p for p in off["curve"]
                                   if p["multiplier"] == m)]
                if m in on_by_mult
            },
        }
        for m, ratio in macro["overlay_off"][
                "goodput_ratio_on_over_off"].items():
            log(f"[macro] overlay on/off goodput at x{m}: {ratio}x "
                f"(delta overlay vs per-write re-encode)")
    if mig_status is not None:
        macro["migration_live"] = {
            "classification": mig_status.get("classification"),
            "phase": mig_status.get("phase"),
            "time_to_cut_ms": float(
                mig_status.get("time_to_cut_ms") or 0.0),
            "freeze_ms": float(mig_status.get("freeze_ms") or 0.0),
            "backfilled": int(mig_status.get("backfilled") or 0),
        }
    macro["slo_ms"] = {k: round(v * 1e3, 1) for k, v in slo_s.items()}
    macro["watch_streams_opened"] = watch_opened_on
    macro["watch_streams_peak"] = peak_streams_on
    macro["slo_monitor"] = {
        o["name"]: {
            "burn_rate": o["windows"]["30s"]["burn_rate"],
            "attainment": o["windows"]["30s"]["attainment"],
        }
        for o in monitor_objectives
    }
    result[result_key] = macro
    knee_txt = ("~" if sweep.knee_saturated else ">= ") + (
        f"{sweep.knee_rps:.0f}" if sweep.knee_rps is not None else "?")
    log(f"[macro] knee {knee_txt} op/s offered"
        f"{'' if sweep.knee_saturated else ' (never reached)'}; "
        f"attainment {sweep.slo_attainment}; "
        f"{watch_opened_on} watch streams opened "
        f"(tail attribution: {sweep.tail_attribution.get('burst')} "
        f"burst, {sweep.tail_attribution.get('traces', 0)} traces)")


def _fold_macro_migration(result: dict) -> None:
    """Fold the migrate-live macro sub-run into the baseline macro dict
    as ``macro.migration`` — the same-seed knee ratio the ISSUE 19
    acceptance gate reads (>= 0.9x means a live rewriting migration
    costs the serving engine at most 10% of its knee)."""
    mig = result.pop("_macro_migration", None)
    base = result.get("macro")
    if not mig or not base:
        return
    base_knee = base.get("knee_rps")
    mig_knee = mig.get("knee_rps")
    if base_knee and mig_knee:
        knee_ratio = mig_knee / base_knee
        basis = "knee"
    else:
        # the sweep never saturated at this scale (small boxes often
        # don't) — fall back to goodput at the highest common offered-
        # load multiplier, same-seed schedules on both sides
        on = {p["multiplier"]: p["goodput_rps"] for p in base["curve"]}
        off = {p["multiplier"]: p["goodput_rps"] for p in mig["curve"]}
        common = sorted(set(on) & set(off))
        if not common:
            return
        m = common[-1]
        knee_ratio = off[m] / max(on[m], 1e-9)
        basis = f"goodput@x{m}"
    base["migration"] = {
        "knee_ratio": round(float(knee_ratio), 3),
        "basis": basis,
        "knee_rps": mig.get("knee_rps"),
        "curve": mig.get("curve"),
        **(mig.get("migration_live") or {}),
    }
    log(f"[macro] live-migration knee ratio {knee_ratio:.2f}x "
        f"({basis}) — rewriting migration held across the sweep, "
        f"backfilled={base['migration'].get('backfilled')} "
        f"freeze={base['migration'].get('freeze_ms')}ms")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small graph (CI / CPU smoke)")
    ap.add_argument("--tiny", action="store_true",
                    help="minimal graph (contract-test smoke, seconds)")
    ap.add_argument("--suite", action="store_true",
                    help="also run BASELINE eval configs 3-5")
    ap.add_argument("--macro-only", action="store_true",
                    help="run ONLY the open-loop macrobench sweep "
                         "(make bench-macro smoke; headline = knee)")
    ap.add_argument("--trials", type=int, default=21)
    ap.add_argument("--profile-dir",
                    help="write a jax profiler trace of the latency loop "
                         "here (tensorboard/xprof format)")
    ap.add_argument("--remote-compare", action="store_true",
                    help="also serve the engine over loopback TCP and "
                         "measure the remote list-filter (packed-bitmask "
                         "wire) against the in-process path")
    ap.add_argument("--deadline", type=float, default=None,
                    help="overall wall-clock budget (default 1200s, or "
                         "2400s with --suite; BENCH_DEADLINE overrides); "
                         "the watchdog emits whatever was measured and "
                         "exits when it expires")
    args = ap.parse_args()
    if args.deadline is None:
        env = os.environ.get("BENCH_DEADLINE")
        # the default budget covers the headline run; the suite's three
        # extra graph builds need their own allowance on top
        args.deadline = float(env) if env else (2400 if args.suite else 1200)

    # The contract: this process ALWAYS prints exactly one JSON line on
    # stdout, whatever happens (r01 crashed before printing; r02 was
    # SIGTERMed outside any try block). Partial results beat no results.
    result: dict = {
        "metric": "p50 list-filter latency (wall), not measured",
        "value": None, "unit": "ms", "vs_baseline": None,
    }

    def on_signal(signum, frame):  # noqa: ARG001
        result.setdefault("error", f"killed by signal {signum}")
        result["degraded"] = True
        emit(result, 128 + signum, os_exit=True)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    def watchdog():
        time.sleep(args.deadline)
        result.setdefault(
            "error", f"deadline {args.deadline:.0f}s exceeded; "
            "emitting partial result")
        # a deadline partial is not a backend downgrade: numbers captured
        # on a chip before the cutoff keep their provenance
        result["deadline_exceeded"] = True
        if result.get("backend") != "tpu":
            result["degraded"] = True
        log(f"WATCHDOG: deadline {args.deadline:.0f}s exceeded")
        emit(result, 2, os_exit=True)

    threading.Thread(target=watchdog, daemon=True).start()

    code = 0
    try:
        _measure(args, result)
    except BaseException as e:  # noqa: BLE001 - emit, then re-signal
        import traceback

        traceback.print_exc(file=sys.stderr)
        result["error"] = f"{type(e).__name__}: {e}"[:500]
        result["degraded"] = True
        code = 1
    emit(result, code)
    sys.exit(code)


if __name__ == "__main__":
    main()

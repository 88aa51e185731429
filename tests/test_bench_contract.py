"""bench.py contract tests: one JSON line on stdout, whatever happens.

An early round forfeited its perf evidence because bench.py crashed
before printing, another because it was SIGTERMed with no JSON flushed.
These tests drive the contract end-to-end as subprocesses, on the CPU
through the environment (``--tiny`` is the CPU contract run):

- the full result schema from a ``--tiny`` run
- SIGTERM mid-run (driver timeout kill)      -> partial JSON flushed
- deadline expiry (watchdog thread)          -> partial JSON flushed
- a full-size run without a TPU              -> ``error``, non-zero exit
"""

import json
import os
import signal
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _parse_only_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line: {lines!r}"
    return json.loads(lines[0])


def test_tiny_cpu_run_emits_full_schema():
    p = subprocess.run(
        [sys.executable, BENCH, "--tiny"],
        env=_env(), capture_output=True, text=True, timeout=300)
    out = _parse_only_line(p.stdout)
    assert p.returncode == 0
    assert out["degraded"] is True
    assert out["backend"] == "cpu"
    assert out["value"] is not None and out["value"] > 0
    # per-stage breakdown (ISSUE 6/7): stages with NO samples in the
    # window are omitted entirely; recorded stages have int counts >= 1
    # and finite-or-null percentiles including p99.9 — never Infinity
    # (json.loads above already rejects bare Infinity-producing bugs at
    # the parse level only for NaN-strict parsers, so check explicitly)
    stages = out["stages"]
    assert set(stages) <= {"admission_wait", "device", "upstream"}
    for st in stages.values():
        assert isinstance(st["n"], int) and st["n"] >= 1
        for k in ("p50_ms", "p99_ms", "p999_ms"):
            v = st[k]
            assert v is None or (isinstance(v, (int, float))
                                 and v == v and abs(v) != float("inf"))
    # the tiny run exercises the engine: the device stage must have
    # samples and real percentiles
    assert stages["device"]["n"] > 0
    assert stages["device"]["p50_ms"] is not None
    _assert_caveat_schema(out["caveats"])
    _assert_mesh_schema(out["mesh"])
    _assert_semiring_schema(out["semiring"])
    _assert_tiered_schema(out["tiered"])
    _assert_shard_schema(out["shard"])
    _assert_rebalance_schema(out["rebalance"])
    _assert_autoscale_schema(out["autoscale"])
    _assert_migration_schema(out["migration"])
    _assert_macro_schema(out["macro"])
    # ISSUE 19: the tiny run also carries the same-seed macro sweep
    # re-run under a live rewriting migration, folded into the baseline
    _assert_macro_migration_schema(out["macro"]["migration"])


def _assert_mesh_schema(mesh: dict) -> None:
    """The ISSUE 15 mesh contract: the device-count axis is MEASURED
    (monotone, labeled with its (data, graph) topology), the caveated
    mix ran ON the mesh (`engine_caveat_mesh_fallback_total` delta
    == 0), steady churn stayed recompile-free on the resident shards,
    p50s are finite, and the K-step fuse's convergence-collective
    reduction is recorded relative to the one-per-hop baseline (the
    single-device iteration count): checks <= ceil(iters/K) + 1."""
    assert mesh["devices_available"] >= 1
    assert mesh["n_pods"] >= 1 and mesh["n_rels"] >= 1
    assert 0.0 < mesh["caveated_share"] < 1.0
    assert mesh["caveat_mesh_fallbacks"] == 0
    counts = mesh["device_counts"]
    assert counts and counts == sorted(set(counts))
    iters = mesh["fixpoint_iters_single"]
    assert isinstance(iters, int) and iters >= 1
    assert set(mesh["points"]) == {str(c) for c in counts}
    for c in counts:
        pt = mesh["points"][str(c)]
        assert pt["devices"] == c
        assert pt["data"] * pt["graph"] == c  # topology label
        assert isinstance(pt["platform"], str) and pt["platform"]
        v = pt["list_p50_ms"]
        assert isinstance(v, (int, float)) and v == v and v > 0 \
            and abs(v) != float("inf"), (c, v)
        k = pt["k_steps"]
        assert isinstance(k, int) and k >= 2
        checks = pt["conv_checks"]
        # per-point baseline, measured at the SAME revision as the mesh
        # query (churn between points can add hops to the cyclic core)
        base = pt["conv_checks_before"]
        assert isinstance(base, int) and base >= 1
        assert 1 <= checks <= -(-base // k) + 1, (c, checks, base, k)
        assert pt["churn_recompiles"] == 0
        assert pt["churn_sharded_updates"] >= 1


def _assert_semiring_schema(sem: dict) -> None:
    """The ISSUE 17 semiring contract: all three forced modes of the one
    SpMM primitive are measured at the SAME revision (the force-mode knob
    is the baseline, not a second checkout), the per-iteration push-vs-
    pull choices are recorded per mode, the dense-phase speedups are
    relative to the forced-pull baseline, the Pallas-vs-lax point is
    present, and a CPU host carries the degraded provenance instead of a
    fabricated MXU number."""
    assert sem["n_pods"] >= 1 and sem["n_rels"] >= 1
    assert 0.0 < sem["caveated_share"] < 1.0
    assert sem["bulk_checks"] >= 1
    # the crossover the auto lax.cond actually compared against (the
    # engine's occupancy EWMA feeds it; bounds pinned by the heuristic)
    assert 0.05 <= sem["crossover"] <= 1.0
    assert set(sem["modes"]) == {"pull", "push", "auto"}
    for mode, pt in sem["modes"].items():
        for k in ("check_p50_ms", "list_p50_ms"):
            v = pt[k]
            assert isinstance(v, (int, float)) and v == v and v > 0 \
                and abs(v) != float("inf"), (mode, k, v)
        iters = pt["iterations"]
        assert isinstance(iters, int) and iters >= 1
        assert 0 <= pt["push_steps"] <= iters
        assert pt["pull_steps"] == iters - pt["push_steps"]
    # a forced-pull fixpoint must never report push steps
    assert sem["modes"]["pull"]["push_steps"] == 0
    for k in ("dense_speedup_push_vs_pull", "dense_speedup_auto_vs_pull",
              "pallas_list_p50_ms", "lax_list_p50_ms", "pallas_over_lax"):
        v = sem[k]
        assert isinstance(v, (int, float)) and v == v and v > 0 \
            and abs(v) != float("inf"), (k, v)
    assert isinstance(sem["pallas_engaged"], bool)
    assert sem["provenance"] in ("tpu", "[DEGRADED: cpu]")
    # no silent MXU claims off-TPU: the kernel cannot have engaged on a
    # degraded (CPU) run, where both sides of the delta are the lax path
    if sem["provenance"] == "[DEGRADED: cpu]":
        assert sem["pallas_engaged"] is False


def _assert_tiered_schema(t: dict) -> None:
    """The ISSUE 18 tiered-storage contract: the SAME graph is measured
    all-resident and under a ~50% device budget (relative ratio — holds
    on any backend speed), the cold start answers with oracle parity,
    steady streaming never re-traces, and the beyond-budget point
    actually paid miss stalls (an empty stall count means the phase
    silently measured a resident graph). tools/tiered_gate.py enforces
    the 1.3x ratio on CI smoke runs; the contract pins the shape."""
    assert t["n_pods"] >= 1 and t["n_rels"] >= 1
    assert t["graph_bytes"] >= 1
    assert 1 <= t["budget_bytes"] < 2 * t["graph_bytes"]
    for k in ("resident_check_p50_ms", "tiered_check_p50_ms",
              "tiered_over_resident", "cold_start_ms"):
        v = t[k]
        assert isinstance(v, (int, float)) and v == v and v > 0 \
            and abs(v) != float("inf"), (k, v)
    assert t["parity_ok"] is True
    assert t["zero_recompiles"] is True
    assert t["miss_stalls"] >= 1
    assert t["hot_blocks"] + t["cold_blocks"] >= 1
    assert t["hot_bytes"] + t["cold_bytes"] == t["graph_bytes"]
    bb = t["beyond_budget"]
    assert bb["budget_bytes"] >= 1
    assert bb["budget_bytes"] < t["budget_bytes"]
    assert bb["n_rels"] >= 1
    assert bb["parity_ok"] is True
    assert bb["miss_stalls"] >= 1
    assert bb["cold_start_ms"] > 0
    assert t["provenance"] in ("tpu", "[DEGRADED: cpu]")


def _assert_shard_schema(sh: dict) -> None:
    """The ISSUE 11 scale-out contract: the 1 vs 2 vs 4 group scaling
    curve is RECORDED (check p50, scatter-lookup p50, goodput per group
    count), and single-shard checks provably never scattered (per-shard
    op counters). Full (non-quick) runs additionally record a 10x
    scale point (~20k namespaces / ~500k relationships) under the same
    schema — pinned here whenever present (the tiny contract run
    doesn't pay its bulk loads)."""
    assert sh["n_ns"] >= 1 and sh["n_rels"] >= 1
    assert sh["single_shard_no_scatter"] is True
    assert set(sh["groups"]) == {"1", "2", "4"}
    for k, g in sh["groups"].items():
        for key in ("check_p50_ms", "scatter_lookup_p50_ms",
                    "goodput_ops_s"):
            v = g[key]
            assert isinstance(v, (int, float)) and v == v and v > 0 \
                and abs(v) != float("inf"), (k, key, v)
        assert g["single_shard_no_scatter"] is True
    if "scale10x" in sh:
        ten = sh["scale10x"]
        assert ten["n_ns"] >= 10 * sh["n_ns"]
        assert ten["n_rels"] >= 100_000
        _assert_shard_schema({k: v for k, v in ten.items()
                              if k != "scale10x"})


def _assert_rebalance_schema(rb: dict) -> None:
    """The ISSUE 14 live-move contract: a 3->4 group grow move is
    MEASURED under load — rows/slices/duration, paused-vs-running
    goodput windows (the mover-interference ratio), zero acked-write
    loss, and zero fail-open probes."""
    assert rb["n_ns"] >= 1 and rb["slices"] >= 1
    assert rb["rows_moved"] >= 1
    assert rb["move_seconds"] > 0
    assert rb["zero_acked_write_loss"] is True
    assert rb["fail_open_probes"] == 0
    for key in ("goodput_paused_ops_s", "goodput_moving_ops_s",
                "goodput_ratio_moving_over_paused"):
        v = rb[key]
        assert v is None or (isinstance(v, (int, float)) and v == v
                             and v > 0 and abs(v) != float("inf")), \
            (key, v)


def _assert_autoscale_schema(au: dict) -> None:
    """The ISSUE 20 elastic scale-out contract: a cross-namespace
    reference schema answered correctly WITHOUT replication (oracle
    parity with exactly one fleet-wide copy of every reference tuple),
    the exchange's boundary mass counter-measured (bounded rounds,
    finite wire bytes), and an SLO-driven shrink PROPOSED by the
    policy and APPLIED by the controller under load with zero acked
    loss and zero fail-open probes."""
    assert au["n_teams"] >= 1 and au["n_docs"] >= 1
    fr = au["frontier"]
    assert fr["parity_checks"] >= 1
    assert fr["parity_ok"] is True
    assert fr["lookup_parity_ok"] is True
    assert fr["reference_single_copy"] is True
    assert fr["exchanges"] >= 1
    assert 1 <= fr["rounds_max"] <= 8
    assert fr["boundary_tuples"] >= 1
    for key in ("scatter_bytes", "gather_bytes"):
        v = fr[key]
        assert isinstance(v, int) and v > 0, (key, v)
    sh = au["shrink"]
    assert sh["proposal_action"] == "shrink"
    assert sh["ticks_to_fire"] >= 2  # hysteresis held, not a one-tick
    assert sh["groups_after"] == 2
    assert sh["move_seconds"] > 0
    assert sh["zero_acked_write_loss"] is True
    assert sh["fail_open_probes"] == 0
    for key in ("goodput_paused_ops_s", "goodput_moving_ops_s",
                "goodput_ratio_moving_over_paused"):
        v = sh[key]
        assert v is None or (isinstance(v, (int, float)) and v == v
                             and v > 0 and abs(v) != float("inf")), \
            (key, v)


def _assert_caveat_schema(cav: dict) -> None:
    """The ISSUE 9 caveat-mix contract: caveated share, cold check p50
    with/without request context vs the uncaveated baseline, warm
    (decision-cached) p50s, the caveated/uncaveated ratio, and the
    fail-closed missing-context denial count."""
    assert cav["n_tuples"] >= 1
    assert 0.0 < cav["caveated_share"] < 1.0
    for k in ("check_p50_uncaveated_ms", "check_p50_caveated_ctx_ms",
              "check_p50_caveated_noctx_ms", "warm_p50_caveated_ctx_ms",
              "warm_p50_uncaveated_ms"):
        v = cav[k]
        assert isinstance(v, (int, float)) and v == v and v >= 0 \
            and abs(v) != float("inf")
    assert cav["caveated_over_uncaveated"] > 0
    # fail-closed accounting: a whole caveated batch without context
    # MUST register missing-context denials (the old behavior silently
    # excluded the tuples instead)
    assert cav["missing_context_denials"] >= 1


def _assert_migration_schema(mig: dict) -> None:
    """The ISSUE 19 live-migration contract: an additive and a
    rewriting migration each complete under a sustained check/write mix
    with finite time-to-cut / freeze / during-window p50 numbers, the
    additive one backfills nothing, the rewriting one backfills the
    affected closure, and the provenance label is honest."""
    assert mig["provenance"] in ("tpu", "[DEGRADED: cpu]")
    assert mig["n_rels"] >= 1
    p50_before = mig["p50_before_ms"]
    assert isinstance(p50_before, (int, float)) and p50_before > 0 \
        and p50_before == p50_before
    ratio = mig["during_over_before_p50"]
    assert isinstance(ratio, (int, float)) and ratio > 0 \
        and abs(ratio) != float("inf")
    for cls in ("additive", "rewriting"):
        row = mig[cls]
        assert row["classification"] == cls
        assert row["phase"] == "done"
        assert row["during_samples"] >= 1
        for k in ("time_to_cut_ms", "freeze_ms", "p50_during_ms"):
            v = row[k]
            assert isinstance(v, (int, float)) and v >= 0 \
                and v == v and abs(v) != float("inf")
    assert mig["additive"]["backfilled"] == 0
    assert mig["rewriting"]["backfilled"] >= 1


def _assert_macro_migration_schema(m: dict) -> None:
    """The macro.migration fold: same-seed sweep under a held-open
    rewriting migration, knee (or top-multiplier goodput) ratio against
    the baseline, and the migration itself finished DONE with a real
    backfill and a sub-second freeze."""
    assert isinstance(m["knee_ratio"], (int, float)) and m["knee_ratio"] > 0
    assert m["basis"] == "knee" or m["basis"].startswith("goodput@x")
    assert m["classification"] == "rewriting"
    assert m["phase"] == "done"
    assert m["backfilled"] >= 1
    assert len(m["curve"]) >= 4
    for k in ("time_to_cut_ms", "freeze_ms"):
        v = m[k]
        assert isinstance(v, (int, float)) and v >= 0 \
            and abs(v) != float("inf")


def _assert_macro_schema(macro: dict) -> None:
    """The ISSUE 7 macro-phase contract: goodput-vs-offered-load curve
    with >= 4 points, a knee estimate, burst p99.9 per op class, per-
    stage tail attribution for the worst burst window, SLO attainment,
    and the reproducibility pin (seed + schedule digest)."""
    curve = macro["curve"]
    assert len(curve) >= 4
    for pt in curve:
        assert {"multiplier", "offered_rps", "completed_rps",
                "goodput_rps", "shed", "errors", "late",
                "classes"} <= set(pt)
        assert pt["offered_rps"] > 0
        for q in pt["classes"].values():
            for k, v in q.items():
                assert k in ("p50_ms", "p99_ms", "p999_ms")
                assert isinstance(v, (int, float)) and v == v \
                    and abs(v) != float("inf")
    # offered load is monotone in the multiplier (open loop: the server
    # cannot flatten it)
    offered = [pt["offered_rps"] for pt in curve]
    assert offered == sorted(offered)
    assert isinstance(macro["knee_saturated"], bool)
    assert macro["knee_rps"] is None or macro["knee_rps"] > 0
    # burst windows with exact per-class tails including p99.9
    assert set(macro["bursts"]) == {"watch-storm", "get-wave",
                                    "reconcile"}
    assert any(b["classes"] for b in macro["bursts"].values())
    for b in macro["bursts"].values():
        for st in b["classes"].values():
            assert st["n"] >= 1
            assert st["p999_ms"] >= st["p99_ms"] >= st["p50_ms"] >= 0
    # tail attribution names the worst burst and splits its stage time
    ta = macro["tail_attribution"]
    assert ta["burst"] in macro["bursts"]
    if ta["traces"] > 0:
        assert ta["stages_us"]
        if any(ta["stages_us"].values()):
            assert sum(ta["stage_share"].values()) == pytest.approx(
                1.0, abs=0.05)
    assert macro["slo_attainment"]
    for v in macro["slo_attainment"].values():
        assert v is None or 0.0 <= v <= 1.0
    assert macro["slo_monitor"]
    # ISSUE 8: every macro result carries the overlay on/off comparison
    # (the same trace re-swept with IncrementalGraphUpdates off) and its
    # per-multiplier goodput ratio, plus the scale annotation
    off = macro["overlay_off"]
    assert off["curve"]
    for pt in off["curve"]:
        assert pt["offered_rps"] > 0
    assert off["goodput_ratio_on_over_off"]
    for v in off["goodput_ratio_on_over_off"].values():
        assert isinstance(v, (int, float)) and v > 0
    assert macro["scale"]["n_ns"] >= 1
    # reproducibility pin: the recorded seed + the digest of the top
    # point's REBUILT schedule (identical seed => identical schedule)
    assert isinstance(macro["seed"], int)
    assert isinstance(macro["schedule_digest"], str)
    assert len(macro["schedule_digest"]) == 16
    int(macro["schedule_digest"], 16)
    assert macro["watch_streams_opened"] >= 0
    assert macro["capacity_rps"] > 0 and macro["base_rate_rps"] > 0


def test_macro_only_headline_is_knee():
    """`bench.py --tiny --macro-only` (the make bench-macro smoke): only
    the sweep runs, the headline metric is the knee estimate, and the
    macro schema holds."""
    p = subprocess.run(
        [sys.executable, BENCH, "--tiny", "--macro-only"],
        env=_env(), capture_output=True, text=True, timeout=280)
    out = _parse_only_line(p.stdout)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "macrobench goodput knee" in out["metric"]
    assert out["unit"] == "op/s"
    _assert_macro_schema(out["macro"])
    # macro-only really skipped the closed-loop phases
    assert "checks_per_s_per_chip" not in out


def test_sigterm_flushes_partial_json():
    p = subprocess.Popen(
        [sys.executable, BENCH, "--tiny"],
        env=_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    # wait for the backend-init line: bench logs it AFTER installing the
    # signal handlers, so killing now is deterministic regardless of
    # machine load. The read runs in a helper thread so a bench that
    # wedges before logging (or exits instantly) cannot block or
    # busy-spin this test past its deadline.
    import threading

    started = threading.Event()

    def watch_stderr():
        for line in p.stderr:
            if "initialising the JAX backend" in line:
                started.set()
                return

    t = threading.Thread(target=watch_stderr, daemon=True)
    t.start()
    if not started.wait(timeout=60):
        p.kill()
        raise AssertionError("bench never reached backend init")
    p.send_signal(signal.SIGTERM)
    stdout, _ = p.communicate(timeout=60)
    out = _parse_only_line(stdout)
    assert out["error"] == f"killed by signal {signal.SIGTERM}"
    assert out["degraded"] is True
    assert p.returncode == 128 + signal.SIGTERM


def test_deadline_watchdog_emits_partial_json():
    # a deadline short enough to fire during the measurement
    p = subprocess.run(
        [sys.executable, BENCH, "--tiny", "--deadline", "1"],
        env=_env(), capture_output=True, text=True, timeout=120)
    out = _parse_only_line(p.stdout)
    assert p.returncode == 2
    assert "deadline" in out["error"]


def test_full_size_without_tpu_is_an_error():
    """The measurement path fails closed: a full-size run that finds no
    TPU measures nothing, says so in its one JSON line, exits non-zero."""
    p = subprocess.run(
        [sys.executable, BENCH], env=_env(), capture_output=True,
        text=True, timeout=120)
    out = _parse_only_line(p.stdout)
    assert p.returncode != 0
    assert "no TPU" in out["error"]
    assert out["value"] is None
    assert "checks_per_s_per_chip" not in out


@pytest.mark.slow
def test_healthy_cpu_quick_run_full_contract():
    # the CPU contract run: labelled degraded, but a complete measurement
    p = subprocess.run(
        [sys.executable, BENCH, "--tiny"],
        env=_env(), capture_output=True, text=True, timeout=600)
    out = _parse_only_line(p.stdout)
    assert p.returncode == 0
    assert out["vs_baseline"] is not None
    assert out["checks_per_s_per_chip"] > 0

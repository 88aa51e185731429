"""roofline_residual.py's count and readers/residual_stream.py."""

import pytest

import roofline
import roofline_residual
from run import by_name


def test_the_get_cells_graph_streams_in_microseconds():
    """nested-org-1m: 1,022,000 residual edges and 824,576 slots, one
    subject row: 9 + 1 bytes an edge, the state once in and once out."""
    peak = roofline.peaks("TPU v5 lite")
    s = roofline_residual.dispatch_least_s(1_022_000, 824_576, 1, peak)
    assert s == pytest.approx((1_022_000 * 10 + 2 * 824_576) / 819e9)
    assert 10e-6 < s < 20e-6
    # rows widen the gather and the state, not the edge list
    s8 = roofline_residual.dispatch_least_s(1_022_000, 824_576, 8, peak)
    assert s8 == pytest.approx((1_022_000 * 17 + 16 * 824_576) / 819e9)


def ctx(deltas, gauges=None, trace=None):
    return {"gauges": {"hbm_bytes": 16e9} if gauges is None else gauges,
            "delta": deltas.get,
            "trace": {"busy_s": 3.0, "dispatches": 37.0}
            if trace is None else trace}


def test_reader_reads_the_programs_gauges_and_rows(monkeypatch):
    rs = by_name("readers", "residual_stream")
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    class Cpu:
        device_kind = "TPU v5 lite"

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Cpu()])
    deltas = {"engine_dispatch_rows_total": 652.0,
              "engine_check_seconds_count": 652.0}
    metrics.gauge("engine_residual_edges").set(0)
    assert rs.read({}, ctx(deltas)) is None  # no residual edge
    metrics.gauge("engine_residual_edges").set(1_022_000)
    metrics.gauge("engine_graph_slots").set(824_576)
    pct = rs.read({}, ctx(deltas))
    least = roofline_residual.dispatch_least_s(
        1_022_000, 824_576, 1.0, roofline.peaks("TPU v5 lite"))
    assert pct == pytest.approx(37 * least / 3.0 * 100)
    assert 0.01 < pct < 0.03
    # nothing: no trace, a rehearsal's device without peaks, a parent
    # commit without the counter
    assert rs.read({}, ctx(deltas, trace={})) is None
    assert rs.read({}, ctx(deltas, gauges={})) is None
    assert rs.read({}, ctx({"engine_check_seconds_count": 652.0})) is None
    assert rs.program_gauge("engine_no_such_gauge") is None

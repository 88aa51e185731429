"""trace_reduce.py on the trace recorded in bench_results/r5_tpu_profile."""

import os

import pytest

import trace_reduce
from conftest import ROOT

TRACE = os.path.join(ROOT, "bench_results", "r5_tpu_profile")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_path(TRACE, top=10 ** 6)


def test_busy_fits_the_span_and_idle_is_the_rest(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["span_s"]
    gaps = sum(s for _, s in reduced["idle_gaps"])
    assert gaps == pytest.approx(reduced["span_s"] - reduced["busy_s"],
                                 rel=1e-9)


def test_op_table_sums_to_busy(reduced):
    # nested events are charged to the innermost one, so nothing counts
    # twice: the recorded trace's plain sum of durations is 1.7% higher
    assert sum(s for _, s in reduced["device_ops"]) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.062687095, rel=1e-6)


def test_gaps_without_an_annotation_of_ours_are_the_hosts(reduced):
    assert {label for label, _ in reduced["idle_gaps"]} == {"host"}


def test_own_time_charges_the_innermost_event():
    merged, owned = trace_reduce.own_time([
        (0, 10, "while"), (2, 5, "body"), (3, 4, "inner"), (12, 14, "op")])
    assert merged == [[0, 10], [12, 14]]
    assert owned == {"while": 7, "body": 2, "inner": 1, "op": 2}

"""readers/program_timeline.py on cuts of two traces recorded on the
chip (fixtures/README.txt) and on the trace of bench_results."""

import os

import pytest

import trace_reduce
from conftest import BENCH, ROOT
from run import by_name

FIX = os.path.join(BENCH, "tests", "fixtures")
LIST_ARGS = {"what": "idle_unexplained_pct",
             "parents": ["request", "engine_dispatch", "prefilter"],
             "waiting": ["executor_wait", "loop_wait", "device_wait",
                         "admission_wait", "upstream"]}


@pytest.fixture(scope="module")
def pt():
    return by_name("readers", "program_timeline")


def timeline(pt, path):
    from jax.profiler import ProfileData

    return pt.load(ProfileData.from_file(trace_reduce.find_xplane(path)))


@pytest.fixture(scope="module")
def list_cut(pt):
    return timeline(pt, os.path.join(FIX, "list-distinct.cut.xplane.pb"))


@pytest.fixture(scope="module")
def get_cut(pt):
    return timeline(pt, os.path.join(FIX, "get-distinct.cut.xplane.pb"))


def test_a_deep_queue_is_matched_past_what_the_trace_did_not_see(pt, get_cut):
    """32 clients on a device that takes 81 ms a check: 17 workers keep
    16 dispatches queued, and the first 16 modules of the trace belong
    to enqueues made before it began."""
    assert len(get_cut["modules"]) == 54
    assert len(get_cut["stages"]["engine_enqueue"]) == 53
    ms = pt.device_queue_ms(get_cut, {})
    assert ms == pytest.approx(1307.757, rel=1e-4)
    # about sixteen programs of 81 ms ahead of each
    assert 15.5 < ms / 81.1 < 16.5


def test_an_idle_device_has_next_to_no_queue(pt, list_cut, capsys):
    assert pt.device_queue_ms(list_cut, {}) == pytest.approx(0.956, rel=1e-2)
    # the host thread was still inside ``run`` (waiting for the
    # interpreter lock) when the device had begun: said, not hidden
    assert "modules started before their enqueue's call returned" \
        in capsys.readouterr().err


@pytest.mark.parametrize("module_at, expected", [
    ((130, 150), 0.00003),  # 30 ns after the enqueue returned
    ((60, 80), 0.0),        # inside the call: no queue, and a line
    ((10, 30), None)])      # before the call began: no pairing, no reading
def test_a_module_pairs_with_no_enqueue_that_began_after_it(
        pt, module_at, expected, capsys, monkeypatch):
    monkeypatch.setattr(pt, "SLACK_NS", 5)
    tl = {"span": (0, 1000), "busy": [], "stages": {
        "engine_enqueue": [(50, 100, 1)], "device_wait": [(100, 400, 1)]},
        "modules": [module_at + ("jit_sdbkp_fixpoint(1)",)]}
    assert pt.device_queue_ms(tl, {}) == expected
    assert ("counted as no queue" in capsys.readouterr().err) \
        == (expected == 0.0)


def test_other_names_are_arguments(pt):
    """The trace recorded before this PR: the enqueue annotation was
    ``sdbkp:fixpoint``, the module ``jit__unknown``, no wait recorded."""
    old = timeline(pt, os.path.join(ROOT, "bench_results", "r5_tpu_profile"))
    assert pt.device_queue_ms(old, {}) is None
    ms = pt.device_queue_ms(old, {"enqueue": "fixpoint",
                                  "module": "jit__unknown"})
    assert ms == pytest.approx(39.5, rel=0.01)
    assert pt.idle_unexplained_pct(old, LIST_ARGS) > 99


def test_idle_is_charged_to_the_stage_open_on_the_host(pt, list_cut, capsys):
    table = pt.idle_by_stage(list_cut, LIST_ARGS)
    lo, hi = list_cut["span"]
    busy = sum(b - a for a, b in list_cut["busy"])
    assert sum(table.values()) == pytest.approx(hi - lo - busy, rel=1e-9)
    # the parents are not in it; the body filter on the event loop is
    # what the host does while the device idles
    assert not {"request", "prefilter", "engine_dispatch"} & set(table)
    assert max(table, key=table.get) == "body_filter"
    assert table["body_filter"] / sum(table.values()) > 0.75
    pct = pt.idle_unexplained_pct(list_cut, LIST_ARGS)
    assert pct == pytest.approx(
        table["(no stage)"] / sum(table.values()) * 100)
    assert 0 < pct < 5
    assert "body_filter 0.5339" in capsys.readouterr().err


def test_waiting_stages_take_idle_only_where_nothing_works(pt):
    tl = {"span": (0, 100), "busy": [(0, 10)], "modules": [],
          "stages": {"device_wait": [(0, 60, 1)], "body_filter": [(20, 40, 0)],
                     "mask_to_ids": [(30, 40, 1), (30, 40, 2)],
                     "request": [(0, 100, 0)]}}
    table = pt.idle_by_stage(tl, LIST_ARGS)
    # 10-20 wait alone, 20-30 body_filter, 30-40 one of three working
    # stages, 40-60 wait alone again, 60-100 nothing
    assert table["device_wait"] == pytest.approx(30)
    assert table["body_filter"] == pytest.approx(10 + 10 / 3)
    assert table["mask_to_ids"] == pytest.approx(20 / 3)
    assert table["(no stage)"] == pytest.approx(40)


def test_nothing_without_a_traced_run_or_annotations(pt):
    assert pt.read({"what": "device_queue_ms"}, {"trace": None}) is None
    bare = {"span": (0, 10), "busy": [(0, 1)], "modules": [], "stages": {}}
    assert pt.device_queue_ms(bare, {}) is None
    assert pt.idle_unexplained_pct(bare, LIST_ARGS) is None
    ctx = {"trace": {"busy_s": 1.0}, "program_timeline": bare}
    assert pt.read(LIST_ARGS, ctx) is None


def test_the_newest_trace_under_bench_work_is_the_runs_own(pt, tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(pt, "ROOT", str(tmp_path))
    assert pt.newest_xplane() == ""
    for age, cell in ((100, "zz-throwaway.get-few"), (5, "a.cell")):
        d = tmp_path / ".bench_work" / cell / "trace" / "plugins" \
            / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1e9 - age, 1e9 - age))
    assert "a.cell" in pt.newest_xplane()

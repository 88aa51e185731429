"""BENCHMARK.json is what manifest.py assembles from the files, and it
keeps to the contract's names and limits."""

import json
import os
import re

import manifest
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def committed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_is_assembled_from_the_files():
    assert committed() == manifest.build()


def test_names_units_and_lines():
    b = committed()
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert m.get("workloads", cells)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    assert "setup_s" in e2e
    for w in cells:  # every cell: setup_s, one more, and a per-layer one
        assert sum(w in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2
        assert any(w in m["workloads"] for m in b["per_layer"])
    assert len(json.dumps(b)) < 64 * 1024


def test_every_metric_names_a_reader_that_exists():
    for d in ("end_to_end", "metrics"):
        for fn in os.listdir(os.path.join(BENCH, d)):
            with open(os.path.join(BENCH, d, fn)) as f:
                m = json.load(f)
            assert fn == m["name"] + ".json"
            assert os.path.isfile(os.path.join(BENCH, "readers",
                                               m["reader"] + ".py"))


def test_files_are_named_from_a_names_characters():
    for base, _, files in os.walk(BENCH):
        if "__pycache__" in base:
            continue
        for fn in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), fn

#!/usr/bin/env python3
"""Assemble ``BENCHMARK.json`` from the benchmark's own files, so that the
manifest the driver reads and the files the harness reads cannot drift:
every configuration directory, cell file and metric file becomes one
entry. ``python3 benchmark/manifest.py`` prints it; ``--write`` replaces
``BENCHMARK.json``. A PR that adds a file runs it once.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
HEAD = {"command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 51}
EVERY_CELL = "*"  # a metric file's ``workloads`` for "reported in every cell"


def _files(directory: str) -> list:
    d = os.path.join(HERE, directory)
    return [os.path.join(d, f) for f in sorted(os.listdir(d))
            if f.endswith(".json")]


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def build() -> dict:
    out = dict(HEAD, configs=[], workloads=[], end_to_end=[], per_layer=[])
    for name in sorted(os.listdir(os.path.join(HERE, "configs"))):
        c = _load(os.path.join(HERE, "configs", name, "config.json"))
        out["configs"].append({
            "name": name, "source": c["source"],
            "file": f"benchmark/configs/{name}/config.json",
            "reduced": c["reduced"], "why": c["why"]})
    for path in _files("workloads"):
        w = _load(path)
        out["workloads"].append({
            "name": os.path.basename(path)[:-len(".json")],
            "config": w["config"], "traffic": w["traffic"],
            "chips": w["chips"], "why": w["why"]})
    for path in _files("end_to_end"):
        m = _load(path)
        out["end_to_end"].append({k: m[k] for k in (
            "name", "unit", "better", "bound", "source", "workloads")
            if m[k] != EVERY_CELL})
    for path in _files("metrics"):
        m = _load(path)
        out["per_layer"].append({k: m[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
    return out


if __name__ == "__main__":
    text = json.dumps(build(), indent=1) + "\n"
    if "--write" in sys.argv[1:]:
        with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
            f.write(text)
    else:
        sys.stdout.write(text)

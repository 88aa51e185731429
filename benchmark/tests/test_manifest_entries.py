"""BENCHMARK.json holds just the entries manifest.py assembles, whatever
their order: a PR that is not of kind ``benchmark`` has to put its entries at
the end of each list, where manifest.py sorts them in by file name, so
``test_manifest_is_assembled_from_the_files`` fails after such a PR until a
benchmark PR mends manifest.py. The drift it guards against is caught here.
"""

import json
import os

import manifest
from conftest import ROOT

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def test_every_entry_is_a_files_and_every_file_has_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        committed = json.load(f)
    built = manifest.build()
    assert set(committed) == set(built)
    for key in built:
        if key not in LISTS:
            assert committed[key] == built[key]
            continue
        names = [e["name"] for e in committed[key]]
        assert len(names) == len(set(names))
        assert ({e["name"]: e for e in committed[key]}
                == {e["name"]: e for e in built[key]})

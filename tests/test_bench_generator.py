"""``bench.py`` is the seeded generator of the headline deployment and
nothing else (ROADMAP D6a): the same seed gives the same columns, another
seed others, and the file stays free of a ``main`` and of package imports
at module level, so importing it costs numpy alone."""

import ast
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402

DIMS = dict(n_pods=400, n_users=60, n_ns=12, n_groups=10, n_rels=4_000)


def test_build_columns_is_a_function_of_its_seed():
    a = bench.build_columns(seed=7, **DIMS)
    b = bench.build_columns(seed=7, **DIMS)
    c = bench.build_columns(seed=8, **DIMS)
    assert set(a) == set(b) == set(c)
    assert {len(v) for v in a.values()} == {len(a["resource_id"])}
    assert 0 < len(a["resource_id"]) <= DIMS["n_rels"]
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    # the caveated mix is seeded the same way and marks the share it says
    m = bench.build_columns(seed=7, cav_share=0.25, **DIMS)
    n = bench.build_columns(seed=7, cav_share=0.25, **DIMS)
    for k in m:
        np.testing.assert_array_equal(m[k], n[k])
    assert set(m["caveat_context"]) == {""} | set(bench.MESH_CTXS)


def test_bench_stays_a_generator():
    with open(os.path.join(ROOT, "bench.py")) as f:
        tree = ast.parse(f.read())
    names = {n.name for n in tree.body
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef))}
    assert names == {"log", "build_columns", "build_engine"}
    assert not any(isinstance(n, ast.If) for n in tree.body), \
        "no __main__ guard: nothing runs this file"
    imported = set()
    for n in tree.body:
        if isinstance(n, ast.Import):
            imported.update(a.name.split(".")[0] for a in n.names)
        elif isinstance(n, ast.ImportFrom):
            imported.add((n.module or "").split(".")[0])
    assert imported <= {"__future__", "sys", "time", "numpy"}, imported

"""Stage-0 chip micro: A/B the final mask-read extraction, ~2 min total.

    python bench_results/extraction_ab.py [n_pods] [n_rels] [trials]

Window #1's trace showed the general fancy-index gather costing 0.95 ms
of the 3.04 ms device time (31%) for the list-filter shape; the
contiguous-window `dynamic_slice` fast path replaced it afterwards and
has never run on a chip. This script builds a mid-size graph (~30 s
host-side), then measures the SAME query with the fast path on and off,
amortizing the per-dispatch overhead by dispatching each trial's queries
back-to-back asynchronously — the A-B delta isolates the extraction op
without needing the full headline run. Emits one JSON line.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def main() -> None:
    n_pods = int(sys.argv[1]) if len(sys.argv) > 1 else 131072
    n_rels = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    trials = int(sys.argv[3]) if len(sys.argv) > 3 else 15
    burst = 8  # queries dispatched back-to-back per timed trial

    sys.path.insert(0, ".")
    import jax

    from spicedb_kubeapi_proxy_tpu.engine import Engine
    from spicedb_kubeapi_proxy_tpu.models import parse_schema

    rng = np.random.default_rng(7)
    e = Engine(schema=parse_schema("""
definition user {}
definition namespace {
  relation viewer: user
  permission view = viewer
}
definition pod {
  relation namespace: namespace
  permission view = namespace->view
}
"""))
    n_ns, n_users = max(n_pods // 10, 100), 1000
    cols = []
    m_ns = max(n_rels - n_pods, n_ns)
    cols.append(("namespace",
                 np.char.add("ns", rng.integers(n_ns, size=m_ns).astype(str)),
                 "viewer", "user",
                 np.char.add("u", rng.integers(n_users, size=m_ns).astype(str))))
    cols.append(("pod", np.char.add("p", np.arange(n_pods).astype(str)),
                 "namespace", "namespace",
                 np.char.add("ns", rng.integers(n_ns, size=n_pods).astype(str))))
    merged = {
        "resource_type": np.concatenate(
            [np.full(len(c[1]), c[0]) for c in cols]),
        "resource_id": np.concatenate([c[1] for c in cols]),
        "relation": np.concatenate(
            [np.full(len(c[1]), c[2]) for c in cols]),
        "subject_type": np.concatenate(
            [np.full(len(c[1]), c[3]) for c in cols]),
        "subject_id": np.concatenate([c[4] for c in cols]),
        "subject_relation": np.concatenate(
            [np.full(len(c[1]), "") for c in cols]),
    }
    t0 = time.time()
    e.bulk_load(merged)
    cg = e.compiled()
    objs = e._objects_by_name()
    print(f"built {len(merged['resource_id'])} rels in {time.time()-t0:.0f}s "
          f"(backend {jax.default_backend()})", file=sys.stderr)

    off = cg.offset_of("pod", "view")
    n = cg.type_sizes["pod"]
    qs = off + np.arange(n, dtype=np.int32)
    qb = np.zeros(n, dtype=np.int32)
    subs = [np.asarray([cg.encode_subject("user", f"u{i}", None, objs)],
                       dtype=np.int32) for i in range(burst)]

    def measure(contig: bool) -> float:
        # warm the trace
        cg.query_async(subs[0], qs, qb, q_contiguous=contig,
                       q_cache_key=("ab", off, n, contig)).result()
        lat = []
        for _ in range(trials):
            t0 = time.perf_counter()
            futs = [cg.query_async(s, qs, qb, q_contiguous=contig,
                                   q_cache_key=("ab", off, n, contig))
                    for s in subs]
            for f in futs:
                f.result()
            lat.append((time.perf_counter() - t0) * 1e3 / burst)
        lat.sort()
        return lat[len(lat) // 2]

    p50_slice = measure(True)
    p50_gather = measure(False)
    out = {
        "backend": jax.default_backend(),
        "n_pods": n_pods, "n_rels": int(len(merged["resource_id"])),
        "burst": burst, "trials": trials,
        "amortized_ms_gather": round(p50_gather, 3),
        "amortized_ms_slice": round(p50_slice, 3),
        "delta_ms": round(p50_gather - p50_slice, 3),
        "note": "per-query amortized over async bursts (dispatch "
                "overhead cancelled); delta isolates the extraction op — window-1 "
                "trace predicts ~0.9ms on a v5e at 131072 pods",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()

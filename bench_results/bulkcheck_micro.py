"""Micro-bench for the bulk-check host path (VERDICT r4 Weak #2).

Reproduces only the `bulk check` section of bench.py --quick, with many
trials so noise is quantified. Run on CPU:

    JAX_PLATFORMS=cpu python bench_results/bulkcheck_micro.py [trials]

Prints one JSON line: {"p50_us_per_check": ..., "checks_per_s": [...]}.
"""

from __future__ import annotations

import json
import os
import sys
import time

# a CPU micro-bench of host code: forced through the environment
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import build_engine  # noqa: E402


def main() -> None:
    trials = int(sys.argv[1]) if len(sys.argv) > 1 else 15
    n_pods, n_users, n_ns, n_groups, n_rels = 2000, 500, 20, 50, 50000
    e, total = build_engine(n_pods, n_users, n_ns, n_groups, n_rels)

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem

    rng = np.random.default_rng(7)
    B, per = 8, 64
    items = [
        CheckItem("pod", f"ns/p{rng.integers(n_pods)}", "view",
                  "user", f"u{b}")
        for b in rng.integers(n_users, size=B)
        for _ in range(per)
    ]
    e.check_bulk(items)  # warmup (jit compile + caches)
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        e.check_bulk(items)
        dt = time.perf_counter() - t0
        rates.append(len(items) / dt)
    rates.sort()
    p50 = rates[len(rates) // 2]
    out = {
        "n_checks": len(items),
        "trials": trials,
        "p50_checks_per_s": round(p50),
        "min_checks_per_s": round(rates[0]),
        "max_checks_per_s": round(rates[-1]),
        "p50_us_per_check": round(1e6 / p50, 3),
        "rates": [round(r) for r in rates],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()

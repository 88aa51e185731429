"""The benchmark's own tests run on the CPU with the kernels interpreted:
`python3 -m pytest benchmark/tests -q -p no:cacheprovider` from the root.
They are not part of tier-1 (which collects tests/ only)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("SDBKP_BITPROP", "interpret")
os.environ.setdefault("SDBKP_SEMIRING", "interpret")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

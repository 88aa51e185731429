"""The table of peaks and the least time one propagation hop can take.

The work is counted from shapes, whatever kernel does it: for one
dispatch with ``B`` subject rows, every dense block of the compiled graph
(``n_dst`` x ``n_src``) is applied once. Its least time is the larger of
streaming the bit-packed operand once plus the frontier in and out
(``n_dst*n_src/8 + B*(n_src + n_dst)`` bytes over the HBM peak) and the
contraction at the MXU's int8 peak (``2*n_dst*n_src*B`` operations).
Iterations of a cyclic core are counted once, so the share errs low; a
kernel that skips blocks whose frontier is empty does less than this
work, so a share near 100% calls for a new count before it is believed.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to benchmark/peaks.json with its source")
    return table[device_kind]


def block_least_s(n_dst: int, n_src: int, rows: float, peak: dict) -> tuple:
    """-> (seconds, "bytes" | "ops"): the bound that holds this block."""
    by_bytes = (n_dst * n_src / 8 + rows * (n_src + n_dst)) \
        / peak["hbm_bytes_per_s"]
    by_ops = 2.0 * n_dst * n_src * rows / peak["int8_ops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


def dispatch_least_s(blocks: list, rows: float, peak: dict) -> float:
    """Least seconds of one dispatch over ``blocks`` = [(n_dst, n_src)]."""
    return sum(block_least_s(d, s, rows, peak)[0] for d, s in blocks)

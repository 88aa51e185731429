"""The least time the sparse hop of one dispatch can take.

The edges no dense block took (the residual) are walked by gather and
segment-max: per dispatch with ``B`` subject rows, every residual edge
is read once (source and destination slot as int32 and the activation
byte: 9 bytes) and gathers ``B`` bytes of state, and the state (one
byte per slot and row) is read once and written once. Bytes over the
HBM peak of ``peaks.json``; there is no contraction to bound it by.
Counted from shapes, whatever implements it. The limits of
``roofline.py`` hold here too: iterations of a cyclic core and the
levels after it walk their own slices once each, which this counts as
one walk over all of them, so the share errs low; a path that does not
walk the graph does less than this.
"""

EDGE_BYTES = 9  # src int32 + dst int32 + activation uint8


def dispatch_least_s(edges: float, slots: float, rows: float,
                     peak: dict) -> float:
    """Least seconds of one dispatch over ``edges`` residual edges and a
    state of ``slots`` slots by ``rows`` subject rows."""
    return (edges * (EDGE_BYTES + rows) + 2.0 * slots * rows) \
        / peak["hbm_bytes_per_s"]

"""Response filtering: lists, tables, and single objects.

Mirrors /root/reference/pkg/authz/responsefilterer.go:190-415: after the
upstream responds, list items / table rows / the single object are filtered
against the allowed set computed by the (concurrent) prefilter. Content is
negotiated like the reference (responsefilterer.go:242-313): JSON
(including Table form and unknown/CRD kinds, which are unstructured dicts
here by construction) and kube protobuf — list responses via schema-light
wire surgery on the ``runtime.Unknown`` envelope (proxy/kubeproto.py),
single objects as byte-identical passthrough keyed on the request path.
Filtering errors surface as 401, an excluded single object as 404
(writeResp semantics, responsefilterer.go:716-735).
"""

from __future__ import annotations

import json
from typing import Optional

from ..obs.trace import tracer
from ..proxy import kubeproto
from ..proxy.types import ProxyResponse, kube_status
from ..rules.input import ResolveInput
from ..utils.metrics import metrics
from .lookups import AllowedSet

# A body this long is filtered on a worker thread, a shorter one where the
# request's coroutine runs (filter_response). The hop to a worker and back
# costs the request about 2 ms under load (executor_wait_ms 0.9 +
# loop_wait_ms 1.1 a crossing, PERF.md §5, ledger PR 26) and nobody else
# anything; the filter on the loop costs EVERY request in flight its
# length, about 2 ns a byte by the native call (body_filter_ms over the
# list cell's 11.6 MB, PERF.md §6 PR 27) and some 50 by the json.loads
# path. 256 KiB is half a millisecond of the one, 13 ms of the other: a
# single object or a namespace's pods stay inline, a cluster-wide list
# never does.
OFF_LOOP_BYTES = 256 * 1024


class FilterError(Exception):
    pass


def _count(path: str, body: bytes) -> None:
    """One filtered body, by who decided it: the ``fused`` native call or
    the ``python`` walkers; a body short enough to stay on the event loop
    counts as ``inline`` whoever decides it."""
    metrics.counter(
        "proxy_body_filter_total",
        path=path if len(body) >= OFF_LOOP_BYTES else "inline").inc()


def _meta_pair(obj: dict) -> tuple[str, str]:
    meta = obj.get("metadata") or {}
    return meta.get("namespace") or "", meta.get("name") or ""


def _filter_list_wire(body: bytes, allowed: AllowedSet):
    """Native wire-level JSON list filtering: ONE call that holds no
    interpreter lock (graphcore.cpp json_list_filter) scans the body,
    decides every item against the allowed records and hands back the
    byte runs to keep — kept items AND the whole wrapper stay
    byte-identical, and a 15 MB 100k-item body never goes through
    json.loads nor makes a Python object per item. Handles *List bodies
    (items, metadata at item top level) and Tables (rows, metadata under
    each row's ``object``). Returns (status, new_body) or None to fall
    back to the Python path (scanner bailed, single objects, native
    unavailable)."""
    from .. import native

    scan = native.json_list_filter(body, *allowed.packed_records())
    if scan is None:
        return None
    (lo, hi), runs, esc, dropped = scan
    if lo < 0:
        # kind says list/table but the array key is absent: nothing to
        # filter (`doc.get(...) or []` semantics) — body passes through
        return 200, body
    if not dropped and not len(esc):
        return 200, body  # byte-identical passthrough
    keep = runs.tolist()
    if len(esc):
        # names with escapes (rare: kube names are DNS labels) come back
        # undecided, each a run of its own: decode exactly, decide here
        pairs = allowed.pairs
        denied = set()
        for run, ns_s, ns_e, nm_s, nm_e in esc.tolist():
            ns_b, nm_b = body[ns_s:ns_e], body[nm_s:nm_e]
            try:
                ns = json.loads(b'"%s"' % ns_b) if b"\\" in ns_b \
                    else ns_b.decode("utf-8")
                nm = json.loads(b'"%s"' % nm_b) if b"\\" in nm_b \
                    else nm_b.decode("utf-8")
            except ValueError:
                # invalid escape / invalid utf-8: json.loads would have
                # rejected the whole body — fall back so the Python path
                # produces its clean 401, not an unhandled 500
                return None
            if (ns, nm) not in pairs:
                denied.add(run)
        if denied:
            dropped += len(denied)
            keep = [r for i, r in enumerate(keep) if i not in denied]
    if not dropped:
        return 200, body
    return 200, b"".join((body[:lo],
                          b",".join([body[s:e] for s, e in keep]),
                          body[hi:]))


def filter_body(body: bytes, allowed: AllowedSet,
                input: ResolveInput) -> tuple[int, bytes]:
    """Filter a JSON response body; returns (status, new_body)."""
    wire = _filter_list_wire(body, allowed)
    if wire is not None:
        _count("fused", body)
        return wire
    _count("python", body)
    try:
        doc = json.loads(body)
    except ValueError as e:
        raise FilterError(f"response is not JSON: {e}") from None
    if not isinstance(doc, dict):
        raise FilterError("response is not an object")
    kind = doc.get("kind", "")
    if kind == "Table":
        rows = doc.get("rows") or []
        kept = []
        for row in rows:
            obj = row.get("object") or {}
            ns, name = _meta_pair(obj)
            if allowed.allows(ns, name):
                kept.append(row)
        if len(kept) == len(rows):
            return 200, body  # nothing dropped: byte-identical
        doc["rows"] = kept
        return 200, json.dumps(doc).encode()
    if kind.endswith("List"):
        items = doc.get("items") or []
        kept = [o for o in items if allowed.allows(*_meta_pair(o))]
        if len(kept) == len(items):
            # nothing dropped — the common admin/owner case: skip the
            # re-serialize of a multi-MB body and keep bytes identical
            return 200, body
        doc["items"] = kept
        return 200, json.dumps(doc).encode()
    # single object
    ns, name = _meta_pair(doc)
    if allowed.allows(ns, name):
        return 200, body
    return 404, b""


def _filter_proto_list_native(body: bytes, raw: bytes,
                              allowed: AllowedSet, table: bool = False):
    """Native proto list/Table filtering (graphcore.cpp
    proto_list_spans / proto_table_spans): same record-set comparison
    as the JSON wire path, ~12x faster than the pure-Python varint
    walker at 100k items. Returns (status, new_body) or None to fall
    back (scanner bailed)."""
    from .. import native

    scan = native.proto_table_spans(raw) if table \
        else native.proto_list_spans(raw)
    if scan is None:
        return None
    item_spans, keys = scan
    recs = keys.split(b"\x1e")
    pairs_rec = allowed.pairs_records()
    drop_spans: list = []
    idx = 0
    for rec in recs[:len(recs) - 1]:
        if rec not in pairs_rec:
            drop_spans.append(idx)
        idx += 1
    if not drop_spans:
        return 200, body  # byte-identical passthrough
    spans = item_spans[drop_spans].tolist()
    parts = []
    pos = 0
    for s, e in spans:
        parts.append(raw[pos:s])
        pos = e
    parts.append(raw[pos:])
    return 200, kubeproto.replace_unknown_raw(body, b"".join(parts))


def filter_body_proto(body: bytes, allowed: AllowedSet,
                      input: ResolveInput) -> tuple[int, bytes]:
    """Filter a kube-protobuf response body; returns (status, new_body).

    Lists are filtered by dropping disallowed ``items`` from the inner
    message (kept bytes are untouched); single objects never need parsing
    — the request path already names the object, so the decision is the
    allowed-set test and the body passes through byte-identical."""
    _count("python", body)  # native scan or not, a Python loop decides
    try:
        _, kind, raw = kubeproto.decode_unknown(body)
        if kind == "Table":
            # rows filtered at the wire level (kept rows byte-identical);
            # an un-keyable row (includeObject=None) raises ProtoError ->
            # a clean 401, never a 500 (reference decodes the full Table,
            # responsefilterer.go:349-374)
            wire = _filter_proto_list_native(body, raw, allowed,
                                             table=True)
            if wire is not None:
                return wire
            new_raw = kubeproto.filter_table_raw(raw, allowed.allows)
            return 200, kubeproto.replace_unknown_raw(body, new_raw)
        if kind.endswith("List"):
            wire = _filter_proto_list_native(body, raw, allowed)
            if wire is not None:
                return wire
            new_raw = kubeproto.filter_list_raw(raw, allowed.allows)
            return 200, kubeproto.replace_unknown_raw(body, new_raw)
    except kubeproto.ProtoError as e:
        raise FilterError(f"malformed kube protobuf response: {e}") \
            from None
    # single object: keyed on the request path, body untouched
    if allowed.allows(input.namespace or "", input.name or ""):
        return 200, body
    return 404, b""


def apply_filter(resp: ProxyResponse, allowed: AllowedSet,
                 input: ResolveInput) -> ProxyResponse:
    """Filter an upstream response in place (the reference hooks
    ReverseProxy.ModifyResponse, pkg/proxy/server.go:103-112)."""
    if resp.status != 200:
        # upstream errors pass through unfiltered
        metrics.counter("proxy_body_filter_total", path="passthrough").inc()
        return resp
    ctype = resp.content_type
    try:
        if ctype and "protobuf" in ctype:
            status, body = filter_body_proto(resp.body, allowed, input)
        elif ctype and "json" not in ctype:
            return kube_status(
                401, f"cannot filter content type {ctype!r}")
        else:
            status, body = filter_body(resp.body, allowed, input)
    except FilterError as e:
        return kube_status(401, str(e))
    if status == 404:
        info = input.request
        return kube_status(
            404,
            f'{info.resource} "{input.name}" not found',
            "NotFound",
        )
    headers = dict(resp.headers)
    headers["Content-Length"] = str(len(body))
    return ProxyResponse(status=200, headers=headers, body=body)


def runs_off_loop(resp: ProxyResponse) -> bool:
    """Whether :func:`filter_response` belongs on a worker thread."""
    return resp.status == 200 and len(resp.body) >= OFF_LOOP_BYTES


def filter_response(resp: ProxyResponse, allowed: AllowedSet,
                    input: ResolveInput) -> ProxyResponse:
    """:func:`apply_filter` as stage ``body_filter``, on whatever thread
    the caller chose by :func:`runs_off_loop`: the stage begins and ends
    around the filter alone, so a hop to a worker is not in it."""
    with tracer.stage("body_filter",
                      metrics.histogram("proxy_body_filter_seconds"),
                      metrics.counter("proxy_body_filter_cpu_seconds_total")):
        return apply_filter(resp, allowed, input)

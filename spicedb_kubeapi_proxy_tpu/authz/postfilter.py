"""PostFilter: per-item bulk permission checks over list responses.

Mirrors /root/reference/pkg/authz/postfilter.go:17-182: the recorded list
(or table) response is parsed, ONE CheckBulkPermissions request is built
covering every item x every postfilter rule, and items whose checks all
pass are kept. The bulk is one logical check at one revision
(engine.check_bulk): one device dispatch per 16,384 items
(``Engine.CHECK_PIPELINE_CHUNK``), so the device's share hardly grows
with the list, but the host's does: parsing the body, resolving each
item's templates into a check, the decision cache's probe and put an
item, and writing what is kept are all linear in the items, on a worker
thread, under the interpreter lock: 24.7 us an item on a v5e host, 247 ms
for a 10,000-object list of which the device works 0.7 ms (a builder's
chip runs, PR 35: PERF.md section 5).

Stage ``postfilter`` (``proxy_postfilter_seconds``) brackets one
filtered list; inside it ``postfilter_parse``, ``postfilter_resolve``
and ``postfilter_write`` bracket the three host steps, and the bulk check
between them is the engine's own stages.
"""

from __future__ import annotations

import dataclasses
import json

from ..engine import CheckItem, Engine
from ..obs.trace import tracer
from ..rules.compile import PostFilter
from ..rules.input import ResolveInput
from ..proxy.types import ProxyResponse, kube_status
from ..utils.metrics import metrics


def _item_input(input: ResolveInput, obj: dict) -> ResolveInput:
    """Per-item ResolveInput: the item's metadata drives name/namespace
    (reference postfilter.go builds per-object inputs)."""
    meta = obj.get("metadata") or {}
    name = meta.get("name") or ""
    ns = meta.get("namespace") or ""
    if input.request.resource == "namespaces":
        ns = ""
    nsname = f"{ns}/{name}" if ns else name
    return dataclasses.replace(
        input, name=name, namespace=ns, namespaced_name=nsname, object=obj,
    )


def filter_list_response(engine: Engine, post_filters: list[PostFilter],
                         input: ResolveInput,
                         resp: ProxyResponse,
                         context: dict = None) -> ProxyResponse:
    if resp.status != 200:
        return resp
    with tracer.stage("postfilter",
                      metrics.histogram("proxy_postfilter_seconds")):
        return _filter(engine, post_filters, input, resp, context)


def _filter(engine: Engine, post_filters: list[PostFilter],
            input: ResolveInput, resp: ProxyResponse,
            context) -> ProxyResponse:
    with tracer.stage("postfilter_parse",
                      metrics.histogram("proxy_postfilter_parse_seconds")):
        try:
            doc = json.loads(resp.body)
        except ValueError:
            return kube_status(401, "postfilter: response is not JSON")
        kind = doc.get("kind", "")
        if kind == "Table":
            entries = doc.get("rows") or []
            objs = [(row.get("object") or {}) for row in entries]
        elif kind.endswith("List"):
            entries = doc.get("items") or []
            objs = entries
        else:
            return kube_status(401, f"postfilter: unexpected kind {kind!r}")

    # one bulk check covering items x rules (postfilter.go:58-182)
    items: list[CheckItem] = []
    item_index: list[int] = []  # check index -> entry index
    with tracer.stage("postfilter_resolve",
                      metrics.histogram("proxy_postfilter_resolve_seconds")):
        for i, obj in enumerate(objs):
            per_item = _item_input(input, obj)
            for pf in post_filters:
                for rel in pf.rel.generate(per_item):
                    items.append(CheckItem(
                        rel.resource_type, rel.resource_id,
                        rel.resource_relation, rel.subject_type,
                        rel.subject_id, rel.subject_relation or None,
                    ))
                    item_index.append(i)
    results = (engine.check_bulk(items, context=context) if context
               else engine.check_bulk(items))
    with tracer.stage("postfilter_write",
                      metrics.histogram("proxy_postfilter_write_seconds")):
        ok = [True] * len(objs)
        for ci, passed in enumerate(results):
            if not passed:
                ok[item_index[ci]] = False
        kept = [e for i, e in enumerate(entries) if ok[i]]
        if kind == "Table":
            doc["rows"] = kept
        else:
            doc["items"] = kept
        body = json.dumps(doc).encode()
    metrics.counter("proxy_postfilter_items_total").inc(len(objs))
    metrics.counter("proxy_postfilter_kept_total").inc(len(kept))
    headers = dict(resp.headers)
    headers["Content-Length"] = str(len(body))
    return ProxyResponse(status=200, headers=headers, body=body)

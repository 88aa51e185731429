"""PostFilter: per-item bulk permission checks over list responses.

Mirrors /root/reference/pkg/authz/postfilter.go:17-182: the recorded list
(or table) response is parsed, ONE CheckBulkPermissions request is built
covering every item x every postfilter rule, and items whose checks all
pass are kept. What the reference asks once an item is asked here once a
distinct question: a rule's templates are resolved once per distinct value
of what they read of an object (``_rule_checks``), only distinct checks
enter the bulk, and verdicts fan back out to the objects. The bulk is one
logical check at one revision (engine.check_bulk): one device dispatch
per 16,384 checks (``Engine.CHECK_PIPELINE_CHUNK``), so the device's
share hardly grows with the list, but the host's does: parsing the body
and keying the objects are linear in the objects, resolving templates,
the decision cache's probe and put, and encoding are linear in the
distinct checks, all on a worker thread, under the interpreter lock. A
10,000-service list in 6,332 namespaces takes 127.8 ms on a v5e host
(235.6 before): parse 17.1, resolve 28.4 (4.5 us a distinct namespace),
the cache's pass 58.6 (9.3 us a check), encode to device wait 10.4 of
which the device works 0.68, write 4.9 (a builder's chip runs, PR 36:
PERF.md section 5).

Stage ``postfilter`` (``proxy_postfilter_seconds``) brackets one
filtered list; inside it ``postfilter_parse``, ``postfilter_resolve``
and ``postfilter_write`` bracket the three host steps, and the bulk check
between them is the engine's own stages.
"""

from __future__ import annotations

import json
from itertools import compress
from operator import and_
from typing import Sequence

from ..engine import CheckItem, Engine
from ..obs.trace import tracer
from ..rules.compile import (
    ITEM_ROOTS, PostFilter, RelationshipExpr, RelFields,
)
from ..rules.input import ResolveInput
from ..proxy.types import ProxyResponse, kube_status
from ..utils.metrics import metrics


def _rule_checks(rel: RelationshipExpr, data: dict,
                 checks: dict[RelFields, int], objs: list[dict],
                 namespaces: list[str], names: list[str]
                 ) -> tuple[list[list[int]], Sequence[int]]:
    """One postfilter rule over the objects of one list -> (the places in
    ``checks`` of each resolution's checks, object -> its resolution).

    An object is keyed by the values of the per-object roots the rule's
    templates read (``RelationshipExpr.refs``, known since the rule
    compiled): the templates are resolved once a distinct key, and every
    object with that key shares the checks. A rule that reads ``object``
    or ``metadata`` has no such key: it resolves per object."""
    reads = rel.refs & ITEM_ROOTS
    resolve = rel.per_list(data)
    asked: list[list[int]] = []

    def ask(namespace: str, name: str) -> None:
        data["name"] = name
        data["namespace"] = namespace
        data["namespacedName"] = data["resourceId"] = (
            f"{namespace}/{name}" if namespace else name)
        asked.append([checks.setdefault(fields, len(checks))
                      for fields in resolve()])

    if reads & {"object", "metadata", "this"}:
        for obj, namespace, name in zip(objs, namespaces, names):
            data["object"] = obj
            if "metadata" in obj:
                data["metadata"] = obj["metadata"]
            else:
                data.pop("metadata", None)
            ask(namespace, name)
        return asked, range(len(objs))
    # the part of (namespace, name) the rule cannot read is left out of
    # the key: one that reads neither is resolved once a list
    unread = [""] * len(objs)
    keys = list(zip(
        namespaces if reads & {"namespace", "namespacedName", "resourceId"}
        else unread,
        names if reads & {"name", "namespacedName", "resourceId"}
        else unread))
    place = {}
    for key in dict.fromkeys(keys):
        place[key] = len(asked)
        ask(*key)
    return asked, list(map(place.__getitem__, keys))


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
                      metrics.histogram("proxy_postfilter_parse_seconds"),
                      metrics.counter(
                          "proxy_postfilter_parse_cpu_seconds_total")):
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

    # one bulk check of the distinct checks of items x rules
    # (postfilter.go:58-182 asks every one; an equal check at the same
    # revision is the same question)
    checks: dict[RelFields, int] = {}
    rules = []
    with tracer.stage("postfilter_resolve",
                      metrics.histogram("proxy_postfilter_resolve_seconds"),
                      metrics.counter(
                          "proxy_postfilter_resolve_cpu_seconds_total")):
        if objs:  # no object, no template resolved: nothing to refuse
            metas = [obj.get("metadata") or {} for obj in objs]
            names = [meta.get("name") or "" for meta in metas]
            namespaces = ([""] * len(objs)
                          if input.request.resource == "namespaces" else
                          [meta.get("namespace") or "" for meta in metas])
            data = input.template_data()
            rules = [_rule_checks(pf.rel, data, checks, objs, namespaces,
                                  names) for pf in post_filters]
        items = [CheckItem(rtype, rid, rel, stype, sid, srel or None)
                 for rtype, rid, rel, stype, sid, srel in checks]
    results = (engine.check_bulk(items, context=context) if context
               else engine.check_bulk(items))
    with tracer.stage("postfilter_write",
                      metrics.histogram("proxy_postfilter_write_seconds"),
                      metrics.counter(
                          "proxy_postfilter_write_cpu_seconds_total")):
        keep = [True] * len(objs)
        for asked, resolution in rules:
            ok = [all(map(results.__getitem__, cs)) for cs in asked]
            keep = map(and_, keep, map(ok.__getitem__, resolution))
        kept = list(compress(entries, keep))
        if kind == "Table":
            doc["rows"] = kept
        else:
            doc["items"] = kept
        body = json.dumps(doc).encode()
    metrics.counter("proxy_postfilter_resolved_total").inc(
        sum(len(asked) for asked, _ in rules))
    metrics.counter("proxy_postfilter_items_total").inc(len(objs))
    metrics.counter("proxy_postfilter_kept_total").inc(len(kept))
    headers = dict(resp.headers)
    headers["Content-Length"] = str(len(body))
    return ProxyResponse(status=200, headers=headers, body=body)

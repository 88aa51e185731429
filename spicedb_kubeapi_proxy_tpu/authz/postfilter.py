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
share hardly grows with the list, but the host's does, all on a worker
thread, under the interpreter lock but for the native scan.

Where no rule reads more of an object than its namespace and name (the
common rule: ``namespace:{{namespace}}#view@user:{{user.name}}``), the
body is never parsed into objects: one native call (``native.
json_list_keys``, the prefilter's scanner) gives every item's byte span
and the id of its key, the distinct keys are decoded once each, the
templates resolved once a key in the order keys first occur, and the kept
items are written back as the upstream's own bytes. Otherwise (a rule
reads ``object``, ``metadata`` or ``this``; the scanner refuses the body;
no native library) the body goes through ``json.loads`` and the kept
objects through ``json.dumps``; both paths ask the same checks in the
same order. A 10,000-service list (1.19 MB) in 6,293 namespaces takes
54.0 ms of a v5e host, 84.6 by the json path: parse 3.1 (17.3), resolve
21.4 (27.8; 3.4 us a distinct namespace), the cache's passes 14.8,
encode to device wait 9.9 of which the device works 0.68, write 2.2
(4.9) (PERF.md section 5).

Stage ``postfilter`` (``proxy_postfilter_seconds``) brackets one
filtered list; inside it ``postfilter_parse``, ``postfilter_resolve``
and ``postfilter_write`` bracket the three host steps, and the bulk check
between them is the engine's own stages. ``proxy_postfilter_total{path}``
says which path read a list.
"""

from __future__ import annotations

import json
from itertools import compress
from operator import and_
from typing import Optional, Sequence

import numpy as np

from .. import native
from ..engine import CheckItem, Engine
from ..obs.trace import tracer
from ..rules.compile import (
    ITEM_ROOTS, PostFilter, RelationshipExpr, RelFields,
)
from ..rules.input import ResolveInput
from ..proxy.types import ProxyResponse, kube_status
from ..utils.metrics import metrics

# the per-object roots that read an object's namespace, its name, or more
# of it than both
NAMESPACE_ROOTS = frozenset({"namespace", "namespacedName", "resourceId"})
NAME_ROOTS = frozenset({"name", "namespacedName", "resourceId"})
OBJECT_ROOTS = frozenset({"object", "metadata", "this"})


def _rule_checks(rel: RelationshipExpr, data: dict,
                 checks: dict[RelFields, int], namespaces: list[str],
                 names: list[str], objs: Sequence[dict] = ()
                 ) -> tuple[list[int], list[int], Sequence[int]]:
    """One postfilter rule over the objects of one list -> (the places in
    ``checks`` of every resolution's checks, one resolution after the
    other; where each resolution's end among them; object -> its
    resolution).

    An object is keyed by the values of the per-object roots the rule's
    templates read (``RelationshipExpr.refs``, known since the rule
    compiled): the templates are resolved once a distinct key, and every
    object with that key shares the checks. A rule that reads ``object``
    or ``metadata`` has no such key: it resolves per object of ``objs``."""
    reads = rel.refs & ITEM_ROOTS
    resolve = rel.per_list(data)
    asked: list[int] = []
    ends: list[int] = []

    def ask(namespace: str, name: str) -> None:
        data["name"] = name
        data["namespace"] = namespace
        data["namespacedName"] = data["resourceId"] = (
            f"{namespace}/{name}" if namespace else name)
        asked.extend([checks.setdefault(fields, len(checks))
                      for fields in resolve()])
        ends.append(len(asked))

    if reads & OBJECT_ROOTS:
        for obj, namespace, name in zip(objs, namespaces, names):
            data["object"] = obj
            if "metadata" in obj:
                data["metadata"] = obj["metadata"]
            else:
                data.pop("metadata", None)
            ask(namespace, name)
        return asked, ends, range(len(objs))
    # the part of (namespace, name) the rule cannot read is left out of
    # the key: one that reads neither is resolved once a list
    unread = [""] * len(names)
    keys = list(zip(namespaces if reads & NAMESPACE_ROOTS else unread,
                    names if reads & NAME_ROOTS else unread))
    place = {}
    for key in dict.fromkeys(keys):
        place[key] = len(ends)
        ask(*key)
    return asked, ends, list(map(place.__getitem__, keys))


def _unescape(raw: str) -> str:
    return json.loads(f'"{raw}"') if "\\" in raw else raw


def _scan(body: bytes, post_filters: list[PostFilter],
          input: ResolveInput) -> Optional[tuple]:
    """The body read by the native scanner, where no rule reads more of an
    object than its namespace and name -> (array span, items' byte spans,
    item -> its key, the distinct keys' namespaces and names in the order
    they first occur); None where ``json.loads`` has to read it."""
    reads = frozenset().union(*(pf.rel.refs & ITEM_ROOTS
                                for pf in post_filters))
    if reads & OBJECT_ROOTS:
        return None
    # a namespace's own namespace reads "" (the json path's rule too)
    scan = native.json_list_keys(
        body, bool(reads & NAMESPACE_ROOTS)
        and input.request.resource != "namespaces",
        bool(reads & NAME_ROOTS))
    if scan is None or scan[0][0] < 0:
        # (an absent array: the json path answers with an empty one)
        return None
    (lo, hi), spans, ids, keys, esc = scan
    cols = keys.decode("utf-8").split("\x1e")
    k = len(cols) // 2
    namespaces, names = cols[:k], cols[k:2 * k]
    if len(esc):
        # decoded exactly, as json.loads would; two escapes of one string
        # are one key, at the place the first of them occurs
        for i in esc.tolist():
            namespaces[i] = _unescape(namespaces[i])
            names[i] = _unescape(names[i])
        place: dict = {}
        ids = np.fromiter((place.setdefault(key, len(place))
                           for key in zip(namespaces, names)),
                          np.int32, k)[ids]
        namespaces = [ns for ns, _ in place]
        names = [name for _, name in place]
    return lo, hi, spans, ids, namespaces, names


def _passed(verdicts: np.ndarray, asked: list[int],
            ends: list[int]) -> np.ndarray:
    """Whether every check of each resolution passed: ``asked`` the
    places of its checks among ``verdicts``, resolution after resolution,
    ``ends`` where each resolution's end among them."""
    failed = np.zeros(len(asked) + 1, np.intp)
    np.cumsum(~verdicts[np.fromiter(asked, np.intp, len(asked))],
              out=failed[1:])
    at = np.fromiter(ends, np.intp, len(ends))
    return failed[at] == failed[at - np.diff(at, prepend=0)]


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
        scan = _scan(resp.body, post_filters, input)
        metrics.counter("proxy_postfilter_total",
                        path="python" if scan is None else "native").inc()
        if scan is not None:
            namespaces, names = scan[-2:]
            objs = ()
        else:
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
                return kube_status(401,
                                   f"postfilter: unexpected kind {kind!r}")
            metas = [obj.get("metadata") or {} for obj in objs]
            names = [meta.get("name") or "" for meta in metas]
            namespaces = ([""] * len(objs)
                          if input.request.resource == "namespaces" else
                          [meta.get("namespace") or "" for meta in metas])

    # one bulk check of the distinct checks of items x rules
    # (postfilter.go:58-182 asks every one; an equal check at the same
    # revision is the same question)
    checks: dict[RelFields, int] = {}
    rules = []
    with tracer.stage("postfilter_resolve",
                      metrics.histogram("proxy_postfilter_resolve_seconds"),
                      metrics.counter(
                          "proxy_postfilter_resolve_cpu_seconds_total")):
        if names:  # no object, no template resolved: nothing to refuse
            data = input.template_data()
            rules = [_rule_checks(pf.rel, data, checks, namespaces, names,
                                  objs) for pf in post_filters]
        items = [CheckItem(rtype, rid, rel, stype, sid, srel or None)
                 for rtype, rid, rel, stype, sid, srel in checks]
    results = (engine.check_bulk(items, context=context) if context
               else engine.check_bulk(items))
    with tracer.stage("postfilter_write",
                      metrics.histogram("proxy_postfilter_write_seconds"),
                      metrics.counter(
                          "proxy_postfilter_write_cpu_seconds_total")):
        verdicts = np.fromiter(results, bool, len(results))
        if scan is not None:
            body, n_items, n_kept = _write_scanned(
                resp.body, rules, verdicts, len(names), *scan[:4])
        else:
            keep = [True] * len(objs)
            for asked, ends, resolution in rules:
                ok = _passed(verdicts, asked, ends).tolist()
                keep = map(and_, keep, map(ok.__getitem__, resolution))
            kept = list(compress(entries, keep))
            if kind == "Table":
                doc["rows"] = kept
            else:
                doc["items"] = kept
            body = json.dumps(doc).encode()
            n_items, n_kept = len(objs), len(kept)
    metrics.counter("proxy_postfilter_resolved_total").inc(
        sum(len(ends) for _, ends, _ in rules))
    metrics.counter("proxy_postfilter_items_total").inc(n_items)
    metrics.counter("proxy_postfilter_kept_total").inc(n_kept)
    headers = dict(resp.headers)
    headers["Content-Length"] = str(len(body))
    return ProxyResponse(status=200, headers=headers, body=body)


def _write_scanned(body: bytes, rules: list, verdicts: np.ndarray,
                   n_keys: int, lo: int, hi: int, spans: np.ndarray,
                   ids: np.ndarray) -> tuple[bytes, int, int]:
    """A list the native scanner read, its verdicts in -> (the body with
    the kept items' own bytes joined back between the array's brackets,
    items, kept): a verdict a key, spread to the items by their key ids."""
    ok = np.ones(n_keys, bool)
    for asked, ends, resolution in rules:
        passed = _passed(verdicts, asked, ends)
        # as many resolutions as keys: each key its own, in order
        ok &= passed if len(ends) == n_keys else passed[resolution]
    keep = ok[ids]
    n_kept = int(np.count_nonzero(keep))
    if n_kept < len(ids):
        body = b"".join((body[:lo], b",".join(
            [body[s:e] for s, e in spans[keep].tolist()]), body[hi:]))
    return body, len(ids), n_kept

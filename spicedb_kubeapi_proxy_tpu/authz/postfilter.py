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
same order. A rule whose every per-object field is a bare root (the
common rule's ``{{namespace}}``; ``RelExpr.columns``) evaluates no
template a key: its checks are zipped from the distinct keys' columns
(``_column_checks``), in the per-key loop's order. A 10,000-service list
(1.19 MB) in 6,303 namespaces takes 40.2 ms of a v5e host: parse 3.1,
resolve 9.4 (21.5 with a template evaluated a key; what is left is a
``CheckItem`` a check), the cache's passes 14.7, encode to device wait
9.2 of which the device works 0.68, write 1.4 (PERF.md section 5).

Stage ``postfilter`` (``proxy_postfilter_seconds``) brackets one
filtered list; inside it ``postfilter_parse``, ``postfilter_resolve``
and ``postfilter_write`` bracket the three host steps, and the bulk check
between them is the engine's own stages. ``proxy_postfilter_total{path}``
says which path read a list, ``proxy_postfilter_column_resolved_total``
how many of ``proxy_postfilter_resolved_total`` were read off columns.
"""

from __future__ import annotations

import json
from itertools import chain, compress, count
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
JOINED_ROOTS = NAMESPACE_ROOTS & NAME_ROOTS
OBJECT_ROOTS = frozenset({"object", "metadata", "this"})


def _rule_checks(rel: RelationshipExpr, data: dict,
                 checks: dict[RelFields, int], namespaces: list[str],
                 names: list[str], objs: Sequence[dict] = (),
                 scanned: bool = False
                 ) -> tuple[list[int], list[int], Sequence[int], bool]:
    """One postfilter rule over the objects of one list -> (the places in
    ``checks`` of every resolution's checks, one resolution after the
    other; where each resolution's end among them; object -> its
    resolution; whether the resolutions were read off columns).

    An object is keyed by the values of the per-object roots the rule's
    templates read (``RelationshipExpr.refs``, known since the rule
    compiled): the templates are resolved once a distinct key, and every
    object with that key shares the checks. A rule that reads ``object``
    or ``metadata`` has no key: it resolves per object of ``objs``.
    ``scanned``: the (namespace, name) pairs are the native scanner's
    distinct keys. Where each of the rule's per-object fields is a bare
    root (``RelationshipExpr.columns``), the distinct keys' checks are
    zipped from their columns, no template evaluated for a key: the same
    checks in the same order as the per-key loop."""
    reads = rel.refs & ITEM_ROOTS
    if not reads & OBJECT_ROOTS:
        key_ns, key_names, resolution = _keys(reads, namespaces, names,
                                              scanned)
        columns = rel.columns
        # (a value of the json path that is not a string is made one by
        # the per-key loop's templates)
        if columns is not None and (scanned or all(
                isinstance(v, str) for v in chain(key_ns, key_names))):
            asked = _column_checks(rel, data, checks, columns, key_ns,
                                   key_names)
            return asked, list(range(1, len(asked) + 1)), resolution, True
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
        return asked, ends, range(len(objs)), False
    for key in zip(key_ns, key_names):
        ask(*key)
    return asked, ends, resolution, False


def _keys(reads: frozenset, namespaces: list[str], names: list[str],
          scanned: bool) -> tuple[list[str], list[str], Sequence[int]]:
    """The distinct keys of a rule that reads ``reads`` of the objects, in
    the order they first occur -> (their namespaces, their names, object
    -> its key). The part of (namespace, name) the rule cannot read is
    left out of the key: one that reads neither has one key a list."""
    unread = [""] * len(names)
    key_ns = namespaces if reads & NAMESPACE_ROOTS else unread
    key_names = names if reads & NAME_ROOTS else unread
    if scanned and key_ns == namespaces and key_names == names:
        # the scanner's keys, each its own
        return key_ns, key_names, range(len(names))
    keys = list(zip(key_ns, key_names))
    place = dict(zip(dict.fromkeys(keys), count()))
    return ([ns for ns, _ in place], [name for _, name in place],
            list(map(place.__getitem__, keys)))


def _column_checks(rel: RelationshipExpr, data: dict,
                   checks: dict[RelFields, int], columns: dict[int, str],
                   key_ns: list[str], key_names: list[str]) -> list[int]:
    """The checks of a rule whose per-object fields are the bare roots
    ``columns`` names, one a key, entered in ``checks`` as the per-key
    loop enters them -> their places there."""
    roots = {"namespace": key_ns, "name": key_names}
    if JOINED_ROOTS & set(columns.values()):
        roots["namespacedName"] = roots["resourceId"] = [
            f"{ns}/{name}" if ns else name
            for ns, name in zip(key_ns, key_names)]
    return [checks.setdefault(fields, len(checks))
            for fields in rel.resolve_columns(data, roots, len(key_ns))]


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
                                  objs, scan is not None)
                     for pf in post_filters]
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
            for asked, ends, resolution, _ in rules:
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
        sum(len(ends) for _, ends, _, _ in rules))
    metrics.counter("proxy_postfilter_column_resolved_total").inc(
        sum(len(ends) for _, ends, _, by_column in rules if by_column))
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
    for asked, ends, resolution, _ in rules:
        passed = _passed(verdicts, asked, ends)
        # as many resolutions as keys: each key its own, in order
        ok &= passed if len(ends) == n_keys else passed[resolution]
    keep = ok[ids]
    n_kept = int(np.count_nonzero(keep))
    if n_kept < len(ids):
        body = b"".join((body[:lo], b",".join(
            [body[s:e] for s, e in spans[keep].tolist()]), body[hi:]))
    return body, len(ids), n_kept

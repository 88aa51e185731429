"""The postfilter's two ways of reading a list: the native scan
(``native.json_list_keys``: item spans and key ids, the kept items' own
bytes written back) and ``json.loads`` / ``json.dumps``. On the same
answer both keep the same objects, count the same items, kept objects and
resolutions, and ask the same bulk of checks in the same order; a rule
that reads the object itself, or a body the scanner refuses, takes the
json path, and ``proxy_postfilter_total{path}`` says which read a list.
The scanner's own spans, ids and keys are fuzzed against a reader built
on ``json.loads``.
"""

from __future__ import annotations

import json
import random

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu import native
from spicedb_kubeapi_proxy_tpu.authz import postfilter
from spicedb_kubeapi_proxy_tpu.engine import Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyResponse
from spicedb_kubeapi_proxy_tpu.rules import RequestMeta
from spicedb_kubeapi_proxy_tpu.rules.expr import ExprError
from spicedb_kubeapi_proxy_tpu.rules.input import ResolveInput, UserInfo
from spicedb_kubeapi_proxy_tpu.rules.matcher import MapMatcher
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

BY_NAMESPACE = 'tpl: "namespace:{{namespace}}#view@user:{{user.name}}"'
BY_NAME = 'tpl: "pod:{{name}}#view@user:{{user.name}}"'
BY_NSNAME = 'tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"'
BY_PREFIXED = ("tupleSet: '[\"namespace:x\" + namespace + \"#view@user:\" + "
               "user.name]'")
BY_LABEL = ('tpl: "namespace:{{object.metadata.labels.team}}#view'
            '@user:{{user.name}}"')
TWO_CHECKS = ("tupleSet: '[\"namespace:\" + namespace + \"#view@user:\" + "
              "user.name, \"namespace:ns1#view@user:\" + user.name]'")
NO_CHECK = "tupleSet: '[]'"
GRANTS = ["namespace:ns1#viewer@user:alice", "namespace:ns3#viewer@user:alice",
          "namespace:x#viewer@user:alice", "pod:ns1/a#viewer@user:alice",
          "pod:ns2/c#viewer@user:alice", "pod:ns1/f#viewer@user:alice",
          "pod:b#viewer@user:alice", "pod:e#viewer@user:alice"]
PODS = [("ns1", "a", "x"), ("ns1", "b", "y"), ("ns2", "c", "x"),
        ("ns2", "d", "y"), ("ns3", "e", "x"), ("ns1", "f", "y")]
PATHS = ("native", "python")


@pytest.fixture(scope="module")
def engine():
    e = Engine()
    e.write_relationships([WriteOp("touch", parse_relationship(g))
                           for g in GRANTS])
    return e


def _objs(pods=PODS) -> list:
    return [{"kind": "Pod", "metadata": {"name": n, "namespace": ns,
                                         "labels": {"team": team}}}
            for ns, n, team in pods]


def _body(kind: str = "PodList", objs=None, **dumps) -> bytes:
    objs = _objs() if objs is None else objs
    if kind == "Table":
        doc = {"kind": "Table", "apiVersion": "meta.k8s.io/v1",
               "columnDefinitions": [{"name": "Name"}],
               "rows": [{"cells": [i], "object": o}
                        for i, o in enumerate(objs)]}
    else:
        doc = {"kind": kind, "apiVersion": "v1",
               "metadata": {"resourceVersion": "9"}, "items": objs}
    return json.dumps(doc, **dumps).encode()


ESCAPED = (b'{"kind":"PodList","items":['
           b'{"metadata":{"name":"a","namespace":"ns1"}},'
           b'{"metadata":{"name":"\\u0061","namespace":"n\\u00731"}},'
           b'{"metadata":{"name":"b","namespace":"n\\u0073\\u0031"}},'
           b'{"metadata":{"name":"\\u0065","namespace":"ns\\u0033"}},'
           b'{"metadata":{"name":"c","namespace":"ns2"}},'
           b'{"metadata":{"name":"a","namespace":"ns1"}}]}')
DUPLICATES = (b'{"kind":"PodList","items":['
              b'{"metadata":{"name":"a","namespace":"ns2"},'
              b'"metadata":{"name":"a","namespace":"ns1"}},'
              b'{"metadata":{"namespace":"ns1","name":"z","namespace":"ns2"}},'
              b'{"metadata":{"name":"e","namespace":"ns3"}}]}')
TABLE_DUPLICATES = (b'{"kind":"Table","rows":['
                    b'{"object":{"metadata":{"name":"a","namespace":"ns2"}},'
                    b'"object":{"metadata":{"name":"a","namespace":"ns1"}}},'
                    b'{"object":{"metadata":{"name":"d","namespace":"ns2"}}}]}')
NAMESPACES = _body("NamespaceList", [
    {"kind": "Namespace", "metadata": {"name": n, "namespace": n}}
    for n in ("ns1", "ns2", "ns3")])


def _case(id, body, checks, path="native", resource="pods", user="alice"):
    return pytest.param(body, checks, path, resource, user, id=id)


CASES = [
    _case("list", _body(), [BY_NAMESPACE]),
    _case("list-spaced", _body(indent=2), [BY_NSNAME]),
    _case("table", _body("Table"), [BY_NAMESPACE]),
    _case("table-by-name", _body("Table", separators=(",", ":")), [BY_NAME]),
    _case("escaped", ESCAPED, [BY_NAMESPACE]),
    _case("escaped-both", ESCAPED, [BY_NSNAME]),
    _case("escaped-ascii", _body(objs=_objs(
        PODS + [("日本", "é", "x"), ('ns"q', "tab\tname", "y")]),
        ensure_ascii=True), [BY_NSNAME]),
    _case("non-ascii", _body(objs=_objs(
        PODS + [("日本", "é", "x"), ("日本", "b", "y")]), ensure_ascii=False),
        [BY_NAMESPACE]),
    _case("duplicates-last-wins", DUPLICATES, [BY_NSNAME]),
    _case("table-duplicates-last-wins", TABLE_DUPLICATES, [BY_NAMESPACE]),
    _case("missing-metadata", _body(objs=[
        {"metadata": {"name": "b"}}, {"spec": {}}, {"metadata": {}},
        {"metadata": {"name": "e", "namespace": "ns3"}}]), [BY_PREFIXED]),
    _case("missing-namespace", _body(objs=[
        {"metadata": {"name": "a", "namespace": "ns1"}},
        {"metadata": {"name": "g"}}]), [BY_NAMESPACE]),
    _case("non-string-namespace", _body(objs=[
        {"metadata": {"name": "a", "namespace": 7}}]), [BY_NAME], "python"),
    _case("namespaces", NAMESPACES,
          ['tpl: "namespace:{{namespacedName}}#view@user:{{user.name}}"'],
          resource="namespaces"),
    _case("empty-items", _body(objs=[]), [BY_NAMESPACE]),
    _case("nothing-dropped", _body(objs=_objs([PODS[0], PODS[1], PODS[5]])),
          [BY_NAMESPACE]),
    _case("nothing-kept", _body(), [BY_NAMESPACE], user="mallory"),
    _case("not-json", b"{not json", [BY_NAMESPACE], "python"),
    _case("not-a-list", _body("Pod"), [BY_NAMESPACE], "python"),
    _case("array-absent", b'{"kind":"PodList","metadata":{}}',
          [BY_NAMESPACE], "python"),
    _case("reads-object", _body(), [BY_LABEL], "python"),
    _case("two-rules-two-roots", _body(), [BY_NAMESPACE, BY_NAME]),
    _case("two-rules-table", _body("Table"), [BY_NAME, BY_NSNAME]),
    _case("two-checks-a-key", _body(), [TWO_CHECKS]),
    _case("no-check", _body(), [NO_CHECK]),
]


def _listed(engine, body, checks, resource, user, force_json):
    """One answer through the postfilter -> (status, parsed body or None,
    counter deltas, the bulks asked)."""
    rules_yaml = ("apiVersion: authzed.com/v1alpha1\nkind: ProxyRule\n"
                  "metadata:\n  name: listed\nmatch:\n- apiVersion: v1\n"
                  f"  resource: {resource}\n  verbs: [list]\npostfilter:\n"
                  + "".join(f"- checkPermissionTemplate:\n    {c}\n"
                            for c in checks))
    info = parse_request_info("GET", f"/api/v1/{resource}", {})
    rules = MapMatcher.from_yaml(rules_yaml).match(
        RequestMeta.from_request(info))
    post_filters = [p for r in rules for p in r.post_filters]
    asked = []

    class Recorded:
        def check_bulk(self, items, **kw):
            asked.append(list(items))
            return engine.check_bulk(items, **kw)

    counters = [metrics.counter(f"proxy_postfilter_{c}_total")
                for c in ("items", "kept", "resolved")] + [
        metrics.counter("proxy_postfilter_total", path=p) for p in PATHS]
    before = [c.value for c in counters]
    scan = postfilter._scan
    if force_json:
        postfilter._scan = lambda *a: None
    try:
        resp = postfilter.filter_list_response(
            Recorded(), post_filters,
            ResolveInput.create(info, UserInfo(name=user)),
            ProxyResponse(status=200, headers={}, body=body))
        status, out = resp.status, resp.body
    except ExprError:
        status, out = 401, None
    finally:
        postfilter._scan = scan
    moved = [c.value - b for c, b in zip(counters, before)]
    return status, out, moved, asked


def _names(out: bytes) -> list:
    doc = json.loads(out)
    objs = ([r["object"] for r in doc["rows"]] if doc["kind"] == "Table"
            else doc["items"])
    return [(o.get("metadata") or {}).get("name") for o in objs]


@pytest.mark.parametrize("body,checks,path,resource,user", CASES)
def test_the_native_read_answers_what_the_json_read_answers(
        engine, body, checks, path, resource, user):
    """The same answer, read natively and by ``json.loads``: the same
    status, the same document, the same objects kept, the same counts and
    the same bulk of checks in the same order; the list is counted under
    the path the rules and the body choose; nothing dropped gives back the
    upstream's very bytes."""
    status, out, moved, asked = _listed(engine, body, checks, resource, user,
                                        force_json=False)
    j_status, j_out, j_moved, j_asked = _listed(
        engine, body, checks, resource, user, force_json=True)
    assert status == j_status
    assert asked == j_asked
    assert moved[:3] == j_moved[:3]
    assert moved[3:] == ([1, 0] if path == "native" else [0, 1])
    if status != 200:
        assert out is None or json.loads(out)["kind"] == "Status"
        return
    assert json.loads(out) == json.loads(j_out)
    assert _names(out) == _names(j_out)
    if path == "native" and moved[0] == moved[1]:  # nothing dropped
        assert out == body


def test_the_cases_keep_some_and_drop_some(engine):
    """The differential cases are not all trivial: among them lists that
    keep part, all and none of what they hold, escaped keys merged with
    their plain spellings, and a refusal."""
    got = {}
    for case in CASES:
        body, checks, path, resource, user = case.values
        got[case.id] = _listed(engine, body, checks, resource, user,
                               force_json=False)
    assert _names(got["list"][1]) == ["a", "b", "e", "f"]
    assert _names(got["two-rules-two-roots"][1]) == ["b", "e"]
    # "n\\u00731" and "n\\u0073\\u0031" are ns1: one key with "ns1"
    assert _names(got["escaped"][1]) == ["a", "a", "b", "e", "a"]
    assert got["escaped"][2][2] == 3
    assert _names(got["escaped-both"][1]) == ["a", "a", "c", "a"]
    assert got["escaped-both"][2][2] == 4
    assert _names(got["missing-metadata"][1]) == ["b", None, None]
    assert _names(got["non-ascii"][1]) == ["a", "b", "e", "f"]
    assert _names(got["escaped-ascii"][1]) == ["a", "c", "f"]
    assert _names(got["duplicates-last-wins"][1]) == ["a"]
    assert _names(got["nothing-kept"][1]) == []
    assert got["missing-namespace"][0] == 401
    assert got["not-json"][0] == 401
    assert _names(got["namespaces"][1]) == ["ns1", "ns3"]


# -- the scanner against a reader built on json.loads ------------------------

def _reference(body: bytes, read_namespace: bool, read_name: bool):
    """-> (items' (namespace, name) keys as decoded, the distinct keys in
    the order they first occur)."""
    doc = json.loads(body)
    table = doc["kind"] == "Table"
    objs = [(r.get("object") or {}) if table else r
            for r in doc["rows" if table else "items"]]
    keys = []
    for obj in objs:
        meta = obj.get("metadata") or {}
        keys.append((meta.get("namespace") or "" if read_namespace else "",
                     meta.get("name") or "" if read_name else ""))
    return keys, list(dict.fromkeys(keys))


NAMES = ["plain", "with/slash", 'quo"te', "back\\slash", "uni-日本",
         "tab\there", "new\nline", " sep", "na\x00me", "", "é"]


def _fuzzed(rng) -> bytes:
    entries = []
    for _ in range(rng.randrange(12)):
        meta = {}
        if rng.random() < 0.9:
            meta["name"] = rng.choice(NAMES)
        if rng.random() < 0.7:
            meta["namespace"] = rng.choice(NAMES[:5])
        if rng.random() < 0.3:
            meta["labels"] = {"k": rng.choice(NAMES)}
        obj = {"metadata": meta, "spec": {"n": rng.random()}}
        if rng.random() < 0.1:
            del obj["metadata"]
        entries.append(obj)
    table = rng.random() < 0.4
    if table:
        doc = {"kind": "Table", "columnDefinitions": [],
               "rows": [{"cells": [1], "object": o} for o in entries]}
    else:
        doc = {"kind": "PodList", "metadata": {"resourceVersion": "3"},
               "items": entries}
    sep = rng.choice([(",", ":"), (", ", ": "), (",\n ", " : ")])
    return json.dumps(doc, separators=sep,
                      ensure_ascii=rng.random() < 0.5).encode()


@pytest.mark.parametrize("read_namespace,read_name",
                         [(True, False), (False, True), (True, True),
                          (False, False)],
                         ids=["namespace", "name", "both", "neither"])
def test_the_keys_scan_agrees_with_json_loads(read_namespace, read_name):
    """Over random Lists and Tables (separators, ``ensure_ascii``,
    escapes, missing keys): every item's span parses to that item, the
    ids spread the distinct keys back to every item's key, the distinct
    keys come once each in the order they first occur (decoded: escaped
    spellings of one string are told apart, as raw bytes differ), and
    what is unread is empty."""
    rng = random.Random(4100 + 2 * read_namespace + read_name)
    for trial in range(200):
        body = _fuzzed(rng)
        scan = native.json_list_keys(body, read_namespace, read_name)
        assert scan is not None, body
        (lo, hi), spans, ids, keys, esc = scan
        doc = json.loads(body)
        entries = doc["rows" if doc["kind"] == "Table" else "items"]
        assert [json.loads(body[s:e]) for s, e in spans.tolist()] == entries
        assert json.loads(b"[" + body[lo:hi] + b"]") == entries
        cols = keys.decode().split("\x1e")
        k = len(cols) // 2
        assert len(cols) == 2 * k + 1 and cols[-1] == ""
        escaped = set(esc.tolist())
        decoded = [tuple(json.loads(f'"{c}"') for c in (ns, nm))
                   for ns, nm in zip(cols[:k], cols[k:2 * k])]
        for i, (ns, nm) in enumerate(zip(cols[:k], cols[k:2 * k])):
            assert (i in escaped) == ("\\" in ns + nm), (trial, i)
            if i not in escaped:
                assert decoded[i] == (ns, nm)
        item_keys, distinct = _reference(body, read_namespace, read_name)
        assert [decoded[i] for i in ids.tolist()] == item_keys, trial
        assert list(dict.fromkeys(decoded)) == distinct, trial
        # raw bytes are interned: first occurrences in order, ids dense
        first = list(dict.fromkeys(ids.tolist()))
        assert first == list(range(k)), trial


@pytest.mark.parametrize("body", [
    b'{"kind":"PodList","items":[1,2]}',
    b'{"kind":"PodList","items":[{}],"items":[{}]}',
    b'{"kind":"PodList","items":[{}]} trailing',
    b'{"kind":"PodList","items":[{"metadata":{"name":123}}]}',
    b'{"kind":"PodList","items":[{"metadata":{"namespace":null}}]}',
    b'{"kind":"PodList","items":[{"metadata":null}]}',
    b'{"kind":"PodList","items":null}',
    b'{"kind":"Table","rows":[{"object":null}]}',
    b'{"kind":"PodList","items":[{"metadata":{"name":"a\\qb"}}]}',
    b'{"kind":"Pod","metadata":{"name":"x"}}',
    b'[1,2,3]',
    b'not json',
], ids=["non-object-items", "duplicate-items", "trailing", "name-number",
        "namespace-null", "metadata-null", "items-null", "object-null",
        "invalid-escape", "single-object", "root-array", "not-json"])
def test_the_keys_scan_bails_where_the_filter_scan_bails(body):
    """What the filter's scan refuses, the keys' scan refuses too: the
    json path keeps authority."""
    assert native.json_list_keys(body, True, True) is None
    assert native.json_list_filter(
        body, b"", np.zeros(1, dtype=np.int64)) is None


def test_the_keys_scan_grows_past_its_first_guess():
    """More items than the first guess (a 64th of the body's bytes, plus
    1,024): the call counts them, and the second pass holds them all."""
    body = b'{"kind":"PodList","items":[' + b",".join(
        [b'{}'] * 5000) + b"]}"
    (lo, hi), spans, ids, keys, esc = native.json_list_keys(body, True, True)
    assert len(spans) == len(ids) == 5000
    assert ids.tolist() == [0] * 5000 and keys == b"\x1e\x1e"
    assert spans[:2].tolist() == [[27, 29], [30, 32]]

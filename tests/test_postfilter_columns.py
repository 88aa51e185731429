"""The postfilter's column path against its per-key loop.

A rule whose every per-object field is a bare item root (``{{namespace}}``,
``{{ name }}``, ``{{namespacedName}}``, ``{{resourceId}}``) has its distinct
keys' checks zipped from the key columns (``RelExpr.columns``,
``RelExpr.resolve_columns``); every other rule resolves its templates once
a key, or once an object. On the same answer, read natively or by
``json.loads``, the column path fills the list's checks with the same
tuples in the same order as the per-key loop and as a plain resolver kept
here, gives the same places, ends and resolutions, the same body and the
same errors; ``proxy_postfilter_column_resolved_total`` counts the
resolutions it answered.
"""

from __future__ import annotations

import json

import pytest

from spicedb_kubeapi_proxy_tpu import native
from spicedb_kubeapi_proxy_tpu.authz import postfilter
from spicedb_kubeapi_proxy_tpu.engine import Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyResponse
from spicedb_kubeapi_proxy_tpu.rules import RequestMeta
from spicedb_kubeapi_proxy_tpu.rules.compile import (
    ITEM_ROOTS, RelExpr, _compile_rel_string,
)
from spicedb_kubeapi_proxy_tpu.rules.expr import ExprError
from spicedb_kubeapi_proxy_tpu.rules.input import ResolveInput, UserInfo
from spicedb_kubeapi_proxy_tpu.rules.matcher import MapMatcher
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

BY_NAMESPACE = 'tpl: "namespace:{{namespace}}#view@user:{{user.name}}"'
BY_NAME = 'tpl: "pod:{{name}}#view@user:{{user.name}}"'
BY_NSNAME = 'tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"'
BY_RESOURCE_ID = 'tpl: "pod:{{resourceId}}#view@user:{{user.name}}"'
BY_SPACED = 'tpl: "namespace:{{ namespace }}#view@user:{{user.name}}"'
BY_SPLIT = 'tpl: "pod:{{split_name(resourceId)}}#view@user:{{user.name}}"'
BY_LABEL = ('tpl: "namespace:{{object.metadata.labels.team}}#view'
            '@user:{{user.name}}"')
BY_TUPLESET = ("tupleSet: '[\"namespace:\" + namespace + \"#view@user:\" + "
               "user.name, \"namespace:ns1#view@user:\" + user.name]'")
BY_TWO_ROOTS = 'tpl: "pod:{{name}}#view@user:{{namespace}}"'
GRANTS = ["namespace:ns1#viewer@user:alice", "namespace:ns3#viewer@user:alice",
          "namespace:x#viewer@user:alice", "pod:ns1/a#viewer@user:alice",
          "pod:ns2/c#viewer@user:alice", "pod:ns1/f#viewer@user:alice",
          "pod:b#viewer@user:alice", "pod:e#viewer@user:alice",
          "pod:a#viewer@user:alice"]
PODS = [("ns1", "a", "x"), ("ns1", "b", "y"), ("ns2", "c", "x"),
        ("ns2", "d", "y"), ("ns3", "e", "x"), ("ns1", "f", "y"),
        ("ns2", "a", "x"), ("ns3", "e", "y")]
# (rule templates, whether every rule takes the column path)
FORMS = {
    "namespace": ([BY_NAMESPACE], True),
    "name": ([BY_NAME], True),
    "namespacedName": ([BY_NSNAME], True),
    "resourceId": ([BY_RESOURCE_ID], True),
    "namespace-spaced": ([BY_SPACED], True),
    "two-roots": ([BY_TWO_ROOTS], True),
    "split_name-falls-back": ([BY_SPLIT], False),
    "object-falls-back": ([BY_LABEL], False),
    "tupleSet-falls-back": ([BY_TUPLESET], False),
    "two-rules-sharing-checks": ([BY_NAMESPACE, BY_SPACED], True),
    "column-and-tupleSet-sharing": ([BY_NAMESPACE, BY_TUPLESET], None),
    "two-rules-two-roots": ([BY_NAMESPACE, BY_NAME], True),
}


@pytest.fixture(scope="module")
def engine():
    e = Engine()
    e.write_relationships([WriteOp("touch", parse_relationship(g))
                           for g in GRANTS])
    return e


def _body(metas, kind: str = "PodList") -> bytes:
    objs = [{"kind": "Pod", "metadata": m} for m in metas]
    return json.dumps({"kind": kind, "apiVersion": "v1",
                       "metadata": {"resourceVersion": "9"},
                       "items": objs}).encode()


PODS_BODY = _body([{"name": n, "namespace": ns, "labels": {"team": t}}
                   for ns, n, t in PODS])
ESCAPED = (b'{"kind":"PodList","items":['
           b'{"metadata":{"name":"a","namespace":"ns1"}},'
           b'{"metadata":{"name":"\\u0061","namespace":"n\\u00731"}},'
           b'{"metadata":{"name":"b","namespace":"n\\u0073\\u0031"}},'
           b'{"metadata":{"name":"\\u0065","namespace":"ns\\u0033"}},'
           b'{"metadata":{"name":"c","namespace":"ns2"}},'
           b'{"metadata":{"name":"a","namespace":"ns1"}}]}')


def _post_filters(checks, resource="pods"):
    rules_yaml = ("apiVersion: authzed.com/v1alpha1\nkind: ProxyRule\n"
                  "metadata:\n  name: listed\nmatch:\n- apiVersion: v1\n"
                  f"  resource: {resource}\n  verbs: [list]\npostfilter:\n"
                  + "".join(f"- checkPermissionTemplate:\n    {c}\n"
                            for c in checks))
    info = parse_request_info("GET", f"/api/v1/{resource}", {})
    rules = MapMatcher.from_yaml(rules_yaml).match(
        RequestMeta.from_request(info))
    return info, [p for r in rules for p in r.post_filters]


def _per_key(rel, data, checks, namespaces, names, objs=(), scanned=False):
    """A plain resolver: every object keyed by the part of (namespace,
    name) its rule reads, each distinct key's templates resolved once
    through ``per_list`` in the order keys first occur; per object for a
    rule that reads the object."""
    reads = rel.refs & ITEM_ROOTS
    resolve = rel.per_list(data)
    asked, ends, resolution, seen = [], [], [], {}
    for i, (ns, name) in enumerate(zip(namespaces, names)):
        if reads & postfilter.OBJECT_ROOTS:
            data["object"] = objs[i]
            if "metadata" in objs[i]:
                data["metadata"] = objs[i]["metadata"]
            else:
                data.pop("metadata", None)
            key = i
        else:
            ns = ns if reads & postfilter.NAMESPACE_ROOTS else ""
            name = name if reads & postfilter.NAME_ROOTS else ""
            key = (ns, name)
        if key not in seen:
            seen[key] = len(ends)
            data["namespace"], data["name"] = ns, name
            data["namespacedName"] = data["resourceId"] = (
                f"{ns}/{name}" if ns else name)
            for fields in resolve():
                asked.append(checks.setdefault(fields, len(checks)))
            ends.append(len(asked))
        resolution.append(seen[key])
    return asked, ends, resolution, False


def _run(engine, body, checks, force_json, way, resource="pods",
         user="alice"):
    """One answer through the postfilter, its rules resolved ``way``
    ("columns": as shipped; "loop": no rule is columnar; "plain": every
    rule through ``_per_key``) -> (status, body or the error's message,
    every rule's (asked, ends, resolution), the list's checks in order,
    the bulk asked, counter deltas (resolved, column resolved))."""
    info, post_filters = _post_filters(checks, resource)
    calls, asked = [], []
    checks_seen = {}

    def recorded(rel, data, checks, *args):
        if way == "plain":
            out = _per_key(rel, data, checks, *args)
        else:
            out = rule_checks(rel, data, checks, *args)
        calls.append((out[0], out[1], list(out[2])))
        checks_seen["checks"] = checks
        return out

    class Recorded:
        def check_bulk(self, items, **kw):
            asked.append(list(items))
            return engine.check_bulk(items, **kw)

    counters = [metrics.counter("proxy_postfilter_resolved_total"),
                metrics.counter("proxy_postfilter_column_resolved_total")]
    before = [c.value for c in counters]
    rule_checks, scan = postfilter._rule_checks, postfilter._scan
    columns = RelExpr.columns
    postfilter._rule_checks = recorded
    if force_json:
        postfilter._scan = lambda *a: None
    if way == "loop":
        RelExpr.columns = property(lambda self: None)
    try:
        resp = postfilter.filter_list_response(
            Recorded(), post_filters,
            ResolveInput.create(info, UserInfo(name=user)),
            ProxyResponse(status=200, headers={}, body=body))
        status, out = resp.status, resp.body
    except ExprError as e:
        status, out = 401, str(e)
    finally:
        postfilter._rule_checks, postfilter._scan = rule_checks, scan
        RelExpr.columns = columns
    moved = [c.value - b for c, b in zip(counters, before)]
    return (status, out, calls, list(checks_seen.get("checks", {}).items()),
            asked, moved)


@pytest.mark.parametrize("force_json", [False, True], ids=["native", "json"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_column_path_asks_what_the_per_key_loop_asks(engine, form,
                                                         force_json):
    """Every rule form, on both body paths: the column path, the per-key
    loop and a plain resolver fill the list's checks with the same tuples
    in the same order, give every rule the same places, ends and
    resolutions, ask the same bulk and answer the same body."""
    checks, _ = FORMS[form]
    got = _run(engine, PODS_BODY, checks, force_json, "columns")
    loop = _run(engine, PODS_BODY, checks, force_json, "loop")
    plain = _run(engine, PODS_BODY, checks, force_json, "plain")
    assert got[0] == 200
    assert got[:5] == loop[:5] == plain[:5]
    assert got[2] and got[3] and got[4]


@pytest.mark.parametrize("force_json", [False, True], ids=["native", "json"])
@pytest.mark.parametrize("form", ["namespace", "namespacedName",
                                  "two-rules-two-roots"])
def test_escaped_keys_give_the_same_checks(engine, form, force_json):
    """A body whose keys are escaped (the scanner's ``esc`` branch: two
    spellings of one string are one key) asks what the json path and the
    per-key loop ask."""
    checks, _ = FORMS[form]
    got = _run(engine, ESCAPED, checks, force_json, "columns")
    assert got[0] == 200
    assert got[:5] == _run(engine, ESCAPED, checks, force_json,
                           "loop")[:5]
    assert got[3] == _run(engine, ESCAPED, checks, True, "plain")[3]


CLUSTER_SCOPED = _body([{"name": "b"}, {"name": "a", "namespace": "ns1"},
                        {"name": "e"}, {"name": "b", "namespace": ""},
                        {"name": "a"}])


@pytest.mark.parametrize("force_json", [False, True], ids=["native", "json"])
@pytest.mark.parametrize("checks", [[BY_NAME], [BY_NSNAME], [BY_RESOURCE_ID],
                                    [BY_NSNAME, BY_NAME]],
                         ids=["name", "namespacedName", "resourceId",
                              "joined-and-name"])
def test_an_object_with_no_namespace_joins_to_its_name(engine, checks,
                                                       force_json):
    """Objects with no namespace among namespaced ones: the join of an
    object with no namespace is its name alone, as the per-key loop and
    the plain resolver make it."""
    got = _run(engine, CLUSTER_SCOPED, checks, force_json, "columns")
    assert got[0] == 200 and got[5][1] == got[5][0] > 0
    assert got[:5] == _run(engine, CLUSTER_SCOPED, checks, force_json,
                           "loop")[:5]
    assert got[:5] == _run(engine, CLUSTER_SCOPED, checks, force_json,
                           "plain")[:5]
    if checks != [BY_NAME]:
        assert ("pod", "b", "view", "user", "alice", "") in dict(got[3])


ERRORS = [
    pytest.param(_body([{"name": "a", "namespace": "ns1"}, {"name": "g"}]),
                 [BY_NAMESPACE], "relationship field resource_id",
                 id="no-namespace"),
    pytest.param(_body([{"name": "a"}, {"name": "", "namespace": "ns1"}]),
                 [BY_TWO_ROOTS], "relationship field subject_id",
                 id="first-empty-key-wins"),
    pytest.param(_body([{"namespace": "ns1"}, {"name": "a"}]),
                 [BY_TWO_ROOTS], "relationship field resource_id",
                 id="first-empty-field-wins"),
]


@pytest.mark.parametrize("force_json", [False, True], ids=["native", "json"])
@pytest.mark.parametrize("body,checks,message", ERRORS)
def test_an_empty_field_fails_the_list_as_the_loop_does(
        engine, body, checks, message, force_json):
    """A field (not a subject relation) that resolves empty for some key
    refuses the list with the per-key loop's message: the first key that
    fails, and its first field in order; nothing is dispatched and no
    resolution counted."""
    got = _run(engine, body, checks, force_json, "columns")
    loop = _run(engine, body, checks, force_json, "loop")
    assert got[0] == loop[0] == 401
    assert got[1] == loop[1] == f"{message} resolved empty"
    assert got[4] == loop[4] == []
    assert got[5] == [0, 0]


@pytest.mark.parametrize("metas,checks", [
    ([{"name": 123, "namespace": "ns1"}, {"name": "a", "namespace": "ns1"}],
     [BY_NAME]),
    ([{"name": 5.0, "namespace": "ns1"}, {"name": True, "namespace": "ns2"}],
     [BY_NSNAME]),
    ([{"name": 5.0, "namespace": "ns1"}], [BY_NAME]),
    ([{"name": "a", "namespace": 7}], [BY_NAMESPACE]),
], ids=["name-int", "nsname-float-bool", "name-float", "namespace-int"])
def test_a_value_that_is_not_a_string_resolves_as_templates_make_it(
        engine, metas, checks):
    """The json path's values that are not strings (the scanner refuses
    such a body) go through the per-key loop, whose templates make them
    strings: a number by ``_tostr`` (5.0 -> "5") for a bare root, by the
    join for ``namespacedName`` ("ns1/5.0"); the list counts no column
    resolution."""
    body = _body(metas)
    got = _run(engine, body, checks, False, "columns")
    assert got[:5] == _run(engine, body, checks, False, "loop")[:5]
    assert got[0] == 200 and got[5][1] == 0 and got[5][0] > 0
    fields = [key for key, _ in got[3]]
    if checks == [BY_NAME]:
        assert fields[0][1] == str(int(metas[0]["name"]))
    if checks == [BY_NSNAME]:
        assert [f[1] for f in fields] == ["ns1/5.0", "ns2/True"]


@pytest.mark.parametrize("force_json", [False, True], ids=["native", "json"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_column_counter_counts_the_column_resolutions(engine, form,
                                                          force_json):
    """``proxy_postfilter_column_resolved_total`` moves by every
    resolution of a columnar rule (a distinct key each) and by none of a
    rule that falls back; ``proxy_postfilter_resolved_total`` counts
    both."""
    checks, columnar = FORMS[form]
    _, post_filters = _post_filters(checks)
    got = _run(engine, PODS_BODY, checks, force_json, "columns")
    resolved, column = got[5]
    per_rule = [len(ends) for _, ends, _ in got[2]]
    assert resolved == sum(per_rule) > 0
    assert column == sum(n for pf, n in zip(post_filters, per_rule)
                         if pf.rel.columns is not None)
    if columnar is not None:
        assert column == (resolved if columnar else 0)
    else:
        assert 0 < column < resolved


@pytest.mark.parametrize("template,columns", [
    ("namespace:{{namespace}}#view@user:{{user.name}}", {1: "namespace"}),
    ("namespace:{{ namespace }}#view@user:{{user.name}}", {1: "namespace"}),
    ("pod:{{name}}#view@user:{{namespace}}", {1: "name", 4: "namespace"}),
    ("pod:{{namespacedName}}#view@user:{{user.name}}#{{resourceId}}",
     {1: "namespacedName", 5: "resourceId"}),
    ("namespace:namespace#view@user:{{user.name}}", {}),
    ("pod:{{split_name(resourceId)}}#view@user:{{user.name}}", None),
    ("namespace:{{object.metadata.name}}#view@user:{{user.name}}", None),
    ("namespace:{{this.namespace}}#view@user:{{user.name}}", None),
    ("namespace:{{namespace + \"x\"}}#view@user:{{user.name}}", None),
], ids=["bare", "spaced", "two-fields", "joined-and-subject-relation",
        "literal", "function-of-a-root", "object", "this", "expression"])
def test_columns_are_derived_from_the_expressions(template, columns):
    """A field that reads an item root is a column only where it is that
    root, bare; a literal that spells a root's name reads none."""
    assert _compile_rel_string(template).columns == columns

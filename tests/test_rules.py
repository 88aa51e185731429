"""Rules engine tests: expression language, templates, tupleSets, matcher,
config validation — modeled on the reference's pkg/rules and
pkg/config/proxyrule test suites."""

import json
import os

import pytest

from spicedb_kubeapi_proxy_tpu.rules import (
    ExprError,
    MapMatcher,
    RequestInfo,
    RequestMeta,
    ResolveInput,
    RuleValidationError,
    UserInfo,
    compile_expr,
    compile_template,
    parse_rule_configs,
)
from spicedb_kubeapi_proxy_tpu.rules.compile import compile_rule
from spicedb_kubeapi_proxy_tpu.rules.proxyrule import RuleConfig


def make_input(verb="get", resource="pods", name="nginx", namespace="default",
               user="alice", groups=(), body=None, api_version="v1",
               api_group=""):
    return ResolveInput.create(
        RequestInfo(verb=verb, api_group=api_group, api_version=api_version,
                    resource=resource, name=name, namespace=namespace,
                    path=f"/api/v1/namespaces/{namespace}/{resource}/{name}"),
        UserInfo(name=user, groups=list(groups)),
        body=body,
        headers={"X-Request-Id": "42"},
    )


# ---------------------------------------------------------------------------
# Expression language
# ---------------------------------------------------------------------------


def ev(src, data=None):
    return compile_expr(src).evaluate(data or {})


def test_expr_basics():
    assert ev("1 + 2") == 3
    assert ev("'a' + 'b'") == "ab"
    assert ev('"x" == "x"')
    assert ev("user.name", {"user": {"name": "alice"}}) == "alice"
    assert ev("'system:masters' in user.groups",
              {"user": {"groups": ["system:masters"]}})
    assert ev("a.b.c", {}) is None  # missing chains to null
    assert ev("a.b.c | 'dflt'", {}) == "dflt"
    assert ev("x ? 'y' : 'n'", {"x": True}) == "y"
    assert ev("if x == 2 { 'two' } else { 'other' }", {"x": 2}) == "two"
    assert ev("!(1 == 2)")
    assert ev("[1,2,3].length()") == 3
    assert ev("'a/b'.split('/')") == ["a", "b"]
    assert ev("'AbC'.lowercase()") == "abc"
    assert ev("'ns1/pod1'.startsWith('ns1')")
    assert ev("7.string()") == "7"
    assert ev("has(a.b)", {"a": {"b": 1}})
    assert not ev("has(a.b)", {})


def test_expr_errors():
    with pytest.raises(ExprError):
        ev("1 + 'a'")
    with pytest.raises(ExprError):
        ev("nosuchfn(1)")
    with pytest.raises(ExprError):
        compile_expr("1 +")
    with pytest.raises(ExprError):
        ev("x.map_each(this)", {"x": "notalist"})
    # non-boolean condition
    with pytest.raises(ExprError):
        compile_expr("'str'").evaluate_bool({})


def test_expr_split_functions():
    # the custom Bloblang env functions (reference env.go)
    assert ev("split_name('ns1/pod1')") == "pod1"
    assert ev("split_namespace('ns1/pod1')") == "ns1"
    assert ev("split_name('cluster-scoped')") == "cluster-scoped"
    assert ev("split_namespace('cluster-scoped')") == ""


def test_expr_lambda_capture_let():
    data = {"namespacedName": "default/dep1",
            "object": {"spec": {"template": {"spec": {"containers": [
                {"name": "server"}, {"name": "sidecar"}]}}}}}
    # the reference's flagship tupleSet expression shape (tupleset_test.go:26)
    out = ev('this.namespacedName.(nsName -> this.object.spec.template.spec'
             '.containers.map_each("deployment:" + nsName '
             '+ "#has-container@container:" + this.name))', data)
    assert out == [
        "deployment:default/dep1#has-container@container:server",
        "deployment:default/dep1#has-container@container:sidecar",
    ]
    # filter variant (tupleset_test.go:64)
    out = ev('this.namespacedName.(nsName -> this.object.spec.template.spec'
             '.containers.filter(this.name != "sidecar")'
             '.map_each("deployment:" + nsName + "#c@container:" + this.name))',
             data)
    assert out == ["deployment:default/dep1#c@container:server"]
    # missing list fallback (tupleset_test.go:116)
    out = ev('(this.object.spec.nope | []).map_each(this.name)', data)
    assert out == []
    # let + $var
    out = ev('let ns = this.namespacedName\n$ns + "!"', data)
    assert out == "default/dep1!"
    # bare var reference
    out = ev('let ns = this.namespacedName\nns + "!"', data)
    assert out == "default/dep1!"


def test_expr_if_else_method_style():
    # service ports shape (tupleset_test.go:81)
    data = {"ports": [{"name": "http", "port": 80}, {"port": 9090}]}
    out = ev('ports.map_each(if this.name != null { this.name } '
             'else { this.port.string() })', data)
    assert out == ["http", "9090"]


def test_template_literal_duality():
    # full-wrap => expression; otherwise literal (reference rules.go:1005-1026)
    assert compile_template("{{user.name}}").evaluate(
        {"user": {"name": "bob"}}) == "bob"
    assert compile_template("literal").evaluate({}) == "literal"
    assert compile_template("$").evaluate({}) == "$"
    assert compile_template("{{}}").evaluate({}) == ""
    assert compile_template("{{split_namespace(resourceId)}}").evaluate(
        {"resourceId": "ns9/p"}) == "ns9"


# ---------------------------------------------------------------------------
# ResolveInput
# ---------------------------------------------------------------------------


def test_resolve_input_namespace_normalization():
    # namespaces resource: namespace field cleared (reference rules.go:331-333)
    i = ResolveInput.create(
        RequestInfo(verb="get", resource="namespaces", name="ns1",
                    namespace="ns1"),
        UserInfo(name="u"))
    assert i.name == "ns1" and i.namespace == "" and i.namespaced_name == "ns1"

    # object metadata preferred over request (create with body)
    body = json.dumps({"metadata": {"name": "frombody", "namespace": "nsb"},
                       "kind": "Pod"}).encode()
    i2 = ResolveInput.create(
        RequestInfo(verb="create", resource="pods", namespace="nsr"),
        UserInfo(name="u"), body=body)
    assert i2.name == "frombody"
    assert i2.namespace == "nsb"
    assert i2.namespaced_name == "nsb/frombody"
    assert i2.object["metadata"]["name"] == "frombody"

    d = i2.template_data()
    assert d["metadata"]["name"] == "frombody"
    assert d["resourceId"] == "nsb/frombody"
    c = i2.condition_data()
    assert c["resourceNamespace"] == "nsb"


# ---------------------------------------------------------------------------
# Rule config parsing + compilation (the repo's own deploy/rules.yaml, the
# rule set modelled on the reference's)
# ---------------------------------------------------------------------------

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "deploy", "rules.yaml")) as _f:
    REFERENCE_RULES = _f.read()


def test_parse_reference_deploy_rules():
    cfgs = parse_rule_configs(REFERENCE_RULES)
    assert len(cfgs) == 8
    byname = {c.name: c for c in cfgs}
    cn = byname["namespace-create"]
    assert cn.spec.locking == "Pessimistic"
    assert cn.spec.update.creates and cn.spec.update.precondition_does_not_exist
    lw = byname["pod-list-watch"]
    assert lw.spec.pre_filters[0].from_object_id_namespace_expr
    # all of them compile
    for c in cfgs:
        compile_rule(c)


def test_rule_end_to_end_resolution():
    cfgs = {c.name: compile_rule(c) for c in parse_rule_configs(REFERENCE_RULES)}
    # pod-get check template resolution
    i = make_input(verb="get", resource="pods", name="nginx",
                   namespace="default", user="alice")
    rels = cfgs["pod-get"].checks[0].generate(i)
    assert str(rels[0]) == "pod:default/nginx#view@user:alice"
    # namespace-create update resolution
    i2 = ResolveInput.create(
        RequestInfo(verb="create", resource="namespaces", name="",
                    namespace=""),
        UserInfo(name="admin"),
        body=json.dumps({"metadata": {"name": "newns"}}).encode())
    upd = cfgs["namespace-create"].update
    assert [str(r) for r in upd.creates[0].generate(i2)] == \
        ["namespace:newns#creator@user:admin"]
    # the precondition is a filter: any creator at all ($ = match any)
    assert [str(r) for r in upd.preconditions_do_not_exist[0].generate(i2)] == \
        ["namespace:newns#creator@user:$"]
    # prefilter: lookup rel has $ resource id
    pf = cfgs["pod-list-watch"].pre_filters[0]
    i3 = make_input(verb="list", resource="pods", name="", namespace="")
    rel = pf.rel.generate(i3)[0]
    assert rel.resource_id == "$"
    assert rel.subject_id == "alice"
    # name/namespace mapping expressions
    assert pf.name_expr.evaluate({"resourceId": "ns1/p1"}) == "p1"
    assert pf.namespace_expr.evaluate({"resourceId": "ns1/p1"}) == "ns1"


def test_tupleset_rule():
    cfg = parse_rule_configs("""
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: deployment-containers
match:
- apiVersion: apps/v1
  resource: deployments
  verbs: ["create"]
update:
  creates:
  - tupleSet: >-
      this.namespacedName.(nsName -> this.object.spec.template.spec.containers.map_each("deployment:" + nsName + "#has-container@container:" + this.name))
""")[0]
    r = compile_rule(cfg)
    body = json.dumps({
        "metadata": {"name": "dep1", "namespace": "default"},
        "spec": {"template": {"spec": {"containers": [
            {"name": "server"}, {"name": "cfg"}]}}},
    }).encode()
    i = ResolveInput.create(
        RequestInfo(verb="create", resource="deployments", namespace="default",
                    api_group="apps", api_version="v1"),
        UserInfo(name="u"), body=body)
    rels = r.update.creates[0].generate(i)
    assert [str(x) for x in rels] == [
        "deployment:default/dep1#has-container@container:server",
        "deployment:default/dep1#has-container@container:cfg",
    ]


def test_if_conditions():
    cfg = parse_rule_configs("""
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: cond
match:
- apiVersion: v1
  resource: pods
  verbs: ["get"]
if:
- "request.verb == 'get'"
- "'system:masters' in user.groups"
- "resourceNamespace == 'default'"
check:
- tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"
""")[0]
    r = compile_rule(cfg)
    assert r.conditions_pass(make_input(groups=["system:masters"]))
    assert not r.conditions_pass(make_input(groups=["other"]))
    assert not r.conditions_pass(make_input(groups=["system:masters"],
                                            namespace="kube-system"))


def test_matcher():
    m = MapMatcher.from_yaml(REFERENCE_RULES)
    got = m.match(RequestMeta("get", "", "v1", "pods"))
    assert [r.name for r in got] == ["pod-get"]
    assert m.match(RequestMeta("deletecollection", "", "v1", "pods")) == []
    assert m.match(RequestMeta("get", "apps", "v1", "deployments")) == []
    got = m.match(RequestMeta("watch", "", "v1", "namespaces"))
    assert [r.name for r in got] == ["namespace-list-watch"]


def test_structured_relationship_template():
    cfg = parse_rule_configs("""
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: structured
match:
- apiVersion: v1
  resource: pods
  verbs: ["get"]
check:
- resource:
    type: pod
    id: "{{namespacedName}}"
    relation: view
  subject:
    type: group
    id: eng
    relation: member
""")[0]
    r = compile_rule(cfg)
    rel = r.checks[0].generate(make_input())[0]
    assert str(rel) == "pod:default/nginx#view@group:eng#member"
    assert rel.subject_relation == "member"


@pytest.mark.parametrize("yaml_text,msg", [
    ("kind: ProxyRule\napiVersion: authzed.com/v1alpha1\nmetadata: {name: x}\n",
     "match is required"),
    ("""
kind: ProxyRule
apiVersion: authzed.com/v1alpha1
metadata: {name: x}
match:
- apiVersion: v1
  resource: pods
  verbs: ["frobnicate"]
""", "invalid verb"),
    ("""
kind: ProxyRule
apiVersion: authzed.com/v1alpha1
metadata: {name: x}
match:
- apiVersion: v1
  resource: pods
  verbs: ["list"]
postcheck:
- tpl: "a:b#c@d:e"
""", "postcheck is incompatible"),
    ("""
kind: ProxyRule
apiVersion: authzed.com/v1alpha1
metadata: {name: x}
match:
- apiVersion: v1
  resource: pods
  verbs: ["get"]
check:
- tpl: "a:b#c@d:e"
  tupleSet: "['x']"
""", "mutually exclusive"),
    ("""
kind: NotARule
metadata: {name: x}
match:
- apiVersion: v1
  resource: pods
  verbs: ["get"]
""", "unsupported kind"),
])
def test_rule_validation_errors(yaml_text, msg):
    with pytest.raises(RuleValidationError, match=msg):
        parse_rule_configs(yaml_text)


def test_review_regressions_expr():
    # .or() absorbs missing/null receivers
    assert ev('object.metadata.labels["team"].or("unowned")',
              {"object": {"metadata": {"labels": {}}}}) == "unowned"
    assert ev('x.or("d")', {"x": "real"}) == "real"
    # runtime type errors are recoverable ExprErrors, caught by `|`
    assert ev("int(request.name) | 0", {"request": {"name": "abc"}}) == 0
    with pytest.raises(ExprError):
        ev("request.name.length()", {"request": {"name": 5}})


def test_namespace_subresources_requestinfo():
    from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
    i = parse_request_info("PUT", "/api/v1/namespaces/default/finalize")
    assert (i.resource, i.name, i.subresource, i.namespace) == \
        ("namespaces", "default", "finalize", "")
    i2 = parse_request_info("GET", "/api/v1/namespaces/default/pods")
    assert (i2.resource, i2.namespace, i2.verb) == ("pods", "default", "list")

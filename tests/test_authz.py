"""Authorization middleware integration tests.

Ports the shape of the reference e2e scenario suite
(reference e2e/proxy_test.go): every verb through the full middleware
against a fake kube upstream, using the repo's deploy/rules.yaml rule set
(modelled on the reference's) and the default bootstrap schema — per-user isolation on get/list/watch,
dual-write visibility, table filtering, postchecks, CEL `if` rules.
"""

import asyncio
import json
import os

import pytest

from spicedb_kubeapi_proxy_tpu.authz import AuthzDeps, authorize
from spicedb_kubeapi_proxy_tpu.dtx import ActivityHandler, WorkflowEngine, register_workflows
from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, RelationshipFilter
from spicedb_kubeapi_proxy_tpu.proxy.authn import HeaderAuthenticator
from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyRequest
from spicedb_kubeapi_proxy_tpu.rules import MapMatcher
from spicedb_kubeapi_proxy_tpu.rules.input import UserInfo

from fake_kube import FakeKube

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "deploy", "rules.yaml")) as _f:
    RULES = _f.read()


class Env:
    def __init__(self, rules_yaml: str = RULES, bootstrap=None):
        # default: DEFAULT_BOOTSTRAP schema; custom bootstrap YAML gets the
        # dual-write infra definitions (lock/workflow/activity) appended by
        # parse_bootstrap
        self.engine = Engine(bootstrap=bootstrap)
        self.kube = FakeKube()
        self.workflow = WorkflowEngine()
        register_workflows(self.workflow)
        ActivityHandler(self.engine, self.kube).register(self.workflow)
        self.deps = AuthzDeps(
            matcher=MapMatcher.from_yaml(rules_yaml),
            engine=self.engine,
            upstream=self.kube,
            workflow=self.workflow,
            watch_poll_interval=0.01,
        )

    async def request(self, method: str, path: str, user: str = "alice",
                      body=None, query=None, groups=(), headers=None):
        query = query or {}
        info = parse_request_info(method, path, query)
        req = ProxyRequest(
            method=method, path=path, query=query,
            headers={"Content-Type": "application/json", **(headers or {})},
            body=json.dumps(body).encode() if body is not None else b"",
            user=UserInfo(name=user, groups=list(groups)),
            request_info=info,
        )
        return await authorize(req, self.deps)

    async def create_ns(self, name: str, user: str = "alice"):
        return await self.request(
            "POST", "/api/v1/namespaces", user=user,
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": name}})

    async def create_pod(self, ns: str, name: str, user: str = "alice"):
        return await self.request(
            "POST", f"/api/v1/namespaces/{ns}/pods", user=user,
            body={"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": name, "namespace": ns}})


def run(coro):
    return asyncio.run(coro)


def test_discovery_always_allowed():
    async def go():
        env = Env()
        resp = await env.request("GET", "/api")
        assert resp.status == 200
    run(go())


def test_unmatched_request_forbidden():
    async def go():
        env = Env()
        resp = await env.request("GET", "/api/v1/configmaps")
        assert resp.status == 403
        assert b"Forbidden" in resp.body
    run(go())


def test_create_then_get_namespace_dual_write():
    async def go():
        env = Env()
        resp = await env.create_ns("team-a")
        assert resp.status == 201
        # relationships written
        assert env.engine.store.exists(RelationshipFilter(
            "namespace", "team-a", "creator", "user", "alice"))
        assert not env.engine.store.exists(RelationshipFilter(
            resource_type="lock"))
        # creator can get it
        r2 = await env.request("GET", "/api/v1/namespaces/team-a")
        assert r2.status == 200
        # another user cannot
        r3 = await env.request("GET", "/api/v1/namespaces/team-a", user="bob")
        assert r3.status == 403
    run(go())


def test_create_conflict_second_user():
    async def go():
        env = Env()
        assert (await env.create_ns("shared")).status == 201
        # second create: precondition (the name has a creator) -> 409
        resp = await env.create_ns("shared", user="mallory")
        assert resp.status == 409
        assert not env.engine.store.exists(RelationshipFilter(
            "namespace", "shared", "creator", "user", "mallory"))
    run(go())


def test_list_namespaces_prefiltered_per_user():
    async def go():
        env = Env()
        await env.create_ns("alpha", user="alice")
        await env.create_ns("beta", user="bob")
        await env.create_ns("gamma", user="alice")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice")
        assert resp.status == 200
        names = [o["metadata"]["name"] for o in json.loads(resp.body)["items"]]
        assert sorted(names) == ["alpha", "gamma"]
        resp = await env.request("GET", "/api/v1/namespaces", user="bob")
        names = [o["metadata"]["name"] for o in json.loads(resp.body)["items"]]
        assert names == ["beta"]
        resp = await env.request("GET", "/api/v1/namespaces", user="carol")
        assert json.loads(resp.body)["items"] == []
    run(go())


def test_list_pods_prefiltered_split_names():
    async def go():
        env = Env()
        await env.create_ns("ns1", user="alice")
        await env.create_pod("ns1", "p1", user="alice")
        await env.create_pod("ns1", "p2", user="alice")
        await env.create_ns("ns2", user="bob")
        await env.create_pod("ns2", "q1", user="bob")
        resp = await env.request("GET", "/api/v1/pods", user="alice")
        names = [o["metadata"]["name"] for o in json.loads(resp.body)["items"]]
        assert sorted(names) == ["p1", "p2"]
        # namespace-scoped list also filtered
        resp = await env.request("GET", "/api/v1/namespaces/ns2/pods",
                                 user="alice")
        assert json.loads(resp.body)["items"] == []
    run(go())


def test_get_single_pod_not_allowed():
    async def go():
        env = Env()
        await env.create_ns("ns1", user="alice")
        await env.create_pod("ns1", "p1", user="alice")
        assert (await env.request(
            "GET", "/api/v1/namespaces/ns1/pods/p1", user="alice")).status == 200
        assert (await env.request(
            "GET", "/api/v1/namespaces/ns1/pods/p1", user="bob")).status == 403
    run(go())


def test_delete_namespace_removes_relationships():
    async def go():
        env = Env()
        await env.create_ns("doomed", user="alice")
        resp = await env.request("DELETE", "/api/v1/namespaces/doomed",
                                 user="alice")
        assert resp.status == 200
        assert not env.engine.store.exists(RelationshipFilter(
            "namespace", "doomed", "creator"))
        # object gone upstream
        assert ("namespaces", "", "doomed") not in env.kube.objects
    run(go())


def test_table_response_filtering():
    async def go():
        env = Env()
        await env.create_ns("mine", user="alice")
        await env.create_ns("theirs", user="bob")
        # hand-craft a Table response upstream
        table = {
            "kind": "Table", "apiVersion": "meta.k8s.io/v1",
            "columnDefinitions": [{"name": "Name"}],
            "rows": [
                {"cells": ["mine"],
                 "object": {"kind": "PartialObjectMetadata",
                            "metadata": {"name": "mine"}}},
                {"cells": ["theirs"],
                 "object": {"kind": "PartialObjectMetadata",
                            "metadata": {"name": "theirs"}}},
            ],
        }
        import spicedb_kubeapi_proxy_tpu.proxy.types as T

        async def table_upstream(req):
            return T.json_response(200, table)

        env.deps.upstream = table_upstream
        resp = await env.request("GET", "/api/v1/namespaces", user="alice")
        doc = json.loads(resp.body)
        assert [r["cells"][0] for r in doc["rows"]] == ["mine"]
    run(go())


POSTFILTER_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: list-pods-postfiltered
match:
- apiVersion: v1
  resource: pods
  verbs: ["list"]
postfilter:
- checkPermissionTemplate:
    tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"
"""


def test_postfilter_bulk_checks():
    async def go():
        env = Env(rules_yaml=RULES + "\n---\n" + POSTFILTER_RULES)
        # seed engine + kube directly (no create rule interplay needed)
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        env.engine.write_relationships([
            WriteOp("touch", parse_relationship("pod:ns1/a#viewer@user:alice")),
        ])
        for name in ("a", "b"):
            env.kube.objects[("pods", "ns1", name)] = {
                "kind": "Pod",
                "metadata": {"name": name, "namespace": "ns1"}}
        resp = await env.request("GET", "/api/v1/namespaces/ns1/pods",
                                 user="alice")
        names = [o["metadata"]["name"] for o in json.loads(resp.body)["items"]]
        # prefilter (view) allows 'a'; postfilter also only passes 'a'
        assert names == ["a"]

        # postfilter paths must force a JSON upstream response even when
        # the client negotiates protobuf (the postfilter resolves rule
        # expressions over item JSON; proxy/upstream.py otherwise forwards
        # protobuf ranges now that the prefilter path can filter them)
        seen = {}
        orig = env.deps.upstream

        async def recording_upstream(req):
            seen["accept"] = next((v for k, v in req.headers.items()
                                   if k.lower() == "accept"), None)
            return await orig(req)

        env.deps.upstream = recording_upstream
        resp = await env.request(
            "GET", "/api/v1/namespaces/ns1/pods", user="alice",
            headers={"Accept":
                     "application/vnd.kubernetes.protobuf,application/json"})
        assert seen["accept"] == "application/json"
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["a"]
    run(go())


POSTCHECK_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: get-pod-postcheck
match:
- apiVersion: v1
  resource: pods
  verbs: ["get"]
postcheck:
- tpl: "pod:{{namespacedName}}#edit@user:{{user.name}}"
"""


def test_postchecks_run_after_upstream():
    async def go():
        env = Env(rules_yaml=POSTCHECK_RULES)
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        env.engine.write_relationships([
            WriteOp("touch", parse_relationship("pod:ns1/a#creator@user:alice")),
        ])
        env.kube.objects[("pods", "ns1", "a")] = {
            "kind": "Pod", "metadata": {"name": "a", "namespace": "ns1"}}
        ok = await env.request("GET", "/api/v1/namespaces/ns1/pods/a",
                               user="alice")
        assert ok.status == 200
        denied = await env.request("GET", "/api/v1/namespaces/ns1/pods/a",
                                   user="bob")
        assert denied.status == 403
    run(go())


CEL_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: masters-only
match:
- apiVersion: v1
  resource: secrets
  verbs: ["get"]
if:
- "'system:masters' in user.groups"
"""


def test_cel_if_conditions_gate_rules():
    async def go():
        env = Env(rules_yaml=CEL_RULES)
        env.kube.objects[("secrets", "ns1", "s")] = {
            "kind": "Secret", "metadata": {"name": "s", "namespace": "ns1"}}
        ok = await env.request("GET", "/api/v1/namespaces/ns1/secrets/s",
                               groups=["system:masters"])
        assert ok.status == 200
        denied = await env.request("GET", "/api/v1/namespaces/ns1/secrets/s",
                                   groups=["dev"])
        assert denied.status == 403
    run(go())


def test_watch_filtered_per_user():
    async def go():
        env = Env()
        await env.create_ns("w1", user="alice")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        assert resp.status == 200 and resp.stream is not None
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))
                if len(frames) >= 2:
                    return

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        # alice's initial namespace should stream through (ADDED)
        # bob creates one -> must NOT reach alice; alice creates -> must
        await env.create_ns("w2", user="bob")
        await env.create_ns("w3", user="alice")
        await asyncio.wait_for(task, timeout=5)
        names = [f["object"]["metadata"]["name"] for f in frames]
        assert names == ["w1", "w3"]
        env.kube.stop_watches()
    run(go())


def test_watch_allows_object_after_grant():
    async def go():
        env = Env()
        await env.create_ns("gr", user="bob")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))
                return

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        assert not frames  # buffered: alice can't see bob's namespace yet
        # grant alice viewer -> the buffered ADDED frame must flush
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:gr#viewer@user:alice"))])
        await asyncio.wait_for(task, timeout=5)
        assert frames[0]["object"]["metadata"]["name"] == "gr"
        env.kube.stop_watches()
    run(go())


def test_watch_namespaced_resource_keys_frames_by_prefilter():
    """Pods watch: the prefilter carries a namespace expression
    (split_namespace over 'ns/name' object ids), so frames key on
    (metadata.namespace, metadata.name) — buffer for the wrong user,
    flush on grant, keyed exactly as the grant side maps object ids
    (authz/watch.py _frame_object_key)."""
    async def go():
        env = Env()
        await env.create_ns("wns", user="bob")
        await env.create_pod("wns", "api", user="bob")
        resp = await env.request("GET", "/api/v1/pods", user="alice",
                                 query={"watch": ["true"]})
        assert resp.status == 200 and resp.stream is not None
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))
                return

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        assert not frames  # buffered: alice can't view bob's namespace
        # grant alice view on the pod directly (the default bootstrap has
        # no namespace arrow) -> the buffered ADDED frame for (wns, api)
        # must flush
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "pod:wns/api#viewer@user:alice"))])
        await asyncio.wait_for(task, timeout=5)
        meta = frames[0]["object"]["metadata"]
        assert (meta["namespace"], meta["name"]) == ("wns", "api")
        env.kube.stop_watches()
    run(go())


def test_watch_drops_frames_after_revocation_mid_stream():
    """Reference proxy_test.go:905-943: once a subject's permission on an
    object is revoked, subsequent watch events for that object are dropped
    from the stream (and other objects keep flowing)."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

        env = Env()
        await env.create_ns("mine", user="alice")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))

        task = asyncio.ensure_future(consume())
        # alice owns "mine": the ADDED frame flows through
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1), timeout=5)
        assert frames[0]["object"]["metadata"]["name"] == "mine"
        # revoke alice's ownership, then emit a MODIFIED event upstream
        env.engine.write_relationships([WriteOp("delete", parse_relationship(
            "namespace:mine#creator@user:alice"))])
        await asyncio.sleep(0.05)  # let the revocation reach the tracker
        env.kube.emit_watch_event("namespaces", "MODIFIED", "mine")
        # and a fresh grant on another namespace must still flow
        await env.create_ns("other", user="bob")
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:other#viewer@user:alice"))])
        await asyncio.wait_for(
            _wait_for(lambda: any(
                f["object"]["metadata"]["name"] == "other" for f in frames)),
            timeout=5)
        names = [f["object"]["metadata"]["name"] for f in frames]
        # the post-revocation MODIFIED frame for "mine" was dropped
        assert names.count("mine") == 1, names
        task.cancel()
        env.kube.stop_watches()
    run(go())


async def _wait_for(pred, interval=0.02):
    while not pred():
        await asyncio.sleep(interval)


def test_proto_watch_filtered_grant_and_revoke_mid_stream():
    """VERDICT r4 directive 5: a protobuf watch passes through the filter
    natively — frames are kube-proto WatchEvents (length-prefixed, byte-
    identical to what the upstream sent), buffered frames flush on grant,
    and post-revocation frames are dropped. No JSON downgrade."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import (
            parse_relationship,
        )
        from spicedb_kubeapi_proxy_tpu.proxy import kubeproto

        env = Env()
        await env.create_ns("pw-mine", user="alice")
        await env.create_ns("pw-hidden", user="bob")
        resp = await env.request(
            "GET", "/api/v1/namespaces", user="alice",
            query={"watch": ["true"]},
            headers={"Accept": kubeproto.CONTENT_TYPE
                     + ",application/json"})
        assert resp.status == 200 and resp.stream is not None
        assert "protobuf" in resp.headers.get("Content-Type", "")
        frames: list = []

        async def consume():
            async for f in resp.stream:
                frames.append(f)

        task = asyncio.ensure_future(consume())
        # alice's own namespace streams through as a proto frame,
        # byte-identical to the upstream encoding (length prefix intact)
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1),
                               timeout=5)
        assert int.from_bytes(frames[0][:4], "big") == len(frames[0]) - 4
        assert kubeproto.watch_frame_key(frames[0]) == ("", "pw-mine")
        expected = kubeproto.encode_watch_frame(
            "ADDED", kubeproto.encode_unknown(
                "v1", "Namespace",
                kubeproto.encode_object_meta_only("pw-mine")))
        assert frames[0] == expected  # byte-identical passthrough
        # bob's namespace stayed buffered; granting alice flushes it
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:pw-hidden#viewer@user:alice"))])
        await asyncio.wait_for(
            _wait_for(lambda: any(
                kubeproto.watch_frame_key(f) == ("", "pw-hidden")
                for f in frames)), timeout=5)
        # revoke and emit: the post-revocation frame must be dropped
        env.engine.write_relationships([WriteOp("delete", parse_relationship(
            "namespace:pw-hidden#viewer@user:alice"))])
        await asyncio.sleep(0.05)
        env.kube.emit_watch_event("namespaces", "MODIFIED", "pw-hidden")
        env.kube.emit_watch_event("namespaces", "MODIFIED", "pw-mine")
        await asyncio.wait_for(
            _wait_for(lambda: sum(
                1 for f in frames
                if kubeproto.watch_frame_key(f) == ("", "pw-mine")) >= 2),
            timeout=5)
        keys = [kubeproto.watch_frame_key(f) for f in frames]
        assert keys.count(("", "pw-hidden")) == 1, keys
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_proto_watch_bookmarks_pass_through():
    """Proto BOOKMARK frames (progress markers, no object) pass through
    to every watcher byte-identically."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.proxy import kubeproto

        env = Env()
        await env.create_ns("pb", user="alice")
        resp = await env.request(
            "GET", "/api/v1/namespaces", user="alice",
            query={"watch": ["true"],
                   "allowWatchBookmarks": ["true"]},
            headers={"Accept": kubeproto.CONTENT_TYPE})
        frames: list = []

        async def consume():
            async for f in resp.stream:
                frames.append(f)

        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 2),
                               timeout=5)
        types = [kubeproto.decode_watch_event(f[4:])[0] for f in frames]
        assert "BOOKMARK" in types
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_watch_skips_recompute_for_unrelated_writes(monkeypatch):
    """Writes to types that cannot affect the watched permission must not
    cost a device query per watcher: the schema-derived relevant-type set
    gates the recompute. (The expiry tick is pinned long so only the gate
    is under test.)"""
    from spicedb_kubeapi_proxy_tpu.authz import watchhub as watchhub_mod

    monkeypatch.setattr(watchhub_mod, "EXPIRY_RECOMPUTE_INTERVAL", 600.0)

    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

        env = Env()
        await env.create_ns("sk", user="alice")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))

        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1),
                               timeout=10)
        await asyncio.sleep(0.1)  # drain any startup polls
        lookups0 = metrics.counter("engine_lookups_total").value
        # lock/workflow writes (the dual-write machinery's own types)
        # cannot affect namespace#view: no recompute may fire
        for i in range(3):
            env.engine.write_relationships([WriteOp(
                "touch", parse_relationship(
                    f"lock:unrelated-{i}#workflow@workflow:w{i}"))])
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.2)  # several poll ticks
        assert metrics.counter("engine_lookups_total").value == lookups0, \
            "unrelated writes triggered allowed-set recomputes"
        # a RELEVANT write still recomputes and flushes
        await env.create_ns("sk2", user="bob")
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:sk2#viewer@user:alice"))])
        await asyncio.wait_for(_wait_for(lambda: any(
            f["object"]["metadata"]["name"] == "sk2" for f in frames)),
            timeout=10)
        assert metrics.counter("engine_lookups_total").value > lookups0
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_watch_enforces_expiring_grant_without_events(monkeypatch):
    """An expiring grant revokes at QUERY time with no watch event: the
    periodic recompute tick must drop post-expiry frames even when no
    other write ever lands (review finding: the type gate must not starve
    expiry enforcement — which previously depended on unrelated write
    traffic arriving at all)."""
    import time as _time

    from spicedb_kubeapi_proxy_tpu.authz import watchhub as watchhub_mod
    from spicedb_kubeapi_proxy_tpu.engine import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship

    monkeypatch.setattr(watchhub_mod, "EXPIRY_RECOMPUTE_INTERVAL", 0.05)

    async def go():
        env = Env(bootstrap="""
schema: |-
  use expiration

  definition user {}
  definition cluster {}
  definition namespace {
    relation cluster: cluster
    relation creator: user
    relation viewer: user with expiration
    permission admin = creator
    permission view = viewer + creator
  }
relationships: ""
""")
        await env.create_ns("exp", user="bob")
        # pre-warm the expiry-shaped kernels: the first expiring tuple
        # changes the compiled graph shape, and that one-time XLA compile
        # (~1s) must not eat the 0.6s expiry budget this test times
        env.engine.write_relationships([WriteOp("touch", Relationship(
            "namespace", "warm", "viewer", "user", "alice",
            expiration=_time.time() + 300))])
        env.engine.lookup_resources("namespace", "view", "user", "alice")
        env.engine.write_relationships([WriteOp("delete", Relationship(
            "namespace", "warm", "viewer", "user", "alice"))])
        env.engine.write_relationships([WriteOp("touch", Relationship(
            "namespace", "exp", "viewer", "user", "alice",
            expiration=_time.time() + 0.6))])
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))

        task = asyncio.ensure_future(consume())
        # while the grant is live, the ADDED frame flows
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1),
                               timeout=10)
        # wait past expiry with ZERO further writes, then emit an event
        await asyncio.sleep(0.9)
        env.kube.emit_watch_event("namespaces", "MODIFIED", "exp")
        await asyncio.sleep(0.4)
        assert len(frames) == 1, "post-expiry frame must be dropped"
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_prefilter_strict_vs_lenient_id_mapping():
    """strict=True (the pre-headers run) raises on an unmappable id;
    strict=False (mid-stream recomputes) skips only that id — an aborted
    recompute would freeze the watch's allowed set, which fails OPEN for
    revocations."""
    from spicedb_kubeapi_proxy_tpu.authz.lookups import (
        PreFilterError,
        run_prefilter_sync,
    )
    from spicedb_kubeapi_proxy_tpu.engine import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.rules.expr import ExprError

    env = Env()
    env.engine.write_relationships([
        WriteOp("touch", parse_relationship("namespace:good#creator@user:a")),
        WriteOp("touch", parse_relationship("namespace:bad#creator@user:a")),
    ])
    info = parse_request_info("GET", "/api/v1/namespaces",
                              {"watch": ["true"]})
    from spicedb_kubeapi_proxy_tpu.rules.input import ResolveInput
    inp = ResolveInput.create(info, UserInfo(name="a"), headers={})
    from spicedb_kubeapi_proxy_tpu.rules.compile import compile_rule
    from spicedb_kubeapi_proxy_tpu.rules.proxyrule import parse_rule_configs
    rule = compile_rule(parse_rule_configs("""
match: [{apiVersion: v1, resource: namespaces, verbs: [list, watch]}]
prefilter:
  - fromObjectIDNameExpr: "{{resourceId}}"
    lookupMatchingResources:
      tpl: "namespace:$#view@user:{{user.name}}"
""")[0])
    pf = rule.pre_filters[0]

    class FailsOnBad:
        def evaluate_str(self, data):
            if data["resourceId"] == "bad":
                raise ExprError("unmappable id")
            return data["resourceId"]

    object.__setattr__(pf, "name_expr", FailsOnBad())
    # mapping_kind is DERIVED from the exprs: the duck-typed fake (no
    # refs/source) reclassifies the prefilter as "general" automatically,
    # so the substituted expr actually runs
    assert pf.mapping_kind == "general"
    with pytest.raises(PreFilterError, match="unmappable|mapping"):
        run_prefilter_sync(env.engine, pf, inp)  # strict default
    allowed = run_prefilter_sync(env.engine, pf, inp, strict=False)
    assert allowed.pairs == {("", "good")}  # bad skipped, not fatal


def test_watch_flushes_on_arrow_mediated_grant():
    """A NAMESPACE-level grant makes buffered POD frames flush (pod#view
    includes namespace->view): the event batch recomputes the full
    allowed set, catching permission changes the changed relationship's
    own type never mentions. (The reference's per-object re-check of
    same-type events misses this — our join is strictly stronger.)
    Symmetrically, revoking the namespace grant drops subsequent pod
    frames."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

        # the DEFAULT bootstrap has no namespace->view arrow on pods (and
        # the reference's sample create-pods rule writes the namespace
        # tuple keyed by bare name, disconnected from namespacedName
        # checks — our deploy/rules.yaml fixes that): use an arrowed
        # schema and write the consistently-keyed namespace tuple
        env = Env(bootstrap="""
schema: |-
  definition user {}
  definition namespace {
    relation creator: user
    relation viewer: user
    permission admin = creator
    permission view = viewer + creator
  }
  definition pod {
    relation namespace: namespace
    relation creator: user
    relation viewer: user
    permission edit = creator
    permission view = viewer + creator + namespace->view
  }
relationships: ""
""")
        await env.create_ns("wa", user="bob")
        await env.create_pod("wa", "api", user="bob")
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "pod:wa/api#namespace@namespace:wa"))])
        resp = await env.request("GET", "/api/v1/pods", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f)["object"]["metadata"]["name"])

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        assert not frames  # buffered: alice can't view bob's namespace
        # grant at the NAMESPACE level — no pod-type relationship changes
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:wa#viewer@user:alice"))])
        await asyncio.wait_for(_wait_for(lambda: frames == ["api"]),
                               timeout=10)
        # revoke the namespace grant; a subsequent pod event is dropped
        env.engine.write_relationships([WriteOp("delete", parse_relationship(
            "namespace:wa#viewer@user:alice"))])
        await asyncio.sleep(0.1)  # let the revocation reach the join
        env.kube.emit_watch_event("pods", "MODIFIED", "api", ns="wa")
        await asyncio.sleep(0.3)
        assert frames == ["api"]  # the MODIFIED frame was dropped
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_concurrent_watchers_per_user_isolation():
    """Three users watch namespaces concurrently; each stream delivers
    exactly that user's objects as grants land (proxy_test.go:615-649
    exercises per-user watch isolation with parallel clients)."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

        env = Env()
        # warm the jitted watch-check kernels before the delivery clock
        # starts: a cold first compile (up to ~3s on a loaded machine)
        # made a 5s all-or-nothing wait flaky
        env.engine.check_bulk([
            CheckItem("namespace", "warm", "view", "user", "alice")])
        frames = {}

        async def consume(user, stream):
            async for f in stream:
                frames[user].append(
                    json.loads(f)["object"]["metadata"]["name"])

        tasks = []
        for user in ("alice", "bob", "carol"):
            resp = await env.request("GET", "/api/v1/namespaces", user=user,
                                     query={"watch": ["true"]})
            assert resp.status == 200
            frames[user] = []
            tasks.append(asyncio.ensure_future(consume(user, resp.stream)))
        # create one namespace per user (interleaved)
        for user, ns in (("alice", "a-ns"), ("bob", "b-ns"),
                         ("carol", "c-ns")):
            r = await env.create_ns(ns, user=user)
            assert r.status == 201
        # and one namespace bob shares with carol
        r = await env.create_ns("shared", user="bob")
        assert r.status == 201
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:shared#viewer@user:carol"))])
        want = {"alice": ["a-ns"], "bob": ["b-ns", "shared"],
                "carol": ["c-ns", "shared"]}
        try:
            await asyncio.wait_for(
                _wait_for(lambda: frames == want), timeout=15)
        finally:
            for t in tasks:
                t.cancel()
            env.kube.stop_watches()
        assert frames == want  # reports per-user stream contents on failure
    run(go())


def test_watch_frames_pass_through_byte_identical():
    """The reference guarantees allowed watch frames are relayed
    byte-identical (frameCapturingReader, pkg/authz/frames.go:13-68) —
    no re-serialization, no key reordering. Compare the delivered bytes
    against exactly what the upstream emitted."""
    async def go():
        env = Env()
        await env.create_ns("bi", user="alice")
        # capture what the upstream actually sends
        sent = []
        orig_notify = env.kube._notify

        def capturing_notify(res, ns, event):
            sent.append((json.dumps(event) + "\n").encode())
            orig_notify(res, ns, event)

        env.kube._notify = capturing_notify
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        got = []

        async def consume():
            async for f in resp.stream:
                got.append(bytes(f))

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.05)
        env.kube.emit_watch_event("namespaces", "MODIFIED", "bi")
        await asyncio.wait_for(_wait_for(lambda: len(got) >= 2), timeout=5)
        task.cancel()
        # frame 0 is the initial ADDED (sent before capture); frame 1 must
        # be bit-for-bit the upstream's MODIFIED frame
        assert sent and got[1] == sent[0], (got[1], sent[0])
        env.kube.stop_watches()
    run(go())


UPDATE_PATCH_RULES = RULES + """
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: pod-update
match:
  - apiVersion: v1
    resource: pods
    verbs: ["update", "patch"]
check:
  - tpl: "pod:{{namespacedName}}#edit@user:{{user.name}}"
update:
  touches:
    # viewer is NOT written by the create rule, so its existence after an
    # update proves this rule's touches ran (reference touches #creator,
    # which create also writes — that assertion would be vacuous here)
    - tpl: "pod:{{namespacedName}}#viewer@user:{{user.name}}"
"""


def test_update_and_patch_verbs_dual_write():
    """Reference e2e updateTestResource rule (proxy_test.go:1256-1272):
    update/patch gated on #edit, dual-writing a #creator touch. The
    creator may update AND patch; a viewer-only user is denied both."""
    async def go():
        env = Env(rules_yaml=UPDATE_PATCH_RULES)
        await env.create_ns("upd", user="alice")
        await env.create_pod("upd", "api", user="alice")
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "pod:upd/api#viewer@user:bob"))])  # bob can view, not edit
        # the touched relation must not pre-exist: the assertion below is
        # only meaningful if the PUT's dual-write creates it
        assert not env.engine.store.exists(RelationshipFilter(
            "pod", "upd/api", "viewer", "user", "alice"))
        body = {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": "api", "namespace": "upd",
                             "labels": {"v": "2"}}}
        # creator updates: allowed, upstream applied, touch written
        r = await env.request("PUT", "/api/v1/namespaces/upd/pods/api",
                              user="alice", body=body)
        assert r.status == 200, r.body
        assert env.kube.objects[("pods", "upd", "api")]["metadata"][
            "labels"] == {"v": "2"}
        assert env.engine.store.exists(RelationshipFilter(
            "pod", "upd/api", "viewer", "user", "alice"))
        # creator patches: allowed
        body["metadata"]["labels"] = {"v": "3"}
        r = await env.request("PATCH", "/api/v1/namespaces/upd/pods/api",
                              user="alice", body=body)
        assert r.status == 200, r.body
        # viewer-only bob: denied on both verbs with a DISTINCT body, so
        # a fail-open forward would be visible upstream
        bob_body = {"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "api", "namespace": "upd",
                                 "labels": {"v": "bob-was-here"}}}
        rv = env.kube.objects[("pods", "upd", "api")]["metadata"][
            "resourceVersion"]
        for method in ("PUT", "PATCH"):
            r = await env.request(method, "/api/v1/namespaces/upd/pods/api",
                                  user="bob", body=bob_body)
            assert r.status == 403, (method, r.status)
        meta = env.kube.objects[("pods", "upd", "api")]["metadata"]
        assert meta["labels"] == {"v": "3"}
        assert meta["resourceVersion"] == rv  # no upstream write happened
    run(go())


def test_multiple_update_rules_rejected():
    async def go():
        dup = RULES + "\n---\n" + RULES.split("---")[0]  # duplicate create rule
        env = Env(rules_yaml=dup)
        resp = await env.create_ns("x")
        assert resp.status == 500
        assert b"only one" in resp.body
    run(go())


CRD_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: testresource-create
lock: Pessimistic
match:
- apiVersion: example.com/v1alpha1
  resource: testresources
  verbs: ["create"]
update:
  preconditionDoesNotExist:
  # subject-independent: NO creator may exist yet (the '$' wildcard), so
  # a second user's create conflicts instead of adding a second owner
  - tpl: "testresource:{{namespacedName}}#creator@user:$"
  creates:
  - tpl: "testresource:{{namespacedName}}#creator@user:{{user.name}}"
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: testresource-read
match:
- apiVersion: example.com/v1alpha1
  resource: testresources
  verbs: ["get", "list", "watch"]
prefilter:
- fromObjectIDNameExpr: "{{split_name(resourceId)}}"
  fromObjectIDNamespaceExpr: "{{split_namespace(resourceId)}}"
  lookupMatchingResources:
    tpl: "testresource:$#view@user:{{user.name}}"
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: testresource-write
lock: Pessimistic
match:
- apiVersion: example.com/v1alpha1
  resource: testresources
  verbs: ["update", "patch"]
check:
- tpl: "testresource:{{namespacedName}}#edit@user:{{user.name}}"
update:
  touches:
  - tpl: "testresource:{{namespacedName}}#creator@user:{{user.name}}"
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: testresource-delete
lock: Pessimistic
match:
- apiVersion: example.com/v1alpha1
  resource: testresources
  verbs: ["delete"]
check:
- tpl: "testresource:{{namespacedName}}#edit@user:{{user.name}}"
update:
  deletes:
  - tpl: "testresource:{{namespacedName}}#creator@user:{{user.name}}"
"""

CRD_BOOTSTRAP = """
schema: |-
  definition user {}
  definition testresource {
    relation creator: user
    relation viewer: user
    permission edit = creator
    permission view = viewer + creator
  }
"""


def test_crd_custom_group_end_to_end():
    """CRD-shaped resources under a named apiGroup
    (/apis/example.com/v1alpha1/...): create / get / list / watch /
    update / delete with per-user isolation and cross-user write denial —
    the reference installs testresource CRDs into envtest and drives the
    verbs on them (e2e/e2e_test.go:74, proxy_test.go:448-546).
    Unstructured handling means no type registration is needed here."""
    async def go():
        env = Env(rules_yaml=CRD_RULES, bootstrap=CRD_BOOTSTRAP)

        base = "/apis/example.com/v1alpha1/namespaces/ns1/testresources"
        resp = await env.request(
            "POST", base, user="alice",
            body={"apiVersion": "example.com/v1alpha1",
                  "kind": "TestResource",
                  "metadata": {"name": "tr1", "namespace": "ns1"}})
        assert resp.status == 201, resp.body
        resp = await env.request(
            "POST", base, user="bob",
            body={"apiVersion": "example.com/v1alpha1",
                  "kind": "TestResource",
                  "metadata": {"name": "tr2", "namespace": "ns1"}})
        assert resp.status == 201, resp.body

        # list isolation per user
        resp = await env.request("GET", base, user="alice")
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["tr1"]
        resp = await env.request("GET", base, user="bob")
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["tr2"]

        # single-get isolation
        assert (await env.request("GET", f"{base}/tr1",
                                  user="alice")).status == 200
        assert (await env.request("GET", f"{base}/tr1",
                                  user="bob")).status == 404

        # create conflict on the precondition
        resp = await env.request(
            "POST", base, user="bob",
            body={"apiVersion": "example.com/v1alpha1",
                  "kind": "TestResource",
                  "metadata": {"name": "tr1", "namespace": "ns1"}})
        assert resp.status == 409

        # update allowed for the owner, denied cross-user (check on #edit)
        resp = await env.request(
            "PUT", f"{base}/tr1", user="alice",
            body={"apiVersion": "example.com/v1alpha1",
                  "kind": "TestResource",
                  "metadata": {"name": "tr1", "namespace": "ns1",
                               "labels": {"v": "2"}}})
        assert resp.status == 200, resp.body
        resp = await env.request(
            "PUT", f"{base}/tr1", user="bob",
            body={"apiVersion": "example.com/v1alpha1",
                  "kind": "TestResource",
                  "metadata": {"name": "tr1", "namespace": "ns1"}})
        assert resp.status == 403

        # watch: alice's stream carries only her resource
        resp = await env.request("GET", base, user="alice",
                                 query={"watch": ["true"]})
        assert resp.status == 200 and resp.stream is not None
        async for frame in resp.stream:
            ev = json.loads(frame)
            assert ev["object"]["metadata"]["name"] == "tr1"
            break
        env.kube.stop_watches()

        # delete denied cross-user; owner delete removes object + rels
        assert (await env.request("DELETE", f"{base}/tr1",
                                  user="bob")).status == 403
        resp = await env.request("DELETE", f"{base}/tr1", user="alice")
        assert resp.status == 200
        resp = await env.request("GET", base, user="alice")
        assert json.loads(resp.body)["items"] == []
        assert not env.engine.store.exists(
            RelationshipFilter(resource_type="testresource",
                               resource_id="ns1/tr1"))
    run(go())


def test_watch_recomputes_shared_across_watchers():
    """VERDICT r3 directive 2: W watchers on one (rule, subject) must cost
    ONE device query per relevant write batch, not W — the hub groups them
    (reference shared watch service, pkg/authz/watch.go:48-109). Watchers
    with DISTINCT subjects each get their own group."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

        env = Env()
        await env.create_ns("shared-w", user="alice")
        env.engine.check_bulk([  # warm kernels off the delivery clock
            CheckItem("namespace", "warm", "view", "user", "alice")])
        n_watchers = 100
        tasks, streams = [], []
        frames_per = [[] for _ in range(n_watchers)]

        async def consume(i, stream):
            async for f in stream:
                frames_per[i].append(
                    json.loads(f)["object"]["metadata"]["name"])

        for i in range(n_watchers):
            resp = await env.request(
                "GET", "/api/v1/namespaces", user="alice",
                query={"watch": ["true"]})
            assert resp.status == 200
            streams.append(resp.stream)
            tasks.append(asyncio.ensure_future(consume(i, resp.stream)))
        # one more watcher for a DIFFERENT subject: its own group
        resp = await env.request("GET", "/api/v1/namespaces", user="bob",
                                 query={"watch": ["true"]})
        bob_frames = []

        async def consume_bob():
            async for f in resp.stream:
                bob_frames.append(json.loads(f)["object"]["metadata"]["name"])

        tasks.append(asyncio.ensure_future(consume_bob()))
        hub = env.deps.watch_hub
        assert hub is not None
        # registration happens when each stream starts being consumed
        await asyncio.wait_for(_wait_for(lambda: sum(
            len(g.watchers) for g in hub._groups.values()) == 101),
            timeout=10)
        assert len(hub._groups) == 2, \
            "100 same-subject watchers + 1 other must form exactly 2 groups"
        await asyncio.sleep(0.1)  # drain initial traffic
        lookups0 = metrics.counter("engine_lookups_total").value
        # one relevant write batch: a new grant for alice
        await env.create_ns("shared-w2", user="alice")
        env.engine.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:shared-w2#viewer@user:alice"))])
        # every alice watcher must see the new namespace
        await asyncio.wait_for(_wait_for(lambda: all(
            "shared-w2" in fp for fp in frames_per)), timeout=10)
        await asyncio.sleep(0.2)  # let any trailing recomputes land
        recomputes = metrics.counter("engine_lookups_total").value - lookups0
        # O(groups) per batch, NOT O(watchers): the two writes above are
        # at most 2 batches x 2 groups (+1 for trigger coalescing slack)
        assert recomputes <= 5, \
            f"{recomputes} device lookups for 101 watchers on 2 groups"
        assert not any("shared-w2" in f for f in bob_frames)
        for t in tasks:
            t.cancel()
        env.kube.stop_watches()
    run(go())


def _postfilter_rule(resource: str, *checks: str) -> str:
    return ("apiVersion: authzed.com/v1alpha1\nkind: ProxyRule\n"
            "metadata:\n  name: listed\nmatch:\n- apiVersion: v1\n"
            f"  resource: {resource}\n  verbs: [list]\npostfilter:\n"
            + "".join(f"- checkPermissionTemplate:\n    {c}\n"
                      for c in checks))


BY_NAMESPACE = 'tpl: "namespace:{{namespace}}#view@user:{{user.name}}"'
BY_NSNAME = 'tpl: "pod:{{namespacedName}}#view@user:{{user.name}}"'
LISTED_PODS = [("ns1", "a", "x"), ("ns1", "b", "y"), ("ns2", "c", "x"),
               ("ns2", "d", "y"), ("ns3", "e", "x"), ("ns1", "f", "y")]
GRANTS = ["namespace:ns1#viewer@user:alice", "namespace:ns3#viewer@user:alice",
          "namespace:x#viewer@user:alice", "pod:ns1/a#viewer@user:alice",
          "pod:ns2/c#viewer@user:alice", "pod:ns1/f#viewer@user:alice",
          "pod:b#viewer@user:alice", "pod:e#viewer@user:alice"]


def _listed(kind: str = "PodList", objs=None) -> dict:
    objs = objs if objs is not None else [
        {"kind": "Pod", "metadata": {"name": n, "namespace": ns,
                                     "labels": {"team": team}}}
        for ns, n, team in LISTED_PODS]
    if kind == "Table":
        return {"kind": "Table", "rows": [
            {"cells": [o["metadata"]["name"]], "object": o} for o in objs]}
    return {"kind": kind, "items": objs}


def _plain_postfilter(engine, post_filters, input, doc):
    """The plain per-object resolver: every object its own ResolveInput,
    every template of every rule resolved from it, nothing shared between
    objects. -> (status, names kept, the checks it asks)."""
    import dataclasses
    from spicedb_kubeapi_proxy_tpu.rules.expr import ExprError

    table = doc["kind"] == "Table"
    objs = [r["object"] for r in doc["rows"]] if table else doc["items"]
    per_object = []
    for obj in objs:
        meta = obj.get("metadata") or {}
        name, ns = meta.get("name") or "", meta.get("namespace") or ""
        if input.request.resource == "namespaces":
            ns = ""
        one = dataclasses.replace(
            input, name=name, namespace=ns, object=obj,
            namespaced_name=f"{ns}/{name}" if ns else name)
        try:
            per_object.append([
                CheckItem(r.resource_type, r.resource_id,
                          r.resource_relation, r.subject_type, r.subject_id,
                          r.subject_relation or None)
                for pf in post_filters for r in pf.rel.generate(one)])
        except ExprError:
            return 401, [], set()
    flat = [c for cs in per_object for c in cs]
    verdict = dict(zip(flat, engine.check_bulk(flat)))
    return 200, [o["metadata"]["name"] for o, cs in zip(objs, per_object)
                 if all(verdict[c] for c in cs)], set(flat)


@pytest.mark.parametrize("resource,checks,doc,resolved,n_checks,kept", [
    ("pods", [BY_NAMESPACE], _listed(), 3, 3, ["a", "b", "e", "f"]),
    ("pods", ['tpl: "pod:{{name}}#view@user:{{user.name}}"'], _listed(),
     6, 6, ["b", "e"]),
    ("pods", [BY_NSNAME], _listed(), 6, 6, ["a", "c", "f"]),
    ("pods", ['tpl: "namespace:{{object.metadata.labels.team}}#view'
              '@user:{{user.name}}"'], _listed(), 6, 2, ["a", "c", "e"]),
    ("pods", ['tpl: "namespace:{{this.namespace}}#view@user:{{user.name}}"'],
     _listed(), 6, 3, ["a", "b", "e", "f"]),
    ("pods", ["tupleSet: '[\"namespace:\" + namespace + \"#view@user:\" + "
              "user.name, \"namespace:ns1#view@user:\" + user.name]'"],
     _listed(), 3, 3, ["a", "b", "e", "f"]),
    ("pods", ["tupleSet: '[\"namespace:ns1#view@user:\" + user.name]'"],
     _listed(), 1, 1, ["a", "b", "c", "d", "e", "f"]),
    ("pods", [BY_NAMESPACE, BY_NSNAME], _listed(), 9, 9, ["a", "f"]),
    ("pods", [BY_NAMESPACE], _listed("Table"), 3, 3, ["a", "b", "e", "f"]),
    ("namespaces",
     ['tpl: "namespace:{{namespacedName}}#view@user:{{user.name}}"'],
     _listed("NamespaceList", [
         {"kind": "Namespace", "metadata": {"name": n, "namespace": n}}
         for n in ("ns1", "ns2", "ns3")]), 3, 3, ["ns1", "ns3"]),
    ("pods", [BY_NAMESPACE], _listed(objs=[
        {"metadata": {"name": "a", "namespace": "ns1"}},
        {"metadata": {"name": "g"}}]), None, 0, None),
    ("pods", [BY_NAMESPACE], _listed(objs=[]), 0, 0, []),
], ids=["namespace", "name", "namespacedName", "object-labels", "this",
        "tupleset", "tupleset-constant", "two-rules", "table",
        "namespaces-list", "empty-namespace", "no-objects"])
def test_postfilter_asks_what_a_per_object_resolver_asks(
        resource, checks, doc, resolved, n_checks, kept):
    """A postfiltered list keeps the objects, and asks the set of checks,
    that resolving every object on its own does, whatever its rule reads;
    it resolves once per distinct value of what the rule reads (per
    object for a rule that reads the object itself) and asks each
    distinct check once."""
    from spicedb_kubeapi_proxy_tpu.engine import WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.proxy.types import json_response
    from spicedb_kubeapi_proxy_tpu.rules import RequestMeta
    from spicedb_kubeapi_proxy_tpu.rules.input import ResolveInput
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    async def go():
        env = Env(rules_yaml=_postfilter_rule(resource, *checks))
        env.engine.write_relationships(
            [WriteOp("touch", parse_relationship(g)) for g in GRANTS])

        async def upstream(req):
            return json_response(200, doc)
        env.deps.upstream = upstream
        asked = []
        check_bulk = env.engine.check_bulk

        def recorded(items, **kw):
            asked.append(list(items))
            return check_bulk(items, **kw)

        info = parse_request_info("GET", f"/api/v1/{resource}", {})
        rules = env.deps.matcher.match(RequestMeta.from_request(info))
        status, plain_kept, plain_checks = _plain_postfilter(
            env.engine, [p for r in rules for p in r.post_filters],
            ResolveInput.create(info, UserInfo(name="alice"), headers={}),
            doc)
        counters = [metrics.counter("proxy_postfilter_items_total"),
                    metrics.counter("proxy_postfilter_resolved_total")]
        before = [c.value for c in counters]
        env.engine.check_bulk = recorded
        resp = await env.request("GET", f"/api/v1/{resource}")
        assert resp.status == status, resp.body
        if kept is None:  # refused whole: nothing dispatched, nothing kept
            assert status == 401 and asked == []
            assert b"resolved empty" in resp.body
            assert [c.value for c in counters] == before
            return
        out = json.loads(resp.body)
        objs = ([r["object"] for r in out["rows"]] if doc["kind"] == "Table"
                else out["items"])
        assert [o["metadata"]["name"] for o in objs] == plain_kept == kept
        (bulk,) = asked  # one bulk check a list
        assert len(bulk) == len(set(bulk)) == n_checks
        assert set(bulk) == plain_checks
        n = len(doc["rows" if doc["kind"] == "Table" else "items"])
        assert [c.value - b for c, b in zip(counters, before)] == [
            n, resolved]
    run(go())


def test_postfilter_proto_response_clean_401_not_500():
    """A hand-crafted proto Accept on a postfilter route is rewritten to
    JSON upstream; an upstream that returns protobuf ANYWAY must produce
    a clean 401 from the postfilter, never a 500 (VERDICT r3 weak #7)."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
        from spicedb_kubeapi_proxy_tpu.proxy import kubeproto
        from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyResponse

        # postfilter-ONLY rule set: the response must reach the
        # postfilter (no prefilter in front) to prove ITS 4xx path
        env = Env(rules_yaml=POSTFILTER_RULES)
        env.engine.write_relationships([
            WriteOp("touch",
                    parse_relationship("pod:ns1/a#viewer@user:alice")),
        ])

        async def stubborn_proto_upstream(req):
            return ProxyResponse(
                status=200,
                headers={"Content-Type": kubeproto.CONTENT_TYPE},
                body=kubeproto.MAGIC + b"\x0a\x00")

        env.deps.upstream = stubborn_proto_upstream
        resp = await env.request(
            "GET", "/api/v1/namespaces/ns1/pods", user="alice",
            headers={"Accept":
                     "application/vnd.kubernetes.protobuf;as=Table"})
        assert resp.status == 401, resp.status
        assert b"Status" in resp.body  # a proper kube Status body
    run(go())


def test_prefilter_proto_table_end_to_end():
    """A protobuf Table response on a prefiltered route is row-filtered
    at the wire level through the full middleware (reference
    responsefilterer.go:349-374)."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.proxy import kubeproto
        from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyResponse

        env = Env()
        await env.create_ns("mine", user="alice")
        await env.create_ns("theirs", user="bob")

        def ld(f, p):
            return kubeproto._ld_field(f, p)

        def row(name):
            pom = ld(1, ld(1, name.encode()))  # PartialObjectMetadata
            wrapped = (kubeproto.MAGIC
                       + ld(1, ld(1, b"meta.k8s.io/v1")
                            + ld(2, b"PartialObjectMetadata"))
                       + ld(2, pom))
            return ld(1, ld(1, b'"cell"')) + ld(3, ld(1, wrapped))

        table_raw = ld(1, ld(2, b"rv1")) + ld(3, row("mine")) \
            + ld(3, row("theirs"))
        body = (kubeproto.MAGIC
                + ld(1, ld(1, b"meta.k8s.io/v1") + ld(2, b"Table"))
                + ld(2, table_raw))

        async def proto_table_upstream(req):
            return ProxyResponse(
                status=200,
                headers={"Content-Type": kubeproto.CONTENT_TYPE},
                body=body)

        env.deps.upstream = proto_table_upstream
        resp = await env.request("GET", "/api/v1/namespaces", user="alice")
        assert resp.status == 200
        _, kind, new_raw = kubeproto.decode_unknown(resp.body)
        assert kind == "Table"
        rows = [p for f, w, _, p in kubeproto.fields(new_raw) if f == 3]
        assert len(rows) == 1
        assert kubeproto.table_row_meta(rows[0]) == ("", "mine")
    run(go())


def test_dual_write_genuine_rv_conflict_from_fake():
    """The fake upstream now enforces optimistic concurrency itself: an
    update carrying a stale resourceVersion draws a GENUINE 409 from the
    fake (not an injected failure), and the dual-write workflow completes
    with the reference's verb-aware semantics (409 counts as applied,
    workflow.go:252-275) — no hung workflow, no leftover locks."""
    async def go():
        env = Env(rules_yaml=UPDATE_PATCH_RULES)
        await env.create_ns("rv-ns", user="alice")
        await env.create_pod("rv-ns", "api", user="alice")
        obj = json.loads((await env.request(
            "GET", "/api/v1/namespaces/rv-ns/pods/api")).body)
        stale_rv = obj["metadata"]["resourceVersion"]
        # an out-of-band write bumps the object's RV
        env.kube.put("pods", "api", ns="rv-ns",
                     obj={"metadata": {"name": "api",
                                       "namespace": "rv-ns",
                                       "labels": {"touched": "yes"}}})
        # now update through the proxy with the STALE rv
        obj["metadata"]["resourceVersion"] = stale_rv
        obj["metadata"]["labels"] = {"mine": "yes"}
        resp = await env.request("PUT", "/api/v1/namespaces/rv-ns/pods/api",
                                 user="alice", body=obj)
        assert resp.status == 409, resp.body
        assert b"Conflict" in resp.body or b"modified" in resp.body
        # workflow finished cleanly: no lock tuples left behind
        # (reference invariant, proxy_test.go:106-111)
        assert not env.engine.store.exists(
            RelationshipFilter(resource_type="lock"))
        # the conflicted write did NOT land upstream
        cur = env.kube.objects[("pods", "rv-ns", "api")]
        assert cur["metadata"].get("labels") == {"touched": "yes"}
        # a fresh-RV update then succeeds
        obj["metadata"]["resourceVersion"] = \
            cur["metadata"]["resourceVersion"]
        resp = await env.request("PUT", "/api/v1/namespaces/rv-ns/pods/api",
                                 user="alice", body=obj)
        assert resp.status == 200
        assert env.kube.objects[("pods", "rv-ns", "api")]["metadata"][
            "labels"] == {"mine": "yes"}
    run(go())


def test_delete_with_finalizer_two_phase():
    """Finalizer semantics in the fake: DELETE on a finalized object only
    marks it terminating (deletionTimestamp, MODIFIED event); the object
    disappears when a controller clears the finalizers — what the
    reference gets from envtest + a real GC controller
    (e2e/e2e_test.go:156-186)."""
    async def go():
        env = Env()
        assert (await env.create_ns("fin-ns")).status == 201
        key = ("namespaces", "", "fin-ns")
        env.kube.objects[key]["metadata"]["finalizers"] = ["test/guard"]
        resp = await env.request("DELETE", "/api/v1/namespaces/fin-ns")
        assert resp.status == 200
        # still present upstream, terminating
        obj = env.kube.objects.get(key)
        assert obj is not None
        assert obj["metadata"]["deletionTimestamp"]
        # the dual-write already removed the relationships (the reference
        # also deletes rels on the DELETE request; kube-side GC finishes
        # later)
        from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter

        assert not env.engine.store.exists(RelationshipFilter(
            "namespace", "fin-ns", "creator"))
        # a controller clears the finalizer -> object actually deleted
        from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyRequest
        patch = ProxyRequest(
            method="PATCH", path="/api/v1/namespaces/fin-ns",
            headers={"Content-Type": "application/merge-patch+json"},
            body=json.dumps({"metadata": {"finalizers": None}}).encode())
        r = await env.kube(patch)
        assert r.status == 200
        assert key not in env.kube.objects
    run(go())


def test_watch_error_status_frames_pass_through():
    """A terminal ERROR/Status frame (watch expiry, 410 Gone) carries no
    authorizable object; suppressing it would hang the client on a dead
    watch — it must pass through (review finding: the JSON path buffered
    it under the unkeyable ("", "") pair forever)."""
    async def go():
        env = Env()
        await env.create_ns("err-ns", user="alice")
        resp = await env.request("GET", "/api/v1/namespaces", user="alice",
                                 query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))

        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1),
                               timeout=5)
        env.kube._notify("namespaces", "", {
            "type": "ERROR",
            "object": {"kind": "Status", "apiVersion": "v1",
                       "code": 410, "reason": "Expired"}})
        await asyncio.wait_for(_wait_for(lambda: any(
            f["type"] == "ERROR" for f in frames)), timeout=5)
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_list_filter_no_drop_is_byte_identical():
    """When every list item / table row is allowed, the response body
    passes through byte-identical — no decode/re-serialize artifacts
    (key order, float formatting, unicode escapes) and no re-serialize
    cost on multi-MB bodies."""
    from spicedb_kubeapi_proxy_tpu.authz.filterer import filter_body
    from spicedb_kubeapi_proxy_tpu.authz.lookups import AllowedSet
    from spicedb_kubeapi_proxy_tpu.rules.input import (
        RequestInfo,
        ResolveInput,
        UserInfo,
    )

    input = ResolveInput.create(
        RequestInfo(verb="list", api_version="v1", resource="pods",
                    path="/api/v1/pods"),
        UserInfo(name="a"))
    # deliberately quirky serialization a re-dump would normalize
    body = (b'{"kind":"PodList",  "items":[\n'
            b'  {"metadata":{"name":"p1","namespace":"ns"}},'
            b'{"metadata":{"namespace":"ns","name":"p2"},"x":1.50}]}')
    allowed = AllowedSet({("ns", "p1"), ("ns", "p2")})
    status, out = filter_body(body, allowed, input)
    assert (status, out) == (200, body)
    # dropping one item still filters (and re-serializes)
    partial = AllowedSet({("ns", "p1")})
    status, out = filter_body(body, partial, input)
    assert status == 200
    names = [o["metadata"]["name"] for o in json.loads(out)["items"]]
    assert names == ["p1"]
    # Table branch: all rows kept -> byte-identical; a drop re-serializes
    table = (b'{"kind":"Table", "rows":[\n'
             b' {"cells":["p1"],"object":{"metadata":'
             b'{"name":"p1","namespace":"ns"}}},'
             b' {"cells":["p2"],"object":{"metadata":'
             b'{"name":"p2","namespace":"ns"}}}]}')
    status, out = filter_body(table, allowed, input)
    assert (status, out) == (200, table)
    status, out = filter_body(table, partial, input)
    assert status == 200
    kept_rows = json.loads(out)["rows"]
    assert [r["object"]["metadata"]["name"] for r in kept_rows] == ["p1"]


def test_prefilter_mapping_fast_paths_match_general_evaluation():
    """run_prefilter_sync short-circuits the two deploy/rules.yaml
    mapping shapes (identity, split_name/split_namespace) into plain
    string ops; they must produce byte-for-byte the same allowed pairs
    as general expression evaluation, including slashless (cluster-
    scoped) and multi-slash ids."""
    from spicedb_kubeapi_proxy_tpu.authz.lookups import run_prefilter_sync
    from spicedb_kubeapi_proxy_tpu.engine import Engine, WriteOp
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    from spicedb_kubeapi_proxy_tpu.rules.matcher import (
        MapMatcher,
        RequestMeta,
    )
    from spicedb_kubeapi_proxy_tpu.rules.input import (
        RequestInfo,
        ResolveInput,
        UserInfo,
    )

    engine = Engine()
    ids = ["plain", "ns1/pod-a", "ns2/pod/with/slashes"]
    engine.write_relationships([
        WriteOp("touch", parse_relationship(f"pod:{i}#viewer@user:alice"))
        for i in ids
    ])
    input = ResolveInput.create(
        RequestInfo(verb="list", api_version="v1", resource="pods",
                    path="/api/v1/pods"),
        UserInfo(name="alice"))

    def pf_for(mapping_yaml: str):
        rules = MapMatcher.from_yaml(f"""
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
lock: Pessimistic
match:
- apiVersion: v1
  resource: pods
  verbs: ["list"]
prefilter:
{mapping_yaml}
""")
        return rules.match(RequestMeta(
            verb="list", api_group="", api_version="v1",
            resource="pods"))[0].pre_filters[0]

    # identity fast path == a general expr forced off the fast path by
    # an equivalent-but-differently-spelled source; interior whitespace
    # must NOT defeat the compile-time classification
    pf_id = pf_for(
        '- fromObjectIDNameExpr: "{{ resourceId }}"\n'
        '  lookupMatchingResources:\n'
        '    tpl: "pod:$#view@user:{{user.name}}"')
    assert pf_id.mapping_kind == "identity"
    fast = run_prefilter_sync(engine, pf_id, input)
    general = run_prefilter_sync(engine, pf_for(
        '- fromObjectIDNameExpr: "{{string(resourceId)}}"\n'
        '  lookupMatchingResources:\n'
        '    tpl: "pod:$#view@user:{{user.name}}"'), input)
    assert fast.pairs == general.pairs == {("", i) for i in ids}

    # a braceless LITERAL template that merely spells "resourceId" means
    # a CONSTANT name (the {{ }}/literal duality) — it must NOT take the
    # identity fast path (review finding: matching it fails open)
    pf_lit = pf_for(
        '- fromObjectIDNameExpr: "resourceId"\n'
        '  lookupMatchingResources:\n'
        '    tpl: "pod:$#view@user:{{user.name}}"')
    assert pf_lit.mapping_kind == "general"
    literal = run_prefilter_sync(engine, pf_lit, input)
    assert literal.pairs == {("", "resourceId")}

    # split fast path == general split evaluation (name-only spelling
    # avoids the fast path; add the ns expr separately); whitespace
    # variants classify too
    pf_split = pf_for(
        '- fromObjectIDNameExpr: "{{ split_name( resourceId ) }}"\n'
        '  fromObjectIDNamespaceExpr: '
        '"{{ split_namespace( resourceId ) }}"\n'
        '  lookupMatchingResources:\n'
        '    tpl: "pod:$#view@user:{{user.name}}"')
    assert pf_split.mapping_kind == "split"
    fast = run_prefilter_sync(engine, pf_split, input)
    general = run_prefilter_sync(engine, pf_for(
        '- fromObjectIDNameExpr: "{{string(split_name(resourceId))}}"\n'
        '  fromObjectIDNamespaceExpr: '
        '"{{string(split_namespace(resourceId))}}"\n'
        '  lookupMatchingResources:\n'
        '    tpl: "pod:$#view@user:{{user.name}}"'), input)
    assert fast.pairs == general.pairs == {
        ("", "plain"), ("ns1", "pod-a"), ("ns2", "pod/with/slashes")}


def test_gc_cascade_background_semantics():
    """Fake GC fidelity (reference runs a REAL kube GC controller,
    e2e/e2e_test.go:156-186): deleting an owner background-deletes
    dependents whose ownerReferences all dangle; a dependent with a
    second LIVING owner survives; Orphan strips refs instead; a
    finalized dependent terminates rather than vanishing; grandchildren
    cascade recursively."""
    async def go():
        kube = FakeKube()

        def put_with_refs(res, name, ns="", refs=None, finalizers=None):
            obj = {"metadata": {}}
            if refs:
                obj["metadata"]["ownerReferences"] = refs
            if finalizers:
                obj["metadata"]["finalizers"] = finalizers
            return kube.put(res, name, ns, obj)

        ref = lambda kind, name: {"apiVersion": "v1", "kind": kind,  # noqa: E731
                                  "name": name}
        put_with_refs("widgets", "parent")
        put_with_refs("widgets", "keeper")
        put_with_refs("gadgets", "child", refs=[ref("Widget", "parent")])
        put_with_refs("gadgets", "shared", refs=[ref("Widget", "parent"),
                                                 ref("Widget", "keeper")])
        put_with_refs("gizmos", "grandchild",
                      refs=[ref("Gadget", "child")])
        put_with_refs("gadgets", "finalized",
                      refs=[ref("Widget", "parent")],
                      finalizers=["test/guard"])
        from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyRequest

        r = await kube(ProxyRequest(method="DELETE",
                                    path="/api/v1/widgets/parent"))
        assert r.status == 200
        # background: cascade lands after the handler returns
        await asyncio.wait_for(_wait_for(
            lambda: ("gadgets", "", "child") not in kube.objects), 5)
        await asyncio.wait_for(_wait_for(
            lambda: ("gizmos", "", "grandchild") not in kube.objects), 5)
        # the dependent with a living second owner survives
        assert ("gadgets", "", "shared") in kube.objects
        # the finalized dependent is terminating, not gone
        fin = kube.objects[("gadgets", "", "finalized")]
        assert fin["metadata"]["deletionTimestamp"]
        # orphan policy: the deleted owner's refs are stripped from its
        # (sole-owner) dependent, which survives
        put_with_refs("gadgets", "solo", refs=[ref("Widget", "keeper")])
        r = await kube(ProxyRequest(
            method="DELETE", path="/api/v1/widgets/keeper",
            query={"propagationPolicy": ["Orphan"]}))
        assert r.status == 200
        await asyncio.wait_for(_wait_for(
            lambda: "ownerReferences" not in
            kube.objects[("gadgets", "", "solo")]["metadata"]), 5)
        assert ("gadgets", "", "solo") in kube.objects
        # orphan intent survives a finalizer wait (review finding): the
        # owner terminates first, and the GC that runs when its finalizer
        # clears must still ORPHAN, not background-delete
        put_with_refs("widgets", "slowowner", finalizers=["test/guard"])
        put_with_refs("gadgets", "patient",
                      refs=[ref("Widget", "slowowner")])
        r = await kube(ProxyRequest(
            method="DELETE", path="/api/v1/widgets/slowowner",
            query={"propagationPolicy": ["Orphan"]}))
        assert r.status == 200
        assert ("widgets", "", "slowowner") in kube.objects  # terminating
        r = await kube(ProxyRequest(
            method="PATCH", path="/api/v1/widgets/slowowner",
            headers={"Content-Type": "application/merge-patch+json"},
            body=json.dumps({"metadata": {"finalizers": None}}).encode()))
        assert r.status == 200
        await asyncio.wait_for(_wait_for(
            lambda: ("widgets", "", "slowowner") not in kube.objects), 5)
        await asyncio.wait_for(_wait_for(
            lambda: "ownerReferences" not in
            kube.objects[("gadgets", "", "patient")]["metadata"]), 5)
        assert ("gadgets", "", "patient") in kube.objects
    run(go())


def test_unparseable_watch_frame_fails_closed():
    """A frame that is neither JSON nor a well-formed proto frame (e.g.
    truncated by a dying upstream) must never pass through unjudged
    (review finding: it used to be forwarded verbatim)."""
    from spicedb_kubeapi_proxy_tpu.authz.watch import _frame_object_key
    from spicedb_kubeapi_proxy_tpu.proxy import kubeproto
    from spicedb_kubeapi_proxy_tpu.rules.matcher import (
        MapMatcher,
        RequestMeta,
    )

    rules = MapMatcher.from_yaml("""
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
lock: Pessimistic
match:
- apiVersion: v1
  resource: namespaces
  verbs: ["watch"]
prefilter:
- fromObjectIDNameExpr: "{{resourceId}}"
  lookupMatchingResources:
    tpl: "namespace:$#view@user:{{user.name}}"
""")
    pf = rules.match(RequestMeta(verb="watch", api_group="",
                                 api_version="v1",
                                 resource="namespaces"))[0].pre_filters[0]
    with pytest.raises(kubeproto.ProtoError):
        _frame_object_key(b"garbage not json", pf)
    with pytest.raises(kubeproto.ProtoError):
        # a truncated proto frame: length prefix larger than the body
        _frame_object_key(b"\x00\x00\x10\x00partial", pf)
    # bare whitespace keepalives are harmless passthrough
    assert _frame_object_key(b"\n", pf) is None


@pytest.mark.parametrize("mode", ["Pessimistic", "Optimistic"])
def test_dual_write_delete_parent_cascades_children(mode):
    """VERDICT r4 directive 7: dual-write DELETE of a parent whose
    children ride ownerReferences — on success the parent's relationships
    are removed and the fake's GC cascades the children (watch-visible);
    on kube failure the workflow ROLLS BACK the parent's relationships
    and no cascade fires. Both lock modes."""
    rules = RULES.replace("lock: Pessimistic", f"lock: {mode}")

    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter

        env = Env(rules_yaml=rules)
        # parent namespace + child pod referencing it
        assert (await env.create_ns("gcp")).status == 201
        ns_uid = env.kube.objects[("namespaces", "", "gcp")]["metadata"]["uid"]
        resp = await env.request(
            "POST", "/api/v1/namespaces/gcp/pods", user="alice",
            body={"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "victim", "namespace": "gcp",
                               "ownerReferences": [{
                                   "apiVersion": "v1", "kind": "Namespace",
                                   "name": "gcp", "uid": ns_uid}]}})
        assert resp.status == 201, resp.body
        assert env.engine.store.exists(RelationshipFilter(
            "pod", "gcp/victim", "creator", "user", "alice"))

        # -- failure leg first: kube rejects the DELETE ------------------
        env.kube.fail_next(n=1, method="DELETE")
        resp = await env.request("DELETE", "/api/v1/namespaces/gcp",
                                 user="alice")
        assert resp.status >= 400
        # the child was never cascaded (the kube delete never landed)
        assert ("pods", "gcp", "victim") in env.kube.objects
        if mode == "Pessimistic":
            # pessimistic rolls back on a rejected status
            # (workflow.go:232-234): the parent's relationships return
            assert env.engine.store.exists(RelationshipFilter(
                "namespace", "gcp", "creator", "user", "alice"))
        else:
            # reference optimistic semantics: a rejected (non-error) kube
            # response is returned WITHOUT rollback (workflow.go:327-351
            # only arbitrates activity errors) — restore the rel so the
            # success leg's authorization still holds
            from spicedb_kubeapi_proxy_tpu.engine import WriteOp
            from spicedb_kubeapi_proxy_tpu.models.tuples import (
                parse_relationship,
            )

            if not env.engine.store.exists(RelationshipFilter(
                    "namespace", "gcp", "creator", "user", "alice")):
                env.engine.write_relationships([WriteOp(
                    "touch", parse_relationship(
                        "namespace:gcp#creator@user:alice"))])
        assert not env.engine.store.exists(
            RelationshipFilter(resource_type="lock"))

        # -- success leg: delete lands, GC cascades the child -----------
        resp = await env.request("DELETE", "/api/v1/namespaces/gcp",
                                 user="alice")
        assert resp.status == 200, resp.body
        assert not env.engine.store.exists(RelationshipFilter(
            "namespace", "gcp", "creator"))
        assert ("namespaces", "", "gcp") not in env.kube.objects
        await asyncio.wait_for(_wait_for(
            lambda: ("pods", "gcp", "victim") not in env.kube.objects), 5)
        # no lock tuples left behind in either mode (reference invariant,
        # proxy_test.go:106-111)
        assert not env.engine.store.exists(
            RelationshipFilter(resource_type="lock"))
    run(go())


def test_watch_bookmarks_pass_through_filter():
    """BOOKMARK events carry no authorizable object; the filtered watch
    must pass them through (clients use them to checkpoint), not swallow
    them as unauthorized frames."""
    async def go():
        env = Env()
        await env.create_ns("bm-ns", user="alice")
        resp = await env.request(
            "GET", "/api/v1/namespaces", user="alice",
            query={"watch": ["true"], "allowWatchBookmarks": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(json.loads(f))

        task = asyncio.ensure_future(consume())
        # initial ADDED + the initial-events-end bookmark
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 2),
                               timeout=10)
        types = [f["type"] for f in frames]
        assert "BOOKMARK" in types and "ADDED" in types
        # a periodic bookmark also flows
        env.kube.emit_bookmark("namespaces")
        await asyncio.wait_for(
            _wait_for(lambda: types.count("BOOKMARK") < len(
                [f for f in frames if f["type"] == "BOOKMARK"])),
            timeout=10)
        task.cancel()
        env.kube.stop_watches()
    run(go())


def test_strategic_merge_patch_through_dual_write():
    """Strategic-merge-patch fidelity in the fake upstream: lists of
    named objects merge by name (the kube patchMergeKey convention) and
    $patch: delete removes entries — exercised through the proxy's patch
    dual-write path."""
    async def go():
        env = Env(rules_yaml=UPDATE_PATCH_RULES)
        await env.create_ns("smp", user="alice")
        await env.create_pod("smp", "api", user="alice")
        key = ("pods", "smp", "api")
        env.kube.objects[key]["spec"] = {"containers": [
            {"name": "app", "image": "app:v1"},
            {"name": "sidecar", "image": "sc:v1"},
        ]}
        resp = await env.request(
            "PATCH", "/api/v1/namespaces/smp/pods/api", user="alice",
            headers={"Content-Type":
                     "application/strategic-merge-patch+json"},
            body={"spec": {"containers": [
                {"name": "app", "image": "app:v2"},
                {"name": "sidecar", "$patch": "delete"},
                {"name": "logger", "image": "log:v1"},
            ]}})
        assert resp.status == 200, resp.body
        got = {c["name"]: c.get("image")
               for c in env.kube.objects[key]["spec"]["containers"]}
        assert got == {"app": "app:v2", "logger": "log:v1"}
        # plain merge-patch still REPLACES lists wholesale
        resp = await env.request(
            "PATCH", "/api/v1/namespaces/smp/pods/api", user="alice",
            headers={"Content-Type": "application/merge-patch+json"},
            body={"spec": {"containers": [
                {"name": "only", "image": "o:v1"}]}})
        assert resp.status == 200
        assert [c["name"] for c in
                env.kube.objects[key]["spec"]["containers"]] == ["only"]
    run(go())


def test_watch_churn_no_leaked_hub_state():
    """Rapid watcher churn under write load: watchers that come and go
    must leave ZERO hub state behind (groups empty, pump stopped) and
    never wedge registration for later watchers — the register/
    unregister/teardown interleavings are all lock-ordered."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.engine import WriteOp
        from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

        env = Env()
        await env.create_ns("churn", user="alice")
        env.engine.check_bulk([
            CheckItem("namespace", "warm", "view", "user", "alice")])

        async def one_watcher(i):
            resp = await env.request(
                "GET", "/api/v1/namespaces", user="alice",
                query={"watch": ["true"]})
            assert resp.status == 200
            frames = 0
            async for f in resp.stream:
                frames += 1
                if frames >= 1 + (i % 2 == 0):
                    break  # churn: leave after 1-2 frames
            await resp.stream.aclose()

        async def writer():
            for j in range(10):
                env.engine.write_relationships([WriteOp(
                    "touch", parse_relationship(
                        f"namespace:churn#viewer@user:w{j}"))])
                env.kube.emit_watch_event("namespaces", "MODIFIED",
                                          "churn")
                await asyncio.sleep(0.02)

        for wave in range(3):
            tasks = [asyncio.ensure_future(one_watcher(i))
                     for i in range(12)]
            wtask = asyncio.ensure_future(writer())
            await asyncio.wait_for(
                asyncio.gather(*tasks, wtask), timeout=30)
        hub = env.deps.watch_hub
        await asyncio.wait_for(_wait_for(
            lambda: not hub._groups), timeout=10)
        assert hub._pump_task is None, "pump must stop with no watchers"
        assert hub._push_stream is None
        # and a fresh watcher still works after all the churn
        resp = await env.request("GET", "/api/v1/namespaces",
                                 user="alice", query={"watch": ["true"]})
        frames = []

        async def consume():
            async for f in resp.stream:
                frames.append(f)

        t = asyncio.ensure_future(consume())
        await asyncio.wait_for(_wait_for(lambda: len(frames) >= 1),
                               timeout=10)
        t.cancel()
        env.kube.stop_watches()
    run(go())


# -- the body filter's call site: off the event loop by the body's length -----

def _padded_upstream(kube, pad_items: int):
    """``kube``, its list bodies lengthened by ``pad_items`` namespaces
    nobody may see (the filter has to drop every one)."""
    async def upstream(req):
        resp = await kube(req)
        if req.method == "GET" and req.path == "/api/v1/namespaces":
            doc = json.loads(resp.body)
            doc["items"] += [{"metadata": {"name": f"pad-{i:06d}"}}
                             for i in range(pad_items)]
            resp.body = json.dumps(doc).encode()
        return resp
    return upstream


async def _traced_list(env):
    """One traced ``GET /api/v1/namespaces`` as alice beside a coroutine
    that ticks on the same loop -> (response, spans of the trace, and for
    each run of the filter ``(on the loop's thread?, the ticker moved
    while it ran?)``). ``apply_filter`` is held, off the loop, until the
    ticker has moved three times or five seconds have gone; on the loop
    it would see no tick however long it waited, for the ticker needs
    the loop."""
    import threading
    import time

    from spicedb_kubeapi_proxy_tpu.authz import filterer
    from spicedb_kubeapi_proxy_tpu.obs.trace import tracer

    ticks = 0
    loop_thread = threading.get_ident()
    runs = []
    real = filterer.apply_filter

    def held(resp, allowed, input):
        t0, deadline = ticks, time.monotonic() + 5.0
        on_loop = threading.get_ident() == loop_thread
        while not on_loop and ticks < t0 + 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        runs.append((on_loop, ticks >= t0 + 3))
        return real(resp, allowed, input)

    async def ticker():
        nonlocal ticks
        while True:
            ticks += 1
            await asyncio.sleep(0)

    tracer.configure(sample=1.0)
    tracer.reset()
    filterer.apply_filter = held
    tick = asyncio.ensure_future(ticker())
    try:
        with tracer.start("request"):
            resp = await env.request("GET", "/api/v1/namespaces")
    finally:
        tick.cancel()
        filterer.apply_filter = real
        tracer.configure(sample=0.1)
    (trace,) = tracer.recent()
    tracer.reset()
    return resp, trace["spans"], runs


def _filter_hops(spans):
    """``executor_wait`` spans directly under the root: the body filter's
    hop to a worker (the prefilter's own lies under span ``prefilter``)."""
    root = next(s for s in spans if s["name"] == "request")
    return [s for s in spans if s["name"] == "executor_wait"
            and s["parent_id"] == root["span_id"]]


def test_long_list_is_filtered_off_the_event_loop():
    """While a long list is being filtered, a coroutine on the same loop
    goes on running: the filter is on a worker thread, one hop away."""
    from spicedb_kubeapi_proxy_tpu.authz.filterer import OFF_LOOP_BYTES

    async def go():
        env = Env()
        await env.create_ns("alpha")
        env.deps.upstream = _padded_upstream(env.kube, 12_000)
        resp, spans, runs = await _traced_list(env)
        assert resp.status == 200
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["alpha"]
        assert len(resp.body) < OFF_LOOP_BYTES  # what came from upstream
        # ... was longer: one filter, not on the loop, the ticker moving
        assert runs == [(False, True)], runs
        assert len(_filter_hops(spans)) == 1
        assert [s["name"] for s in spans].count("body_filter") == 1
    run(go())


def test_short_body_is_filtered_where_the_coroutine_runs():
    """A short body is not worth a hop: filtered on the loop, and the
    trace holds no ``executor_wait`` but the prefilter's."""
    async def go():
        env = Env()
        await env.create_ns("alpha")
        await env.create_ns("beta", user="bob")
        resp, spans, runs = await _traced_list(env)
        assert resp.status == 200
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["alpha"]
        assert runs == [(True, False)], runs
        assert _filter_hops(spans) == []
        assert [s["name"] for s in spans].count("executor_wait") == 1
        assert [s["name"] for s in spans].count("body_filter") == 1
    run(go())


def test_body_filter_counter_names_the_path():
    """``proxy_body_filter_total{path}``: a long list the native call
    decides reads ``fused``, a short body ``inline``, a long body the
    scanner refuses ``python`` — and that one still gets the Python
    path's answer; an upstream error is a ``passthrough``."""
    from spicedb_kubeapi_proxy_tpu import native
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    if not native.available():
        pytest.skip("native library unavailable")

    def counts():
        return {p: metrics.counter("proxy_body_filter_total", path=p).value
                for p in ("fused", "python", "inline", "passthrough")}

    async def listed(env):
        before = counts()
        resp = await env.request("GET", "/api/v1/namespaces")
        names = [o["metadata"]["name"]
                 for o in json.loads(resp.body).get("items") or []]
        return resp.status, names, {
            p: n - before[p] for p, n in counts().items() if n != before[p]}

    async def go():
        env = Env()
        await env.create_ns("alpha")
        assert await listed(env) == (200, ["alpha"], {"inline": 1})
        env.deps.upstream = _padded_upstream(env.kube, 12_000)
        assert await listed(env) == (200, ["alpha"], {"fused": 1})

        refused = _padded_upstream(env.kube, 12_000)

        async def numeric_name(req):
            resp = await refused(req)
            # a name that is no string: the scanner bails, and the
            # json.loads path keeps authority (and drops the item)
            resp.body = resp.body.replace(
                b'"items": [', b'"items": [{"metadata": {"name": 7}}, ', 1)
            return resp

        env.deps.upstream = numeric_name
        assert await listed(env) == (200, ["alpha"], {"python": 1})

        async def null_items(req):
            resp = await refused(req)
            doc = json.loads(resp.body)
            doc["spec"] = doc.pop("items")  # a long body, "items": null
            doc["items"] = None
            resp.body = json.dumps(doc).encode()
            return resp

        env.deps.upstream = null_items
        assert await listed(env) == (200, [], {"python": 1})

        async def unavailable(req):
            from spicedb_kubeapi_proxy_tpu.proxy.types import kube_status
            return kube_status(503, "apiserver is down")

        env.deps.upstream = unavailable
        status, _, moved = await listed(env)
        assert (status, moved) == (503, {"passthrough": 1})
    run(go())

"""Socket-level e2e: real HTTP through the proxy server to a real-HTTP fake
kube upstream — the whole handler chain, header authn, dual-write, list
filtering, watch streaming over chunked encoding, health and metrics.

Plays the role of the reference's embedded_integration_test.go +
proxy_test.go smoke paths, with FakeKube standing in for envtest.
"""

import asyncio
import json
import os

import pytest

from spicedb_kubeapi_proxy_tpu.proxy.options import Options
from spicedb_kubeapi_proxy_tpu.proxy.inmemory import InMemoryClient

from fake_kube import FakeKube, serve_upstream

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "deploy", "rules.yaml")) as _f:
    RULES = _f.read()


class HttpClient:
    """Tiny raw asyncio HTTP client for tests."""

    def __init__(self, port: int, user: str = "alice"):
        self.port = port
        self.user = user

    async def request(self, method: str, target: str, body=None,
                      stream: bool = False, extra_headers=()):
        reader, writer = await asyncio.open_connection("127.0.0.1", self.port)
        data = json.dumps(body).encode() if body is not None else b""
        headers = [f"{method} {target} HTTP/1.1",
                   f"Host: 127.0.0.1:{self.port}",
                   f"X-Remote-User: {self.user}",
                   "Content-Type: application/json",
                   f"Content-Length: {len(data)}",
                   *extra_headers,
                   "Connection: close", "", ""]
        writer.write("\r\n".join(headers).encode() + data)
        await writer.drain()
        status_line = await reader.readline()
        status = int(status_line.split(b" ")[1])
        resp_headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode().partition(":")
            resp_headers[k.strip().lower()] = v.strip()
        if stream:
            return status, resp_headers, (reader, writer)
        if "chunked" in resp_headers.get("transfer-encoding", ""):
            chunks = []
            while True:
                size = int((await reader.readline()).strip() or b"0", 16)
                if size == 0:
                    break
                chunks.append(await reader.readexactly(size))
                await reader.readline()
            bodyb = b"".join(chunks)
        else:
            n = int(resp_headers.get("content-length", 0))
            bodyb = await reader.readexactly(n) if n else await reader.read()
        writer.close()
        return status, resp_headers, bodyb

    async def read_chunk(self, reader):
        size = int((await reader.readline()).strip() or b"0", 16)
        if size == 0:
            return None
        data = await reader.readexactly(size)
        await reader.readline()
        return data


@pytest.fixture()
def env(tmp_path):
    return str(tmp_path / "dtx.sqlite")


def test_server_stop_drains_idle_watch_connections():
    """Graceful stop with an idle watch stream open must complete within
    the grace period: idle streaming handlers never write, so they only
    notice a dead peer on write — stop() cancels them after the grace
    instead of blocking in wait_closed() forever."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.proxy.demo import build

        cfg = build(port=0)
        await cfg.run()
        # open a watch as alice and read just the response headers,
        # leaving the (idle) stream open
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", cfg.server.port)
        writer.write(b"GET /api/v1/namespaces?watch=true HTTP/1.1\r\n"
                     b"Host: x\r\nX-Remote-User: alice\r\n\r\n")
        await writer.drain()
        line = await asyncio.wait_for(reader.readline(), timeout=10)
        assert b"200" in line
        t0 = asyncio.get_running_loop().time()
        await asyncio.wait_for(cfg.server.stop(grace=1.0), timeout=10)
        assert asyncio.get_running_loop().time() - t0 < 8
        writer.close()
        await cfg.workflow.shutdown()
    asyncio.run(go())


def test_demo_stack_end_to_end():
    """`make demo` wiring (proxy/demo.py): the self-contained stack must
    serve per-user-isolated lists, gets, and a dual-write create over
    real HTTP with nothing external."""
    async def go():
        from spicedb_kubeapi_proxy_tpu.proxy.demo import build

        cfg = build(port=0)
        await cfg.run()
        try:
            alice = HttpClient(cfg.server.port, "alice")
            carol = HttpClient(cfg.server.port, "carol")

            async def names(client):
                status, _, body = await client.request(
                    "GET", "/api/v1/namespaces")
                assert status == 200, body
                return [i["metadata"]["name"]
                        for i in json.loads(body)["items"]]

            assert await names(alice) == ["dev"]
            assert await names(carol) == ["prod"]
            # pods inherit namespace visibility via the arrow
            status, _, body = await alice.request("GET", "/api/v1/pods")
            assert status == 200
            assert [i["metadata"]["namespace"]
                    for i in json.loads(body)["items"]] == ["dev"]
            # cross-user get denied; own get allowed
            status, _, _ = await carol.request(
                "GET", "/api/v1/namespaces/dev")
            assert status in (401, 403, 404)
            status, _, _ = await alice.request(
                "GET", "/api/v1/namespaces/dev")
            assert status == 200
            # dual-write create lands in BOTH the upstream and the graph
            status, _, body = await alice.request(
                "POST", "/api/v1/namespaces",
                body={"metadata": {"name": "mine"}})
            assert status == 201, body
            assert await names(alice) == ["dev", "mine"]
            assert await names(carol) == ["prod"]
        finally:
            await cfg.server.stop()
            await cfg.workflow.shutdown()
    asyncio.run(go())


def test_proto_watch_over_real_server(env):
    """A protobuf watch through the FULL stack — real client socket ->
    proxy server -> HttpUpstream -> real-HTTP fake upstream: the stream
    content-type is the proto streaming variant and frames arrive
    length-prefixed, filtered, and byte-parseable (VERDICT r4 dir. 5)."""
    from spicedb_kubeapi_proxy_tpu.proxy import kubeproto

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
        ).complete()
        await cfg.run()
        try:
            alice = HttpClient(cfg.server.port, "alice")
            status, _, _ = await alice.request(
                "POST", "/api/v1/namespaces",
                body={"apiVersion": "v1", "kind": "Namespace",
                      "metadata": {"name": "proto-a"}})
            assert status == 201
            status, headers, (reader, writer) = await alice.request(
                "GET", "/api/v1/namespaces?watch=true", stream=True,
                extra_headers=[f"Accept: {kubeproto.CONTENT_TYPE}"])
            assert status == 200
            assert headers.get("content-type") == \
                kubeproto.WATCH_CONTENT_TYPE, headers
            buf = b""
            frame = None
            deadline = asyncio.get_running_loop().time() + 10
            while frame is None:
                assert asyncio.get_running_loop().time() < deadline
                chunk = await asyncio.wait_for(
                    alice.read_chunk(reader), timeout=5)
                assert chunk is not None
                buf += chunk
                if len(buf) >= 4:
                    n = int.from_bytes(buf[:4], "big")
                    if len(buf) >= 4 + n:
                        frame, buf = buf[:4 + n], buf[4 + n:]
            assert kubeproto.watch_frame_key(frame) == ("", "proto-a")
            typ, _ = kubeproto.decode_watch_event(frame[4:])
            assert typ == "ADDED"
            writer.close()
            fake.stop_watches()
        finally:
            await cfg.server.stop()
            await cfg.workflow.shutdown()
            upstream_server.close()
    asyncio.run(go())


def test_full_http_round_trips(env):
    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
            enable_debug_config=True,
        ).complete()
        await cfg.run()
        alice = HttpClient(cfg.server.port, "alice")
        bob = HttpClient(cfg.server.port, "bob")

        # health + metrics need no auth
        status, _, body = await HttpClient(cfg.server.port, "").request(
            "GET", "/readyz")
        assert (status, body) == (200, b"ok")

        # unauthenticated resource request -> 401
        noauth = HttpClient(cfg.server.port, "")
        status, _, _ = await noauth.request("GET", "/api/v1/namespaces")
        assert status == 401

        # dual-write create through real sockets
        status, _, body = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "team-a"}})
        assert status == 201, body
        assert json.loads(body)["metadata"]["name"] == "team-a"

        # per-user list isolation
        status, _, body = await alice.request("GET", "/api/v1/namespaces")
        assert [o["metadata"]["name"]
                for o in json.loads(body)["items"]] == ["team-a"]
        status, _, body = await bob.request("GET", "/api/v1/namespaces")
        assert json.loads(body)["items"] == []

        # single get isolation
        status, _, _ = await alice.request("GET", "/api/v1/namespaces/team-a")
        assert status == 200
        status, _, _ = await bob.request("GET", "/api/v1/namespaces/team-a")
        assert status == 403

        # watch: chunked streaming end-to-end
        status, headers, (reader, writer) = await alice.request(
            "GET", "/api/v1/namespaces?watch=true", stream=True)
        assert status == 200
        assert "chunked" in headers.get("transfer-encoding", "")
        first = await asyncio.wait_for(alice.read_chunk(reader), timeout=5)
        ev = json.loads(first)
        assert ev["type"] == "ADDED"
        assert ev["object"]["metadata"]["name"] == "team-a"
        # a new namespace created by alice shows up on the stream
        status2, _, _ = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "team-b"}})
        assert status2 == 201
        nxt = await asyncio.wait_for(alice.read_chunk(reader), timeout=5)
        assert json.loads(nxt)["object"]["metadata"]["name"] == "team-b"
        writer.close()

        # metrics rendered (proxy + engine families)
        status, _, body = await noauth.request("GET", "/metrics")
        assert status == 200 and b"proxy_requests_total" in body
        assert b"engine_checks_total" in body
        # sanitized config dump: flag-gated AND authenticated-only,
        # secrets redacted
        status, _, _ = await noauth.request("GET", "/debug/config")
        assert status == 401
        status, _, body = await alice.request("GET", "/debug/config")
        dump = json.loads(body)
        assert status == 200 and dump["engine_endpoint"]
        assert "upstream_token" in dump and dump["upstream_token"] is None

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_concurrent_lists_fuse_through_batch_window(env):
    """Fusing end to end with default flags: same-type list prefilters
    from different users that wait beside each other leave in one device
    dispatch (the grid fast path), and per-user isolation survives the
    fusion."""
    from fusing import hold, release, warm
    from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
        ).complete()
        await cfg.run()
        users = [f"user{i}" for i in range(6)]
        clients = {u: HttpClient(cfg.server.port, u) for u in users}
        for u in users:
            status, _, body = await clients[u].request(
                "POST", "/api/v1/namespaces",
                body={"apiVersion": "v1", "kind": "Namespace",
                      "metadata": {"name": f"ns-{u}"}})
            assert status == 201, body

        async def list_ns(u):
            status, _, body = await clients[u].request(
                "GET", "/api/v1/namespaces")
            assert status == 200
            return [o["metadata"]["name"]
                    for o in json.loads(body)["items"]]

        batcher = cfg.engine._batcher
        await asyncio.to_thread(warm, cfg.engine, "namespace")
        # held as by a dispatch being enqueued until the six lists'
        # prefilters wait beside each other: a count, not a burst that a
        # busy host may space out
        hold(batcher)
        batches0 = metrics.counter("engine_lookup_batches_total").value
        lookups0 = metrics.counter("engine_lookups_total").value
        burst = asyncio.gather(*(list_ns(u) for u in users))
        await asyncio.to_thread(release, batcher, len(users))
        for u, names in zip(users, await burst):
            assert names == [f"ns-{u}"], (u, names)
        assert metrics.counter(
            "engine_lookup_batches_total").value - batches0 == 1
        assert metrics.counter(
            "engine_lookups_total").value - lookups0 == len(users)

        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_inmemory_client(env):
    async def go():
        fake = FakeKube()
        cfg = Options(
            rule_content=RULES,
            upstream=fake,
            workflow_database_path=env,
        ).complete()
        await cfg.workflow.resume_pending()
        alice = InMemoryClient(cfg.server.handle, user="alice")
        resp = await alice.post("/api/v1/namespaces", {
            "apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": "mem"}})
        assert resp.status == 201
        resp = await alice.get("/api/v1/namespaces")
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["mem"]
        # /debug/config is flag-gated: default options serve 404 even to
        # an authenticated user
        resp = await alice.get("/debug/config")
        assert resp.status == 404
        await cfg.workflow.shutdown()
    asyncio.run(go())


def test_deploy_files_end_to_end(env):
    """The shipped deploy/ rule set + bootstrap schema serve a full
    create -> isolate -> delete cycle (namespaces and namespaced pods)."""
    async def go():
        fake = FakeKube()
        import os
        deploy = os.path.join(os.path.dirname(__file__), "..", "deploy")
        cfg = Options(
            rule_files=[os.path.join(deploy, "rules.yaml")],
            bootstrap_files=[os.path.join(deploy, "bootstrap.yaml")],
            upstream=fake,
            workflow_database_path=env,
        ).complete()
        await cfg.workflow.resume_pending()
        alice = InMemoryClient(cfg.server.handle, user="alice")
        bob = InMemoryClient(cfg.server.handle, user="bob")

        resp = await alice.post("/api/v1/namespaces", {
            "apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": "team-a"}})
        assert resp.status == 201
        # pods in alice's namespace: create, list isolation, delete
        resp = await alice.post("/api/v1/namespaces/team-a/pods", {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "api", "namespace": "team-a"}})
        assert resp.status == 201, resp.body
        resp = await bob.post("/api/v1/namespaces/team-a/pods", {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "intruder", "namespace": "team-a"}})
        assert resp.status == 403
        resp = await alice.get("/api/v1/pods")
        assert [o["metadata"]["name"]
                for o in json.loads(resp.body)["items"]] == ["api"]
        resp = await bob.get("/api/v1/pods")
        assert json.loads(resp.body)["items"] == []
        resp = await alice.delete("/api/v1/namespaces/team-a/pods/api")
        assert resp.status == 200, resp.body
        # deleteByFilter cleaned up every pod relationship
        from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter
        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="pod"))
        await cfg.workflow.shutdown()
    asyncio.run(go())


def test_options_validation(env):
    from spicedb_kubeapi_proxy_tpu.proxy.options import Options, OptionsError
    with pytest.raises(OptionsError, match="rule file"):
        Options(upstream_url="http://x").validate()
    with pytest.raises(OptionsError, match="upstream"):
        Options(rule_content=RULES).validate()
    with pytest.raises(OptionsError, match="engine endpoint"):
        Options(rule_content=RULES, upstream_url="http://x",
                engine_endpoint="grpc://remote:50051").validate()


def test_token_file_authentication(env, tmp_path):
    """kube static-token-file Bearer authn: valid tokens map to
    user/groups, invalid tokens 401 without falling back to headers
    (reference wires kube's token-file authenticator, authn.go:40-47)."""
    tokens = tmp_path / "tokens.csv"
    tokens.write_text(
        "# comment line\n"
        'tok-alice,alice,u1,"team-alpha,devs"\n'
        "tok-bob,bob,u2\n")

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
            token_auth_file=str(tokens),
        ).complete()
        await cfg.run()

        class TokenClient(HttpClient):
            def __init__(self, port, token):
                super().__init__(port, user="")
                self.token = token

            async def request(self, method, target, body=None, stream=False):
                # replace the X-Remote-User header with a Bearer token
                import json as _json
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", self.port)
                data = _json.dumps(body).encode() if body is not None else b""
                headers = [f"{method} {target} HTTP/1.1",
                           f"Host: 127.0.0.1:{self.port}",
                           f"Authorization: Bearer {self.token}",
                           "Content-Type: application/json",
                           f"Content-Length: {len(data)}",
                           "Connection: close", "", ""]
                writer.write("\r\n".join(headers).encode() + data)
                await writer.drain()
                status_line = await reader.readline()
                status = int(status_line.split(b" ")[1])
                hdrs = {}
                while True:
                    line = await reader.readline()
                    if line in (b"\r\n", b"\n", b""):
                        break
                    k, _, v = line.decode().partition(":")
                    hdrs[k.strip().lower()] = v.strip()
                n = int(hdrs.get("content-length", 0))
                out = await reader.readexactly(n) if n else b""
                writer.close()
                return status, hdrs, out

        alice = TokenClient(cfg.server.port, "tok-alice")
        bob = TokenClient(cfg.server.port, "tok-bob")
        wrong = TokenClient(cfg.server.port, "nope")

        status, _, body = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "tok-ns"}})
        assert status == 201, body
        status, _, body = await alice.request("GET", "/api/v1/namespaces")
        assert [o["metadata"]["name"]
                for o in json.loads(body)["items"]] == ["tok-ns"]
        status, _, body = await bob.request("GET", "/api/v1/namespaces")
        assert json.loads(body)["items"] == []
        # invalid bearer: 401, not a fall-through to anonymous/headers
        status, _, _ = await wrong.request("GET", "/api/v1/namespaces")
        assert status == 401
        # non-ASCII bearer: still a clean 401, never a 500
        weird = TokenClient(cfg.server.port, "caf\xe9")
        status, _, _ = await weird.request("GET", "/api/v1/namespaces")
        assert status == 401
        # the uid column reaches the first-class UserInfo field rules
        # template on ({{user.uid}})
        from spicedb_kubeapi_proxy_tpu.proxy.authn import (
            TokenFileAuthenticator,
        )
        u = TokenFileAuthenticator(str(tokens)).authenticate_token(
            "tok-alice")
        assert (u.name, u.uid, u.groups) == (
            "alice", "u1", ["team-alpha", "devs"])

        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_concurrency_soak_cross_feature(env):
    """Cross-feature soak: concurrent dual-writes (creates + deletes),
    batched list prefilters, live watch streams, and the hub's recompute
    machinery all churning against one engine for a few hundred
    operations. Invariants at quiesce (reference proxy_test.go:106-111):
    zero leftover lock tuples, per-user list isolation equals the
    surviving set, and every user's watch saw their own creates."""
    from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
        ).complete()
        await cfg.run()
        users = [f"soak{i}" for i in range(4)]
        clients = {u: HttpClient(cfg.server.port, u) for u in users}
        per_user = 12
        survivors = {u: set() for u in users}
        watch_seen = {u: set() for u in users}

        async def watcher(u):
            c = HttpClient(cfg.server.port, u)
            status, _, (reader, writer) = await c.request(
                "GET", "/api/v1/namespaces?watch=true", stream=True)
            assert status == 200
            try:
                while True:
                    chunk = await asyncio.wait_for(c.read_chunk(reader),
                                                   timeout=20)
                    if chunk is None:
                        break
                    ev = json.loads(chunk)
                    if ev["type"] in ("ADDED", "MODIFIED"):
                        watch_seen[u].add(ev["object"]["metadata"]["name"])
            except (asyncio.TimeoutError, asyncio.IncompleteReadError):
                pass
            finally:
                writer.close()

        watch_tasks = [asyncio.create_task(watcher(u)) for u in users]
        await asyncio.sleep(0.2)  # watchers registered before churn

        async def churn(u):
            c = clients[u]
            for i in range(per_user):
                name = f"ns-{u}-{i}"
                status, _, body = await c.request(
                    "POST", "/api/v1/namespaces",
                    body={"apiVersion": "v1", "kind": "Namespace",
                          "metadata": {"name": name}})
                assert status == 201, (u, i, body)
                survivors[u].add(name)
                # interleave lists (batched prefilters) with the writes;
                # 401 here is the prefilter-wait timeout (reference
                # responsefilterer.go:44 -> 401 body), which a saturated
                # host can legitimately hit — isolation is only checkable
                # on completed lists
                status, _, body = await c.request(
                    "GET", "/api/v1/namespaces")
                assert status in (200, 401), (u, status)
                if status == 200:
                    names = {o["metadata"]["name"]
                             for o in json.loads(body)["items"]}
                    assert names <= survivors[u], (u, names - survivors[u])
                if i % 3 == 2:
                    victim = f"ns-{u}-{i - 1}"
                    status, _, _ = await c.request(
                        "DELETE", f"/api/v1/namespaces/{victim}")
                    assert status in (200, 202), (u, victim, status)
                    survivors[u].discard(victim)

        await asyncio.gather(*(churn(u) for u in users))

        # quiesce: poll until every user's list settles on the surviving
        # set (deletes, hub recomputes, and watch frames drain at
        # host-load-dependent speed; a fixed sleep flakes under contention)
        async def settled(u):
            status, _, body = await clients[u].request(
                "GET", "/api/v1/namespaces")
            if status != 200:  # prefilter-wait timeout under load: retry
                return None
            return {o["metadata"]["name"]
                    for o in json.loads(body)["items"]}

        deadline = asyncio.get_running_loop().time() + 20
        last = {}
        while True:
            last = {u: await settled(u) for u in users}
            if all(last[u] is not None and last[u] == survivors[u]
                   for u in users):
                break
            if asyncio.get_running_loop().time() > deadline:
                raise AssertionError(
                    {u: ("prefilter timeout" if last[u] is None
                         else last[u] ^ survivors[u])
                     for u in users if last[u] != survivors[u]})
            await asyncio.sleep(0.25)

        # the reference's invariant: no leftover lock tuples
        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))

        # watch frames drain asynchronously of the list path: wait until
        # every watcher has seen its surviving creates before cancelling
        deadline = asyncio.get_running_loop().time() + 20
        while not all(survivors[u] <= watch_seen[u] for u in users):
            if asyncio.get_running_loop().time() > deadline:
                break  # the assertions below report the gap
            await asyncio.sleep(0.25)

        for t in watch_tasks:
            t.cancel()
        await asyncio.gather(*watch_tasks, return_exceptions=True)
        for u in users:
            # created-then-quickly-deleted objects may legitimately never
            # surface (a buffered frame is dropped when the deny beats the
            # allow — reference responsefilterer.go:628-710); everything
            # that SURVIVED must have been seen, and nothing foreign
            missed = survivors[u] - watch_seen[u]
            assert not missed, (u, missed)
            created = {f"ns-{u}-{i}" for i in range(per_user)}
            foreign = watch_seen[u] - created
            assert not foreign, (u, foreign)

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


@pytest.mark.parametrize("lock_mode", ["Pessimistic", "Optimistic"])
def test_chaos_storm_transient_kube_failures(env, lock_mode):
    """Chaos leg 1 — transient upstream faults under concurrent churn:
    kube TRANSPORT failures (connection killed mid-request) injected
    while three users create namespaces. The workflow retry loop
    (<=5 attempts, backoff — reference workflow.go:211-222 retries only
    transport errors) must absorb every burst shorter than the budget;
    every create must be fully atomic per name (response == upstream ==
    graph == list visibility), and no lock tuples survive (the crash
    matrix run as a storm, reference proxy_test.go:106-111). A definitive
    kube 500 RESPONSE, by contrast, is a rejection: rolled back without
    retry (workflow.go:243-245) — asserted deterministically at the end."""
    from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            lock_mode=lock_mode,
            bind_port=0,
            # this storm orchestrates its own fault budgets; concurrent
            # bursts can exceed the breaker threshold back-to-back, and a
            # tripped breaker would fail ops the workflow budget should
            # absorb (the breaker has dedicated coverage in test_chaos.py)
            breaker_failure_threshold=100,
        ).complete()
        await cfg.run()
        users = [f"storm{i}" for i in range(3)]
        clients = {u: HttpClient(cfg.server.port, u) for u in users}
        status_by_name: dict[str, tuple] = {}

        async def churn(u, idx):
            c = clients[u]
            for i in range(8):
                if (i + idx) % 3 == 1:
                    # burst of killed connections, below the 5-attempt
                    # budget; concurrent writes share the fault queue, so
                    # which op eats how many faults is nondeterministic
                    # by design
                    fake.fail_next(
                        2, exception=ConnectionResetError("injected"))
                name = f"st-{u}-{i}"
                status, _, _ = await c.request(
                    "POST", "/api/v1/namespaces",
                    body={"apiVersion": "v1", "kind": "Namespace",
                          "metadata": {"name": name}})
                status_by_name[name] = (u, status)

        await asyncio.gather(*(churn(u, i) for i, u in enumerate(users)))

        deadline = asyncio.get_running_loop().time() + 25
        while (cfg.engine.store.exists(RelationshipFilter(
                resource_type="lock"))
               and asyncio.get_running_loop().time() < deadline):
            await asyncio.sleep(0.25)
        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))

        lists = {}
        for u in users:
            status, _, body = await clients[u].request(
                "GET", "/api/v1/namespaces")
            assert status == 200
            lists[u] = {o["metadata"]["name"]
                        for o in json.loads(body)["items"]}

        landed = 0
        for name, (u, status) in status_by_name.items():
            in_upstream = ("namespaces", "", name) in fake.objects
            in_graph = cfg.engine.store.exists(RelationshipFilter(
                resource_type="namespace", resource_id=name))
            visible = name in lists[u]
            if status == 201:
                assert in_upstream and in_graph and visible, (
                    name, status, in_upstream, in_graph, visible)
                landed += 1
            else:
                assert not in_upstream and not in_graph and not visible, (
                    name, status, in_upstream, in_graph, visible)
        # bursts stay under the retry budget: everything must have landed
        assert landed == len(status_by_name), (landed, len(status_by_name))

        # a definitive 500 RESPONSE (nothing else in flight): rejection,
        # rolled back without retry — reference workflow.go:243-245
        fake.fail_next(1, status=500)
        status, _, _ = await clients[users[0]].request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "st-rejected"}})
        assert status == 500
        assert ("namespaces", "", "st-rejected") not in fake.objects
        assert not cfg.engine.store.exists(RelationshipFilter(
            resource_type="namespace", resource_id="st-rejected"))
        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_chaos_crash_mid_dual_write_recovers_on_resume(env):
    """Chaos leg 2 — a failpoint 'process death' mid-dual-write at the
    HTTP layer: the client sees the dual-write timeout, the instance
    stays suspended with its lock held (exactly a crashed process), and
    resume_pending() — what cfg.run() does at boot — replays the event
    log, completes the kube write, and releases the lock: the create
    eventually lands even though its HTTP response was an error
    (at-least-once durable dual-write, reference workflow.go + the e2e
    crash matrix, run through the full server)."""
    from spicedb_kubeapi_proxy_tpu.authz import middleware
    from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter
    from spicedb_kubeapi_proxy_tpu.utils.failpoints import failpoints

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
        ).complete()
        await cfg.run()
        alice = HttpClient(cfg.server.port, "alice")

        # don't sit out the full 30s dual-write wait for the staged crash
        saved_timeout = middleware.WORKFLOW_RESULT_TIMEOUT
        middleware.WORKFLOW_RESULT_TIMEOUT = 3.0
        failpoints.enable("panicKubeWrite", budget=1)
        status, _, body = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "crashy"}})
        middleware.WORKFLOW_RESULT_TIMEOUT = saved_timeout
        # the workflow is suspended (simulated dead process): the client
        # saw a timeout and the half-applied state is held under the lock
        assert status >= 500, (status, body)
        assert cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))
        assert ("namespaces", "", "crashy") not in fake.objects
        failpoints.disable_all()

        # "restart": resume from the event log, as cfg.run() does at boot
        resumed = await cfg.workflow.resume_pending()
        assert resumed, "the suspended instance must be found"
        deadline = asyncio.get_running_loop().time() + 20
        while (cfg.engine.store.exists(RelationshipFilter(
                resource_type="lock"))
               and asyncio.get_running_loop().time() < deadline):
            await asyncio.sleep(0.25)
        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))
        assert ("namespaces", "", "crashy") in fake.objects
        assert cfg.engine.store.exists(RelationshipFilter(
            resource_type="namespace", resource_id="crashy"))
        status, _, body = await alice.request("GET", "/api/v1/namespaces")
        assert status == 200
        assert "crashy" in {o["metadata"]["name"]
                            for o in json.loads(body)["items"]}

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_chaos_crash_storm_converges_after_resumes(env):
    """Chaos leg 3 — a storm of simulated process deaths: failpoints at
    BOTH side-effect edges (SpiceDB write, kube write) strike repeatedly
    while two users create namespaces concurrently. Whichever in-flight
    workflow eats a fault suspends exactly like a crashed process (its
    client sees an error); repeated resume_pending() cycles — process
    restarts — must drain every suspended instance to completion: every
    create eventually lands atomically, locks reach zero, and the event
    logs replay deterministically (reference e2e crash matrix as a storm,
    proxy_test.go:650-830)."""
    from spicedb_kubeapi_proxy_tpu.authz import middleware
    from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter
    from spicedb_kubeapi_proxy_tpu.utils.failpoints import failpoints

    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
        ).complete()
        await cfg.run()
        users = ["stormA", "stormB"]
        clients = {u: HttpClient(cfg.server.port, u) for u in users}

        saved_timeout = middleware.WORKFLOW_RESULT_TIMEOUT
        middleware.WORKFLOW_RESULT_TIMEOUT = 2.0
        try:
            async def churn(u, idx):
                c = clients[u]
                for i in range(6):
                    if (i + idx) % 3 == 0:
                        failpoints.enable("panicKubeWrite", budget=1)
                    elif (i + idx) % 3 == 1:
                        failpoints.enable("panicWriteSpiceDB", budget=1)
                    await c.request(
                        "POST", "/api/v1/namespaces",
                        body={"apiVersion": "v1", "kind": "Namespace",
                              "metadata": {"name": f"cr-{u}-{i}"}})

            await asyncio.gather(*(churn(u, i)
                                   for i, u in enumerate(users)))
        finally:
            middleware.WORKFLOW_RESULT_TIMEOUT = saved_timeout
            failpoints.disable_all()

        # repeated "restarts" until every suspended instance drains
        deadline = asyncio.get_running_loop().time() + 30
        while cfg.workflow.pending_count():
            assert asyncio.get_running_loop().time() < deadline, \
                f"{cfg.workflow.pending_count()} instances never drained"
            await cfg.workflow.resume_pending()
            await asyncio.sleep(0.25)

        assert not cfg.engine.store.exists(
            RelationshipFilter(resource_type="lock"))
        lists = {}
        for u in users:
            status, _, body = await clients[u].request(
                "GET", "/api/v1/namespaces")
            assert status == 200
            lists[u] = {o["metadata"]["name"]
                        for o in json.loads(body)["items"]}
        for u in users:
            for i in range(6):
                name = f"cr-{u}-{i}"
                in_upstream = ("namespaces", "", name) in fake.objects
                in_graph = cfg.engine.store.exists(RelationshipFilter(
                    resource_type="namespace", resource_id=name))
                visible = name in lists[u]
                # faults are one-shot: after enough restarts every create
                # must have landed everywhere
                assert in_upstream and in_graph and visible, (
                    name, in_upstream, in_graph, visible)

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())


def test_upstream_dying_mid_request_surfaces_connection_error(env):
    """An upstream that closes the socket before sending a status line
    must surface as a connection error (which retry paths absorb), never
    a bare IndexError from the status-line parse — found by a soak where
    killed-connection faults printed IndexError tracebacks."""
    async def go():
        fake = FakeKube()
        upstream_server, upstream_port = await serve_upstream(fake)
        cfg = Options(
            rule_content=RULES,
            upstream_url=f"http://127.0.0.1:{upstream_port}",
            workflow_database_path=env,
            bind_port=0,
            # 8 consecutive injected transport failures below; keep the
            # breaker out of the way (dedicated coverage in test_chaos.py)
            breaker_failure_threshold=100,
        ).complete()
        await cfg.run()
        alice = HttpClient(cfg.server.port, "alice")
        # a dual-write whose kube writes ALL die mid-request: the workflow
        # retries then reports cleanly (5xx), no IndexError anywhere.
        # The transport layer never retries POSTs, so the workflow budget
        # consumes exactly the 6 faults (5+1 attempts) and nothing leaks
        # into the later requests
        fake.fail_next(6, exception=ConnectionResetError("mid-request"))
        status, _, body = await alice.request(
            "POST", "/api/v1/namespaces",
            body={"apiVersion": "v1", "kind": "Namespace",
                  "metadata": {"name": "dying"}})
        assert status >= 500, (status, body)
        assert b"IndexError" not in body
        # ONE killed connection on a read: absorbed by the transport
        # layer's idempotent-GET retry (utils/resilience.py)
        fake.fail_next(1, exception=ConnectionResetError("mid-request"))
        status, _, body = await alice.request("GET", "/api/v1/namespaces")
        assert status == 200
        # a read whose retry ALSO dies: clean 5xx, no IndexError
        fake.fail_next(2, exception=ConnectionResetError("mid-request"))
        status, _, body = await alice.request("GET", "/api/v1/namespaces")
        assert status >= 500
        assert b"IndexError" not in body
        # and the path recovers once the upstream behaves
        status, _, _ = await alice.request("GET", "/api/v1/namespaces")
        assert status == 200

        fake.stop_watches()
        await cfg.server.stop()
        await cfg.workflow.shutdown()
        upstream_server.close()
    asyncio.run(go())

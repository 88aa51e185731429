"""Multi-host (multi-process) execution of the sharded engine: the full
query path — bulk load, dense blocks, cross-process collective joins,
incremental writes — over TWO OS processes whose collectives ride Gloo
(the CPU stand-in for DCN). Mirrors SURVEY §2.5's requirement that the
distributed backend scale to multi-host like the reference's gRPC tier.

The worker script lives in this file (__MULTIHOST_WORKER__ guard) and is
re-invoked per process, because jax.distributed can only be initialized
once per process and must happen before the backend comes up.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import os, sys
proc, n, port, repo = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, repo)
import jax
jax.config.update("jax_platforms", "cpu")
from spicedb_kubeapi_proxy_tpu.parallel.multihost import init_distributed
init_distributed(f"127.0.0.1:{port},{n},{proc}")
import numpy as np
from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.parallel import make_mesh

devs = jax.devices()
assert len(devs) == 2 * n, (len(devs), n)
mesh = make_mesh(len(devs), devices=devs)
# identical store on every process (the SPMD contract; serving mirrors
# writes the same way)
rng = np.random.default_rng(7)
rels = [f"namespace:n{i}#creator@user:u{int(rng.integers(50))}"
        for i in range(300)]
rels += [f"pod:n{i%30}/p{i}#namespace@namespace:n{i%30}"
         for i in range(200)]
em = Engine(mesh=mesh)
em.write_relationships([WriteOp("touch", parse_relationship(r))
                        for r in rels])
e1 = Engine()
e1.write_relationships([WriteOp("touch", parse_relationship(r))
                        for r in rels])
items = [CheckItem("namespace", f"n{int(i)}", "view", "user", f"u{int(u)}")
         for i, u in zip(rng.integers(300, size=32),
                         rng.integers(50, size=32))]
assert em.check_bulk(items) == e1.check_bulk(items)
lk = em.lookup_resources("namespace", "view", "user", "u3")
assert sorted(lk) == sorted(
    e1.lookup_resources("namespace", "view", "user", "u3"))
# incremental write over the multi-host mesh, re-queried
for eng in (em, e1):
    eng.write_relationships([WriteOp("touch", parse_relationship(
        "namespace:n1#viewer@user:u49"))])
assert em.check_bulk(
    [CheckItem("namespace", "n1", "view", "user", "u49")]) == [True]
print(f"proc {proc}: MULTIHOST PARITY OK mesh={dict(mesh.shape)}",
      flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_sharded_engine_parity(tmp_path):
    """2 processes x 2 virtual devices: one global ('data','graph') mesh,
    cross-process collectives over Gloo, engine parity vs single-device
    incl. an incremental write."""
    script = tmp_path / "mh_worker.py"
    script.write_text(WORKER)
    port = _free_port()
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # the workers pin their own platform/device config; scrub any
    # conftest leakage that would fight it
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), "2", str(port), repo_root],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=repo_root)
        for i in range(2)
    ]
    # one SHARED deadline for both workers (sequential communicate()
    # timeouts would stack), and always drain stdout after a kill so a
    # flake leaves diagnostics instead of zombies + empty output
    import time as _time

    deadline = _time.monotonic() + 240
    timed_out = False
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - _time.monotonic()))
        except subprocess.TimeoutExpired:
            timed_out = True
            p.kill()
    outs = [p.communicate()[0] for p in procs]
    if timed_out:
        pytest.fail("multihost workers timed out; outputs:\n"
                    + "\n---\n".join(o[-2000:] for o in outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-2000:]}"
        assert "MULTIHOST PARITY OK" in out, out[-2000:]


SERVE_WORKER = r"""
import os, sys
role, port_coord, port_tcp, repo = (sys.argv[1], sys.argv[2], sys.argv[3],
                                    sys.argv[4])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, repo)
import jax
jax.config.update("jax_platforms", "cpu")
from spicedb_kubeapi_proxy_tpu.engine.remote import main

pid = "0" if role == "leader" else "1"
argv = ["--distributed", f"127.0.0.1:{port_coord},2,{pid}",
        "--engine-mesh", "auto", "--token", "mh-tok",
        "--engine-insecure"]  # loopback-only test fixture
if role == "leader":
    argv += ["--bind-port", port_tcp]
    print("LEADER STARTING", flush=True)
else:
    argv += ["--mirror-leader", f"127.0.0.1:{port_tcp}",
             "--bind-port", "0"]
    # the follower says what it has applied, so the test can wait for it
    # (a follower serves no port of its own to ask)
    from spicedb_kubeapi_proxy_tpu.parallel import multihost as _mh
    _apply = _mh.apply_mirror_frame

    def _reporting(engine, frame, blob=None):
        try:
            _apply(engine, frame, blob)
        finally:
            print(f"APPLIED method={frame['method']} "
                  f"revision={engine.revision}", flush=True)

    _mh.apply_mirror_frame = _reporting
    print("FOLLOWER STARTING", flush=True)
sys.exit(main(argv))
"""


def test_multihost_serving_leader_follower(tmp_path):
    """Full multi-host SERVING: the engine-host CLI as leader (process 0,
    serving TCP, MirroredEngine) + follower (process 1, replaying the
    mirror stream); a real client drives writes, bulk checks, and mask
    lookups whose collectives span both processes. Every query that
    spans both is sent once the follower has applied the writes before
    it (its own report, not a sleep): the leader then never sits in a
    collective while a busy host is still replaying the follower's
    writes."""
    import threading
    import time

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
    from spicedb_kubeapi_proxy_tpu.engine.remote import RemoteEngine
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mh_serve_worker.py"
    script.write_text(SERVE_WORKER)
    port_coord, port_tcp = _free_port(), _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs, readers, outs = [], [], [[], []]
    cond = threading.Condition()
    applied = {"writes": 0, "revision": 0}

    def read(p, lines):
        for line in p.stdout:
            with cond:
                lines.append(line)
                if line.startswith("APPLIED "):
                    f = dict(kv.split("=", 1) for kv in line.split()[1:])
                    applied["revision"] = int(f["revision"])
                    applied["writes"] += f["method"] == "write_relationships"
                cond.notify_all()

    def follower_applied(writes):
        """Block until the follower has replayed ``writes`` write frames
        and stands at the leader's revision."""
        want = client.revision
        with cond:
            cond.wait_for(
                lambda: (applied["writes"] >= writes
                         and applied["revision"] >= want)
                or procs[1].poll() is not None, timeout=300)
            assert applied["writes"] >= writes \
                and applied["revision"] == want, \
                (applied, want, "".join(outs[1])[-2000:])

    client = None
    try:
        for i, role in enumerate(("leader", "follower")):
            procs.append(subprocess.Popen(
                [sys.executable, str(script), role, str(port_coord),
                 str(port_tcp), repo_root],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo_root))
            readers.append(threading.Thread(
                target=read, args=(procs[i], outs[i]), daemon=True))
            readers[i].start()
        # wait for the leader's TCP port to accept
        deadline = time.monotonic() + 120
        while True:
            try:
                probe = socket.create_connection(
                    ("127.0.0.1", port_tcp), timeout=1)
                probe.close()
                break
            except OSError:
                for i, p in enumerate(procs):
                    assert p.poll() is None, "".join(outs[i])[-2000:]
                assert time.monotonic() < deadline, "leader never bound"
                time.sleep(0.25)
        client = RemoteEngine("127.0.0.1", port_tcp, token="mh-tok")
        rels = [f"namespace:n{i}#creator@user:u{i % 7}" for i in range(40)]
        client.write_relationships(
            [WriteOp("touch", parse_relationship(r)) for r in rels])
        # reference truth from a local single-device engine
        ref = Engine()
        ref.write_relationships(
            [WriteOp("touch", parse_relationship(r)) for r in rels])
        items = [CheckItem("namespace", f"n{i}", "view", "user",
                           f"u{i % 5}") for i in range(25)]
        follower_applied(writes=1)
        assert client.check_bulk(items) == ref.check_bulk(items)
        assert sorted(client.lookup_resources(
            "namespace", "view", "user", "u3")) == \
            sorted(ref.lookup_resources("namespace", "view", "user", "u3"))
        # a second write + re-query: the incremental path in lockstep
        for eng in (client, ref):
            eng.write_relationships([WriteOp("touch", parse_relationship(
                "namespace:n1#viewer@user:u6"))])
        follower_applied(writes=2)
        assert client.check_bulk(
            [CheckItem("namespace", "n1", "view", "user", "u6")]) == [True]
        # a DETERMINISTICALLY-FAILING write (bad precondition) must fail
        # identically on leader and follower — the follower keeps
        # replaying rather than dying and hanging the next collective
        from spicedb_kubeapi_proxy_tpu.engine import RelationshipFilter
        from spicedb_kubeapi_proxy_tpu.engine.store import (
            Precondition,
            PreconditionFailed,
        )

        try:
            client.write_relationships(
                [WriteOp("touch", parse_relationship(
                    "namespace:nope#viewer@user:u0"))],
                [Precondition(RelationshipFilter(
                    resource_type="ghost-type"), must_exist=True)])
            raise AssertionError("precondition should have failed")
        except PreconditionFailed:
            pass
        # the follower replayed the failing frame too and moved no
        # further than the leader: the set is still alive and consistent
        follower_applied(writes=3)
        assert client.check_bulk(
            [CheckItem("namespace", "n1", "view", "user", "u6")]) == [True]
    finally:
        if client is not None:
            client.close()
        for p in procs:
            p.terminate()
        deadline = time.monotonic() + 20
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        for r in readers:
            r.join(10)
    for role, lines in zip(("leader", "follower"), outs):
        out = "".join(lines)
        assert "STARTING" in out, (role, out[-1500:])
        assert "Traceback" not in out, (role, out[-2500:])


def test_mirror_check_item_codec_round_trip():
    """The compact mirror codec must be injective for ANY client-supplied
    field content (review findings: separator-based encoding let crafted
    ids kill or desync followers) and keep '' distinct from None — the
    engine groups device dispatches by subject key, so a lossy codec
    desyncs SPMD dispatch shapes."""
    from spicedb_kubeapi_proxy_tpu.engine import CheckItem
    from spicedb_kubeapi_proxy_tpu.parallel.multihost import (
        MultiHostError,
        decode_check_items,
        encode_check_items,
        normalize_check_item,
    )

    items = [
        CheckItem("pod", "ns/p1", "view", "user", "alice", None),
        CheckItem("pod", "a\x1fb", "view", "user", "c\x1ed", None),
        CheckItem("group", "g\nx", "member", "group", "inner", "member"),
        CheckItem("ns", "", "view", "user", "u", ""),  # '' != None
        CheckItem("t", "名前", "view", "user", "ünïcode", None),
    ]
    got = decode_check_items(encode_check_items(items))
    assert got == items
    # '' and None subject relations survive distinctly
    assert got[3].subject_relation == "" and got[0].subject_relation is None
    # non-str fields (legal JSON from a token-holding client) normalize to
    # the SAME value the leader executes
    n = normalize_check_item(CheckItem("pod", 123, "view", "user", 7, None))
    assert n.resource_id == "123" and n.subject_id == "7"
    assert decode_check_items(encode_check_items([n])) == [n]
    # malformed payloads fail loudly, not with a silent partial batch
    blob = encode_check_items(items)
    import pytest as _pytest

    with _pytest.raises(MultiHostError):
        decode_check_items(blob[:-3])


def test_multihost_follower_death_blocks_leader_restart_heals():
    """The documented failure model (parallel/multihost.py): SPMD is
    all-or-nothing — with a dead follower the leader's next device
    collective fails or blocks depending on the transport (Gloo errors
    fast; DCN may stall), but NEVER answers, and the leader process
    survives; restarting the process set as a unit heals serving on the
    same endpoint."""
    import time

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo_root, ".pytest-mh-death-worker.py")
    with open(script, "w") as f:
        f.write(SERVE_WORKER)
    port_tcp = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)

    def boot_pair(port_coord):
        procs = []
        for role in ("leader", "follower"):
            procs.append(subprocess.Popen(
                [sys.executable, script, role, str(port_coord),
                 str(port_tcp), repo_root],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo_root))
        try:
            deadline = time.monotonic() + 120
            while True:
                try:
                    probe = socket.create_connection(
                        ("127.0.0.1", port_tcp), timeout=1)
                    probe.close()
                    return procs
                except OSError:
                    for p in procs:
                        assert p.poll() is None, p.communicate()[0][-2000:]
                    assert time.monotonic() < deadline, "leader never bound"
                    time.sleep(0.25)
        except BaseException:
            # boot failed: reap HERE — a surviving leader would hold
            # port_tcp and poison the restart phase
            reap(procs)
            raise

    def reap(procs):
        for p in procs:
            p.terminate()
        deadline = time.monotonic() + 20
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
            p.communicate()

    try:
        _death_and_restart_phases(boot_pair, reap, port_tcp)
    finally:
        if os.path.exists(script):
            os.unlink(script)


def _death_and_restart_phases(boot_pair, reap, port_tcp):
    import threading

    from spicedb_kubeapi_proxy_tpu.engine import CheckItem, WriteOp
    from spicedb_kubeapi_proxy_tpu.engine.remote import RemoteEngine
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship

    procs = boot_pair(_free_port())
    client = None
    try:
        client = RemoteEngine("127.0.0.1", port_tcp, token="mh-tok",
                              timeout=30.0)
        client.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:alive#creator@user:u1"))])
        item = CheckItem("namespace", "alive", "view", "user", "u1")
        assert client.check_bulk([item]) == [True]

        # kill the follower: the leader's NEXT collective must fail or
        # block — never ANSWER — and the leader process must survive
        procs[1].kill()
        procs[1].wait(timeout=10)
        result: dict = {}

        def doomed_check():
            c2 = RemoteEngine("127.0.0.1", port_tcp, token="mh-tok",
                              timeout=60.0)
            try:
                result["got"] = c2.check_bulk([item])
            except Exception as e:  # noqa: BLE001
                result["err"] = e
            finally:
                c2.close()

        t = threading.Thread(target=doomed_check, daemon=True)
        t.start()
        t.join(20.0)
        if t.is_alive():
            pass  # blocked: the DCN-like stall mode
        else:
            # errored: the Gloo fast-fail mode — still no answer
            assert "got" not in result, \
                f"leader ANSWERED with a dead follower: {result}"
            assert "err" in result
        assert procs[0].poll() is None, "leader process died"
    finally:
        if client is not None:
            client.close()
        reap(procs)

    # orchestrator restart: a FRESH process set on the same serving port
    procs = boot_pair(_free_port())
    client = None
    try:
        client = RemoteEngine("127.0.0.1", port_tcp, token="mh-tok",
                              timeout=60.0)
        client.write_relationships([WriteOp("touch", parse_relationship(
            "namespace:healed#creator@user:u2"))])
        assert client.check_bulk([CheckItem(
            "namespace", "healed", "view", "user", "u2")]) == [True]
    finally:
        if client is not None:
            client.close()
        reap(procs)

"""Cross-request lookup fusing: fused dispatches must return exactly
what per-request dispatches return, for full and partly filled fused
programs, mixed types, unknown types, and engine errors; a lookup with
nothing beside it goes alone; a window's first lookup compiles both of
its programs and no other waits for a compile; and all of it by counts
and with the constants production runs (8 rows, used from 3 waiting
lookups), the batcher held and released by the test
(tests/fusing.py)."""

import threading

import numpy as np
import pytest

from fusing import hold, release, warm
from spicedb_kubeapi_proxy_tpu.engine import Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.engine import batcher as batcher_mod
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

SCHEMA = parse_schema("""
definition user {}
definition ns {
  relation viewer: user
  permission view = viewer
}
definition pod {
  relation owner: user
  permission view = owner
}
""")


def build(fuse=True):
    e = Engine(schema=SCHEMA)
    rng = np.random.default_rng(0)
    rels = {f"ns:n{rng.integers(30)}#viewer@user:u{rng.integers(20)}"
            for _ in range(200)} | {
        f"pod:p{i}#owner@user:u{i % 20}" for i in range(25)}
    e.write_relationships(
        [WriteOp("touch", parse_relationship(r)) for r in sorted(rels)])
    if not fuse:
        e.disable_lookup_batching()
    return e


def masks(e, subjects, rtype="ns"):
    futs = [e.lookup_resources_mask_async(rtype, "view", "user", u)
            for u in subjects]
    return [f.result() for f in futs]


def counts():
    return tuple(metrics.counter(n).value for n in (
        "engine_lookup_batches_total", "engine_lookups_total",
        "engine_dispatch_rows_total"))


def held_masks(e, asked):
    """``asked``: [(type, user)] submitted while the batcher is held and
    released together. -> their masks, and the movement of (fused
    dispatches, lookups, rows)."""
    b = e._batcher
    hold(b)
    futs = [e.lookup_resources_mask_async(t, "view", "user", u)
            for t, u in asked]
    c0 = counts()
    release(b, len(asked))
    got = [f.result()[0] for f in futs]
    return got, tuple(b - a for a, b in zip(c0, counts()))


def test_batched_matches_unbatched_across_types():
    plain = build(fuse=False)
    batched = build()
    warm(batched, "ns")
    warm(batched, "pod")
    subjects = [f"u{i}" for i in range(16)]
    want_ns = [m for m, _ in masks(plain, subjects, "ns")]
    want_pod = [m for m, _ in masks(plain, subjects[:3], "pod")]

    # mixed types wait together and leave in one flush: a fused dispatch
    # a type, each row its own user's mask
    got, (fused, lookups, rows) = held_masks(
        batched, [("ns", u) for u in subjects[:3]]
        + [("pod", u) for u in subjects[:3]])
    for g, w in zip(got, want_ns[:3] + want_pod):
        np.testing.assert_array_equal(g, w)
    assert (fused, lookups, rows) == (2, 6, 6)

    # more lookups than the fused program has rows: two full dispatches
    got, (fused, lookups, rows) = held_masks(
        batched, [("ns", u) for u in subjects])
    for g, w in zip(got, want_ns):
        np.testing.assert_array_equal(g, w)
    assert (fused, lookups, rows) == (2, 16, 16)

    # what is left over a full dispatch is too few to fuse: it goes
    # alone, through the one-row program; five fill a dispatch in part
    for n, fused_want in ((10, 1), (5, 1), (2, 0)):
        got, (fused, lookups, rows) = held_masks(
            batched, [("ns", u) for u in subjects[:n]])
        for g, w in zip(got, want_ns):
            np.testing.assert_array_equal(g, w)
        assert (fused, lookups, rows) == (fused_want, n, n)


def test_a_lookup_with_nothing_waiting_is_one_dispatch_of_one_row():
    e = build()
    warm(e, "ns")
    d0 = metrics.histogram("engine_lookup_seconds").n
    c0 = counts()
    mask, interner = e.lookup_resources_mask("ns", "view", "user", "u3")
    assert tuple(b - a for a, b in zip(c0, counts())) == (0, 1, 1)
    assert metrics.histogram("engine_lookup_seconds").n == d0 + 1
    names = {interner.string(i) for i in np.flatnonzero(mask)}
    assert names == set(e.lookup_resources("ns", "view", "user", "u3"))


def test_a_windows_first_lookup_returns_with_both_programs_compiled():
    e = build()
    program = e._batcher._program
    assert not program(e.compiled(), "ns", "view").ready
    c0 = counts()
    e.lookup_resources_mask("ns", "view", "user", "u1")
    # it went alone, and the compile's dispatch of trash seeds is nobody's
    assert tuple(b - a for a, b in zip(c0, counts())) == (0, 1, 1)
    assert program(e.compiled(), "ns", "view").ready
    # a window of its own: the other type's is not begun by this one
    assert not program(e.compiled(), "pod", "view").ready
    # the program rides along an incremental update of the graph
    e.write_relationships(
        [WriteOp("touch", parse_relationship("ns:n1#viewer@user:u99"))])
    assert program(e.compiled(), "ns", "view").ready


def test_a_lookup_that_finds_the_program_being_compiled_goes_alone(
        monkeypatch):
    # the compile is held by the test: the first lookup waits for it, the
    # one that finds it begun is answered meanwhile, outside the queue
    e = build()
    go = threading.Event()
    compile_ = batcher_mod._FusedProgram._compile
    monkeypatch.setattr(
        batcher_mod._FusedProgram, "_compile",
        lambda self, cg: go.wait(60) and compile_(self, cg))
    prog = e._batcher._program(e.compiled(), "ns", "view")
    c0 = counts()
    first = threading.Thread(
        target=e.lookup_resources_mask, args=("ns", "view", "user", "u1"))
    first.start()
    while counts() != (c0[0], c0[1] + 1, c0[2] + 1):
        first.join(0.002)  # until its own dispatch is out: it waits now
    assert prog._begun.locked()
    n0 = metrics.histogram("engine_batch_wait_seconds").n
    c0 = counts()
    mask, _ = e.lookup_resources_mask("ns", "view", "user", "u3")
    assert tuple(b - a for a, b in zip(c0, counts())) == (0, 1, 1)
    assert metrics.histogram("engine_batch_wait_seconds").n == n0
    assert first.is_alive() and not prog.ready
    go.set()
    first.join(60)
    assert prog.ready and not first.is_alive()
    want, _ = build(fuse=False).lookup_resources_mask(
        "ns", "view", "user", "u3")
    np.testing.assert_array_equal(mask, want)


def test_a_program_that_does_not_compile_leaves_lookups_alone(monkeypatch):
    e = build()

    def boom(*a, **k):
        raise RuntimeError("no such program")

    cg = e.compiled()
    real = cg.query_async
    monkeypatch.setattr(
        cg, "query_async", lambda *a, **k: (
            boom() if k.get("q_contig_grid") else real(*a, **k)))
    e.lookup_resources_mask("ns", "view", "user", "u0")  # begins it
    assert not e._batcher._program(cg, "ns", "view").ready
    c0 = counts()
    masks(e, ["u1", "u2", "u3"])
    assert tuple(b - a for a, b in zip(c0, counts())) == (0, 3, 3)


def test_unknown_type_resolves_none():
    e = build()
    fut = e.lookup_resources_mask_async("nosuch", "view", "user", "u1")
    assert fut.result() == (None, None)


def test_error_propagates_to_all_waiters():
    e = build()
    warm(e, "ns")

    def boom(*a, **k):
        raise RuntimeError("device on fire")

    e._batcher._dispatch = boom
    hold(e._batcher)
    f1 = e.lookup_resources_mask_async("ns", "view", "user", "u1")
    f2 = e.lookup_resources_mask_async("ns", "view", "user", "u2")
    release(e._batcher, 2)
    with pytest.raises(RuntimeError, match="on fire"):
        f1.result()
    with pytest.raises(RuntimeError, match="on fire"):
        f2.result()


def test_explicit_now_bypasses_batcher():
    # a pinned evaluation time cannot share the batch's dispatch clock
    e = build()
    warm(e, "ns")
    hold(e._batcher)  # nothing that waited could ever answer
    import time as _t
    mask, interner = e.lookup_resources_mask(
        "ns", "view", "user", "u3", now=_t.time())
    assert interner is not None


def test_concurrent_threads_fuse():
    e = build()
    plain = build(fuse=False)
    warm(e, "ns")
    subjects = [f"u{i}" for i in range(8)]
    want = {u: m for u, (m, _) in zip(subjects, masks(plain, subjects))}
    results = {}
    lock = threading.Lock()

    def worker(u):
        m, _ = e.lookup_resources_mask("ns", "view", "user", u)
        with lock:
            results[u] = m

    hold(e._batcher)
    c0 = counts()
    threads = [threading.Thread(target=worker, args=(u,)) for u in subjects]
    for t in threads:
        t.start()
    release(e._batcher, 8)
    for t in threads:
        t.join()
    for u in subjects:
        np.testing.assert_array_equal(results[u], want[u])
    # the 8 lookups that waited together left in ONE dispatch of 8 rows
    assert tuple(b - a for a, b in zip(c0, counts())) == (1, 8, 8)


def test_close_marks_batcher_dead_and_submits_fall_through():
    # a submit racing disable_lookup_batching (shutdown) must not queue
    # into a dead batcher that nobody will flush
    e = build()
    b = e._batcher
    warm(e, "ns")
    b.close()
    fut = b.submit("ns", "view", "user", "u3", None)
    mask, interner = fut.result()  # direct engine path
    want, _ = build(fuse=False).lookup_resources_mask(
        "ns", "view", "user", "u3")
    np.testing.assert_array_equal(mask, want)


def test_disable_lookup_batching_closes_and_flushes():
    e = build()
    b = e._batcher
    warm(e, "ns")
    hold(b)
    pending = e.lookup_resources_mask_async("ns", "view", "user", "u1")
    with b._cond:
        b._enqueuing = False  # the enqueue it waited behind has ended
    e.disable_lookup_batching()
    assert b._closed and not b._pending
    # the pending lookup was flushed by close(), not abandoned
    mask, interner = pending.result()
    want, _ = build(fuse=False).lookup_resources_mask(
        "ns", "view", "user", "u1")
    np.testing.assert_array_equal(mask, want)
    # new lookups take the direct path
    m2, _ = e.lookup_resources_mask("ns", "view", "user", "u2")
    assert m2 is not None


class _OnTheDevice:
    """A dispatch's output that has not finished."""

    def is_ready(self):
        return False


@pytest.mark.parametrize("unfinished,waiting,goes", [
    (0, 1, True), (0, 5, True),    # nothing on the device: at once
    (1, 1, True), (1, 2, True),    # too few to fuse: pipelined, alone
    (1, 3, False), (1, 7, False),  # enough to fuse: gather company
    (1, 8, True), (1, 11, True),   # a whole dispatch waits: go
    (2, 1, False), (2, 8, False),  # two on the device: everything waits
])
def test_what_waits_leaves_by_what_is_on_the_device(unfinished, waiting,
                                                    goes):
    """The flush rule by counts (default rows: 8, used from 3)."""
    from spicedb_kubeapi_proxy_tpu.engine.batcher import LookupBatcher

    b = LookupBatcher(engine=None)
    b._inflight = [_OnTheDevice() for _ in range(unfinished)]
    b._pending = list(range(waiting))
    with b._cond:
        batch = b._take_locked()
    assert (batch == list(range(waiting))) if goes else (batch is None)
    assert b._enqueuing is goes and len(b._pending) == (0 if goes else
                                                         waiting)

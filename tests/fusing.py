"""Steering the engine's lookup batcher (engine/batcher.py) from a test,
by counts and never by timings: ``hold`` puts it in the state a dispatch
being enqueued puts it in, so every lookup that arrives waits;
``release`` lets them go, as the end of that enqueue would, once the
number the test expects is waiting: they leave in ONE flush. ``warm``
is a window's first lookup: it returns with both lookup programs of the
window compiled, so what follows can fuse."""

import time


def warm(engine, resource_type: str, permission: str = "view",
         subject_type: str = "user") -> None:
    engine.lookup_resources_mask(resource_type, permission, subject_type,
                                 "nobody-the-graph-knows")
    prog = engine._batcher._program(engine.compiled(), resource_type,
                                    permission)
    assert prog is not None and prog.ready


def hold(batcher) -> None:
    with batcher._cond:
        batcher._enqueuing = True


def release(batcher, waiting: int, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while True:
        with batcher._cond:
            if len(batcher._pending) >= waiting:
                batcher._enqueuing = False
                batcher._cond.notify_all()
                return
        if time.monotonic() > deadline:
            raise AssertionError(
                f"{len(batcher._pending)} lookups wait, not {waiting}")
        time.sleep(0.002)

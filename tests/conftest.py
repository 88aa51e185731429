"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Real-TPU runs happen via chip_smoke.py / benchmark/run.py; unit tests
exercise the same jitted code paths on CPU, including multi-device
sharding over a virtual 8-device mesh (SURVEY.md env notes).
"""

import os
import sys

# Must be set before jax is imported anywhere. Force CPU even if the outer
# environment selects the TPU platform — unit tests must not grab the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Opt-in runtime concurrency sanitizer (PROXY_SANITIZE=1): swap the lock
# factories BEFORE any package module imports, so every named lock in
# the codebase is created instrumented and the whole suite doubles as a
# lock-order / loop-blocking race detector (utils/sanitizer.py). The
# session fixture below fails the run on enforced violations.
_SANITIZE = os.environ.get("PROXY_SANITIZE", "") == "1"
if _SANITIZE:
    from spicedb_kubeapi_proxy_tpu.utils import sanitizer as _sanitizer

    _sanitizer.install()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _collector_as_found():
    """A process that begins to serve freezes its heap and hands the
    collecting to a thread of its own, once (obs/profile.py). A test
    process is no serving process: what a test's server did to the
    collector is undone after the test, so no other test inherits it
    (the thread ends by itself once the thresholds are not its own)."""
    import gc

    thresholds = gc.get_threshold()
    yield
    if gc.get_freeze_count():
        gc.unfreeze()
    gc.set_threshold(*thresholds)


@pytest.fixture(scope="session", autouse=True)
def _proxy_sanitize_gate():
    """With PROXY_SANITIZE=1: after the whole session, report advisory
    findings (hold-time, loop contention) and FAIL on enforced ones
    (lock-order cycles, loop-thread blocking calls) — the acceptance
    bar for the sanitizer-enabled tier-1 run in CI's chaos job."""
    yield
    if not _SANITIZE:
        return
    advisory = [v for v in _sanitizer.report()
                if v.kind not in _sanitizer.ENFORCED_KINDS]
    if advisory:
        print(f"\n[sanitizer] {len(advisory)} advisory finding(s):",
              file=sys.stderr)
        for v in advisory[:40]:
            print(f"[sanitizer]   {v.render()}", file=sys.stderr)
    bad = _sanitizer.enforced_violations()
    assert not bad, (
        "concurrency sanitizer violations:\n"
        + "\n".join(v.render() for v in bad))

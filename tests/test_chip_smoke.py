"""chip_smoke.py rehearsed on the CPU: it walks every phase at its tiny
size (kernels interpreted), and without a TPU it never prints a result
and never exits 0 — the driver runs it in a sandbox like this one, where
it must fail."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)  # one CPU device, as the script expects
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_full_width_run_without_tpu_fails_before_any_work():
    p = _run()
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "== load" not in p.stdout  # no CPU run at full width
    assert "not 'tpu'" in p.stderr


def test_tiny_rehearsal_walks_every_phase_but_reports_nothing():
    p = _run("--tiny", SDBKP_BITPROP="interpret",
             SDBKP_SEMIRING="interpret")
    out = p.stdout
    assert p.returncode != 0, out[-2000:]
    assert '"ok"' not in out
    # every phase ran to its end, with its checks
    for marker in ("== device:", "== load:", "== engine:", "== served:",
                   "native graph core built from source",
                   "parity: lookups and checks equal across pull/push/auto",
                   "agree", "GET pod", ": 403", "watch namespaces as"):
        assert marker in out, (marker, out[-3000:], p.stderr[-3000:])
    assert "bit enabled=True interpreted=True" in out
    assert "no result" in p.stderr

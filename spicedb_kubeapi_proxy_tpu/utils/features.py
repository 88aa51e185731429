"""Feature gates: named on/off switches for optional behaviors.

Mirrors the reference's feature-gate plumbing
(/root/reference/pkg/proxy/features.go:10-27, kube component-base style):
gates are registered with a default, overridable from the CLI
(``--feature-gates Name=true,Other=false``). Unlike a bare settings dict,
unknown gate names are rejected at parse time so typos fail boot, not
silently.

Registered gates (all real behavior switches):

- ``IncrementalGraphUpdates`` (default on): O(delta) compiled-graph
  updates on write; off forces a full recompile per revision change.
- ``ProtobufNegotiation`` (default on): forward kube-protobuf Accept
  ranges upstream and wire-filter protobuf responses; off rewrites every
  Accept to JSON.
- ``ProtobufWatch`` (default on): let WATCH requests negotiate protobuf
  too — frames pass through filtered and byte-identical
  (proxy/kubeproto.py WatchEvent surgery); off restores the legacy
  JSON-downgrade rewrite, counted in ``/metrics``
  (``proxy_proto_watch_downgrades_total``) so the re-encoding cost is
  visible to operators.
"""

from __future__ import annotations

import threading


class FeatureGateError(ValueError):
    pass


class FeatureGates:
    def __init__(self):
        self._lock = threading.Lock()
        self._defaults: dict[str, bool] = {}
        self._overrides: dict[str, bool] = {}

    def register(self, name: str, default: bool) -> None:
        with self._lock:
            self._defaults[name] = default

    def enabled(self, name: str) -> bool:
        with self._lock:
            if name not in self._defaults:
                raise FeatureGateError(f"unknown feature gate {name!r}")
            return self._overrides.get(name, self._defaults[name])

    def set(self, name: str, value: bool) -> None:
        with self._lock:
            if name not in self._defaults:
                raise FeatureGateError(
                    f"unknown feature gate {name!r} "
                    f"(known: {', '.join(sorted(self._defaults))})")
            self._overrides[name] = value

    def validate_spec(self, spec: str) -> list[tuple[str, bool]]:
        """Parse ``Name=true,Other=false`` (CLI form) without applying;
        raises FeatureGateError on syntax errors or unknown names."""
        out = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, value = part.partition("=")
            if not sep or value.lower() not in ("true", "false"):
                raise FeatureGateError(
                    f"invalid feature gate setting {part!r} "
                    "(expected Name=true|false)")
            name = name.strip()
            with self._lock:
                if name not in self._defaults:
                    raise FeatureGateError(
                        f"unknown feature gate {name!r} "
                        f"(known: {', '.join(sorted(self._defaults))})")
            out.append((name, value.lower() == "true"))
        return out

    def apply_spec(self, spec: str) -> None:
        for name, value in self.validate_spec(spec):
            self.set(name, value)

    def reset(self) -> None:
        with self._lock:
            self._overrides.clear()

    def known(self) -> dict[str, bool]:
        with self._lock:
            return {n: self._overrides.get(n, d)
                    for n, d in sorted(self._defaults.items())}


features = FeatureGates()
features.register("IncrementalGraphUpdates", True)
features.register("ProtobufNegotiation", True)
features.register("ProtobufWatch", True)

"""The benchmark's own upstream: a read-only stand-in for the
kube-apiserver, the callable ``Options(upstream=...)`` takes. ``get``
answers from a dict of bytes, ``list`` from bytes serialised once during
set-up, so the time of a real apiserver is nobody's to win or lose. It
never imports the program's engine, kernels, authorization or mesh code —
only the request/response dataclasses it has to speak.
"""

import json

import jax

from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import (
    ProxyRequest,
    ProxyResponse,
    kube_status,
)

_JSON = {"Content-Type": "application/json"}


def _kind(resource: str) -> str:
    return resource[:-1].capitalize() if resource.endswith("s") \
        else resource.capitalize()


class ReadOnlyKube:
    def __init__(self, objects: dict):
        """``objects``: resource -> [(namespace, name), ...]."""
        self._get = {}
        self._list = {}
        rv = 0
        for resource, pairs in objects.items():
            kind = _kind(resource)
            rows = []
            for ns, name in pairs:
                rv += 1
                meta = {"name": name, "resourceVersion": str(rv)}
                if ns:
                    meta["namespace"] = ns
                row = json.dumps({"apiVersion": "v1", "kind": kind,
                                  "metadata": meta}).encode()
                self._get[(resource, ns, name)] = row
                rows.append(row)
            self._list[resource] = (
                b'{"kind": "%sList", "apiVersion": "v1", "metadata": '
                b'{"resourceVersion": "%d"}, "items": [' % (kind.encode(), rv)
                + b", ".join(rows) + b"]}")

    async def __call__(self, req: ProxyRequest) -> ProxyResponse:
        with jax.profiler.TraceAnnotation("bench:upstream"):
            info = req.request_info or parse_request_info(
                req.method, req.path, req.query)
            if info.verb == "get":
                body = self._get.get((info.resource, info.namespace,
                                      info.name))
            elif info.verb == "list" and not info.namespace:
                body = self._list.get(info.resource)
            else:
                return kube_status(405, f"read-only stand-in: {info.verb}")
            if body is None:
                return kube_status(404, f'{info.resource} "{info.name}" '
                                   "not found", "NotFound")
            return ProxyResponse(status=200, headers=dict(_JSON), body=body)

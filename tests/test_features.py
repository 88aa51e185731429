"""Feature gates (reference pkg/proxy/features.go:10-27): registry,
CLI spec parsing, and the gates actually switching behavior."""

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.proxy.options import Options, OptionsError
from spicedb_kubeapi_proxy_tpu.proxy.upstream import rewrite_accept
from spicedb_kubeapi_proxy_tpu.utils.features import (
    FeatureGateError,
    features,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def reset_gates():
    features.reset()
    yield
    features.reset()


def test_spec_parsing():
    assert features.validate_spec(
        "IncrementalGraphUpdates=false, ProtobufWatch=true") == [
        ("IncrementalGraphUpdates", False), ("ProtobufWatch", True)]
    for bad in ("Nope=true", "ProtobufWatch=maybe", "ProtobufWatch"):
        with pytest.raises(FeatureGateError):
            features.validate_spec(bad)
    with pytest.raises(OptionsError):
        Options(rule_content="x", upstream_url="http://u",
                feature_gates="Nope=true").validate()


def test_incremental_gate_forces_full_recompiles():
    e = Engine()
    e.write_relationships([WriteOp("touch", parse_relationship(
        "namespace:a#creator@user:alice"))])
    e.compiled()
    c0 = metrics.counter("engine_graph_compiles_total").value
    features.set("IncrementalGraphUpdates", False)
    e.write_relationships([WriteOp("touch", parse_relationship(
        "namespace:b#creator@user:alice"))])
    assert e.check(CheckItem("namespace", "b", "view", "user", "alice"))
    assert metrics.counter("engine_graph_compiles_total").value == c0 + 1
    # back on: next write goes incremental again
    features.set("IncrementalGraphUpdates", True)
    e.write_relationships([WriteOp("touch", parse_relationship(
        "namespace:c#creator@user:alice"))])
    assert e.check(CheckItem("namespace", "c", "view", "user", "alice"))
    assert metrics.counter("engine_graph_compiles_total").value == c0 + 1


def test_protobuf_gate():
    accept = "application/vnd.kubernetes.protobuf,application/json"
    assert rewrite_accept(accept, False) == accept
    features.set("ProtobufNegotiation", False)
    assert rewrite_accept(accept, False) == "application/json"

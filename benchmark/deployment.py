"""A configuration loaded by name: its files, its relationships from the
seed, and the string forms the program and the upstream are fed.

``generate(sizes, seed)`` of a configuration returns integer tables:
``types`` maps an object type to its name segments ``[(prefix, count),
...]`` (object *i* of a segment is called ``prefix + str(i)``, indices run
through the segments in order) and ``edges`` is a list of
``(resource_type, relation, subject_type, subject_relation, resource
index array, subject index array)``.
"""

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Deployment:
    def __init__(self, name: str, seed: int, rehearse: bool = False):
        self.dir = os.path.join(HERE, "configs", name)
        with open(os.path.join(self.dir, "config.json")) as f:
            self.config = json.load(f)
        self.sizes = self.config["rehearse_sizes" if rehearse else "sizes"]
        gen = load_module(os.path.join(self.dir, "generate.py"),
                          "bench_generate_" + name.replace("-", "_"))
        tables = gen.generate(self.sizes, seed)
        self.types, self.edges = tables["types"], tables["edges"]
        self._names = {}

    def text(self, file: str) -> str:
        with open(os.path.join(self.dir, file)) as f:
            return f.read()

    def count(self, typ: str) -> int:
        return sum(n for _, n in self.types[typ])

    def names(self, typ: str) -> np.ndarray:
        """Every object name of a type, by index."""
        if typ not in self._names:
            self._names[typ] = np.concatenate(
                [np.char.add(prefix, np.arange(n).astype(str))
                 for prefix, n in self.types[typ]])
        return self._names[typ]

    def n_relationships(self) -> int:
        return sum(len(e[4]) for e in self.edges)

    def columns(self) -> dict:
        """The ``Engine.bulk_load`` column dict, rows in edge order."""
        cols = {k: [] for k in ("resource_type", "resource_id", "relation",
                                "subject_type", "subject_id",
                                "subject_relation")}
        for rt, rel, st, srel, res, sub in self.edges:
            n = len(res)
            cols["resource_type"].append(np.full(n, rt))
            cols["resource_id"].append(self.names(rt)[res])
            cols["relation"].append(np.full(n, rel))
            cols["subject_type"].append(np.full(n, st))
            cols["subject_id"].append(self.names(st)[sub])
            cols["subject_relation"].append(np.full(n, srel))
        return {k: np.concatenate(v) for k, v in cols.items()}

    def upstream_objects(self) -> dict:
        """kube resource -> [(namespace, name), ...]: an engine id
        ``ns/name`` is a namespaced object, a bare id a cluster-scoped
        one."""
        out = {}
        for resource, typ in self.config["upstream"].items():
            out[resource] = [tuple(i.rpartition("/")[::2])
                             for i in self.names(typ).tolist()]
        return out

"""The fixpoint's schedule (``ops/reachability._stratify``, ``RunMeta``,
``_run``; ``parallel/sharded._run_sharded``): what feeds a cycle is
applied once, in order, before the loop, and the loop walks only edges
whose source and destination both lie in the core.

Four things are held here. The schedule of the benchmark's three
deployments at their rehearsal sizes; that the new order computes, slot
for slot, what one plain loop over every edge computes (``flat``), what
the oracle answers, in no more trips; that writes ride the overlay where
their direction fits the order and recompile where it does not; and that
the mesh's program reads the same schedule to the same state.
"""

import dataclasses
import time

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
from spicedb_kubeapi_proxy_tpu.ops import reachability
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

from test_recursive_arrow import _bench_module

SEED = 2900000011


def touch(*rels):
    return [WriteOp("touch", parse_relationship(r)) for r in rels]


def range_names(cg) -> list:
    """``type#relation`` of every slot range, in ``range_offs``' order."""
    name = {off: f"{t}#{r}" for (t, r), off in cg.slot_offset.items()}
    return [name[int(off)] for off in cg.range_offs]


def levels_by_name(cg) -> dict:
    """``type#relation`` -> level, for every slot range."""
    return dict(zip(range_names(cg), cg.range_levels.tolist()))


def lv_of(cg, off: int) -> int:
    """The level of the slot range that starts at ``off``."""
    return int(cg.range_levels[
        np.searchsorted(cg.range_offs, off, "right") - 1])


def slice_pairs(cg, k: int) -> dict:
    """``(src range, dst range)`` -> count of the real edges of phase k's
    residual slice."""
    name = range_names(cg)
    lo, hi = cg.run_meta().level_slice(k)
    src, dst = cg.res_src[lo:hi], cg.res_dst[lo:hi]
    real = dst != cg.M
    out: dict = {}
    for s, d in zip(
            np.searchsorted(cg.range_offs, src[real], "right") - 1,
            np.searchsorted(cg.range_offs, dst[real], "right") - 1):
        key = (name[s], name[d])
        out[key] = out.get(key, 0) + 1
    return out


# ---------------------------------------------------------------------------
# (a) the benchmark's three deployments
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def deployments():
    cache: dict = {}

    def load(name):
        if name not in cache:
            dep = _bench_module("deployment").Deployment(
                name, SEED, rehearse=True)
            e = Engine(dep.text("bootstrap.yaml"))
            e.bulk_load(dep.columns())
            cache[name] = dep, e.compiled()
        return cache[name]

    return load


def tuples_of(dep, rt, rel, st, srel="") -> int:
    return sum(len(e[4]) for e in dep.edges
               if (e[0], e[1], e[2], e[3] or "") == (rt, rel, st, srel))


def test_nested_org_loop_holds_group_to_group_edges_only(deployments):
    dep, cg = deployments("nested-org-1m")
    nested = tuples_of(dep, "group", "member", "group", "member")
    direct = tuples_of(dep, "group", "member", "user")
    assert nested and direct > 5 * nested
    assert slice_pairs(cg, 0) == {("group#member", "group#member"): nested}
    assert slice_pairs(cg, -1) == {("user#__self", "group#member"): direct}
    lv = levels_by_name(cg)
    assert lv["group#member"] == 0 and lv["user#__self"] < -1
    assert cg.n_pre == 1  # a root needs no phase: the entry alone
    assert cg.core_ranges() == 1 and cg.feeder_ranges() == 1
    lo, hi = cg.run_meta().level_slice(0)
    assert cg.core_edges() == hi - lo == reachability._next_bucket(nested)
    # every entry edge starts at a user: the walked part is its floor
    assert cg.feeder_edges() == 8 + reachability._next_bucket(direct)
    assert cg.run_meta().parts(-1) == (0, 8, cg.feeder_edges())
    assert cg.seed_edges() == direct


def test_ns_tree_loop_holds_the_arrow_edges_only(deployments):
    dep, cg = deployments("ns-tree-10hop")
    arrows = tuples_of(dep, "namespace", "parent", "namespace")
    assert slice_pairs(cg, 0) == {
        ("namespace#view", "namespace#__arrow_view_0"): arrows}
    assert slice_pairs(cg, -1) == {}  # the cycle is entered by a program
    lv = levels_by_name(cg)
    assert lv["namespace#view"] == lv["namespace#__arrow_view_0"] == 0
    # two feeder levels in order, the users below both, then the entry
    assert lv["user#__self"] < lv["group#member"] == \
        lv["namespace#creator"] < lv["namespace#viewer"] == -2
    assert cg.n_pre == 3 and cg.core_ranges() == 2
    assert set(slice_pairs(cg, -3)) == {
        ("user#__self", "group#member"), ("user#__self", "namespace#creator")}
    assert set(slice_pairs(cg, -2)) == {
        ("user#__self", "namespace#viewer"),
        ("group#member", "namespace#viewer")}
    assert lv["pod#view"] > 0  # rests on the cycle, after the loop


def test_kube_rbac_has_no_cycle_and_keeps_the_schedule_it_had(deployments):
    """Read from the parent commit (PR 28) on the same seed: a graph the
    sink-end peel takes whole gets no feeder, no entry, the same levels.
    Since PR 31 the two slices whose edges all start in a ``__self``
    range (users into groups, pods' namespaces) hold them as a seeded
    part behind an empty walked part of 8: (.., 272, .., 1336) before."""
    _, cg = deployments("kube-rbac-10m")
    assert cg.n_pre == 0 and cg.n_levels == 6
    assert cg.res_level_bounds == (0, 8, 16, 280, 312, 320, 832, 1352)
    assert cg.seed.starts == (8, 16, 24, 312, 320, 832, 840)
    assert cg.seed.fanout == (0, 0, 8, 0, 0, 0, 64)
    assert levels_by_name(cg) == {
        "user#__self": 1, "group#member": 2, "namespace#creator": 3,
        "namespace#viewer": 3, "namespace#view": 4, "activity#__self": 5,
        "namespace#__self": 5, "pod#creator": 5, "pod#viewer": 5,
        "pod#__arrow_view_0": 5, "workflow#__self": 5, "group#__self": 6,
        "lock#__self": 6, "lock#workflow": 6, "namespace#admin": 6,
        "pod#__self": 6, "pod#namespace": 6, "pod#edit": 6, "pod#view": 6,
        "workflow#idempotency_key": 6}
    assert [(b.dst_off, b.src_off, b.level, b.closured)
            for b in cg.blocks] == [(2816, 4864, 5, False)]
    assert cg.core_edges() == 8 and cg.core_ranges() == 0
    assert cg.feeder_edges() == 0 and cg.feeder_ranges() == 0
    meta = cg.run_meta()
    assert meta.pre_ranges == () and len(meta.level_ranges) == 6


# ---------------------------------------------------------------------------
# (b) the new order against one plain loop, and against the oracle
# ---------------------------------------------------------------------------

PROGRAM = """
definition user {}
definition group { relation member: user }
definition folder {
  relation parent: folder
  relation viewer: user | group#member
  relation auditor: user | group#member
  relation banned: user | group#member
  permission view = ((viewer & auditor) - banned) + parent->view
}
definition doc {
  relation folder: folder
  permission read = folder->view
}
"""

CHAIN = """
definition user {}
definition team { relation member: user | team#member }
definition project {
  relation owner: team#member | user
  relation parent: project
  permission admin = owner + parent->admin
}
definition doc {
  relation project: project
  permission edit = project->admin
}
"""

NESTED = """
definition user {}
definition group { relation member: user | group#member }
definition namespace {
  relation parent: namespace
  relation viewer: user | group#member
  permission view = viewer + parent->view
}
"""

CONDITIONAL = """
use expiration
caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) { ip in allowed }
definition user {}
definition group {
  relation member: user | user with expiration | user with ip_allowlist
    | group#member | group#member with expiration
}
definition namespace {
  relation viewer: group#member
  permission view = viewer
}
"""

N_USERS = 6
SUBJECTS = [("user", f"u{i}") for i in range(N_USERS)] + [("user", "nobody")]
NOW = time.time()


def _tree(rng, n):
    """(child, parent) pairs of a random forest over 0..n-1."""
    return [(c, int(rng.integers(c))) for c in range(1, n)
            if rng.random() < 0.85]


def program_graph(rng):
    ops = set()
    for g in range(3):
        for u in rng.choice(N_USERS, size=2, replace=False):
            ops.add(f"group:g{g}#member@user:u{u}")
    for f in range(8):
        for rel, share in (("viewer", 0.7), ("auditor", 0.8),
                           ("banned", 0.3)):
            if rng.random() < share:
                ops.add(f"folder:f{f}#{rel}@user:u{rng.integers(N_USERS)}")
            if rng.random() < share / 2:
                ops.add(f"folder:f{f}#{rel}@group:g{rng.integers(3)}#member")
    ops |= {f"folder:f{c}#parent@folder:f{p}" for c, p in _tree(rng, 8)}
    ops |= {f"doc:d{d}#folder@folder:f{rng.integers(8)}" for d in range(6)}
    return ops


def chain_graph(rng):
    ops = {f"team:t{t}#member@user:u{rng.integers(N_USERS)}"
           for t in range(6)}
    ops |= {f"team:t{p}#member@team:t{c}#member" for c, p in _tree(rng, 6)}
    for p in range(7):
        ops.add(f"project:p{p}#owner@team:t{rng.integers(6)}#member")
        if rng.random() < 0.3:
            ops.add(f"project:p{p}#owner@user:u{rng.integers(N_USERS)}")
    ops |= {f"project:p{c}#parent@project:p{p}" for c, p in _tree(rng, 7)}
    ops |= {f"doc:d{d}#project@project:p{rng.integers(7)}" for d in range(5)}
    return ops


def nested_graph(rng):
    ops = {f"group:g{g}#member@user:u{rng.integers(N_USERS)}"
           for g in range(6)}
    ops |= {f"group:g{p}#member@group:g{c}#member" for c, p in _tree(rng, 6)}
    for n in range(8):
        if rng.random() < 0.6:
            ops.add(f"namespace:n{n}#viewer@group:g{rng.integers(6)}#member")
        if rng.random() < 0.4:
            ops.add(f"namespace:n{n}#viewer@user:u{rng.integers(N_USERS)}")
    ops |= {f"namespace:n{c}#parent@namespace:n{p}" for c, p in _tree(rng, 8)}
    return ops


def conditional_graph(rng):
    def stamp(t):
        return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t))

    traits = ("", f"[expiration:{stamp(NOW + 3600)}]",
              f"[expiration:{stamp(NOW - 3600)}]",
              '[ip_allowlist:{"allowed":["10.0.0.0/8"]}]')
    # one entry edge of each kind whatever the draw, the rest drawn
    member = {(g, g): traits[g] for g in range(4)}
    for g in range(6):
        member.setdefault((g, int(rng.integers(N_USERS))),
                          traits[rng.integers(4)])
    ops = {f"group:g{g}#member@user:u{u}{t}" for (g, u), t in member.items()}
    # an expiring edge on the cycle keeps the self-pair from being closed
    # (and so the cycle in the loop) when every pair is made dense
    # (it also closes a cycle among the groups themselves)
    ops |= {f"group:g{p}#member@group:g{c}#member" for c, p in _tree(rng, 6)}
    ops.add(f"group:g5#member@group:g0#member{traits[1]}")
    ops |= {f"namespace:n{n}#viewer@group:g{rng.integers(6)}#member"
            for n in range(6)}
    return ops


# name -> (schema, data, the core the data makes when no block is dense)
CASES = {
    "program": (PROGRAM, program_graph,
                {"folder#view", "folder#__arrow_view_0"}),
    "chain": (CHAIN, chain_graph,
              {"team#member", "project#owner", "project#admin",
               "project#__arrow_admin_0"}),
    # namespace#viewer lies between two cycles: it iterates with both
    "nested": (NESTED, nested_graph,
               {"group#member", "namespace#viewer", "namespace#view",
                "namespace#__arrow_view_0"}),
    "conditional": (CONDITIONAL, conditional_graph, {"group#member"}),
}
CONTEXTS = {"conditional": ({"ip": "10.0.0.5"}, {"ip": "8.8.8.8"}, None)}


def build(case: str, seed: int, dense: bool, monkeypatch) -> Engine:
    """``dense``: every range pair with an edge becomes a dense block (a
    self-pair without conditions a closured one), so the same data runs
    through blocks at feeder, entry and core phases."""
    if dense:
        monkeypatch.setattr(reachability, "DENSE_MIN_EDGES", 1)
    schema, data, _ = CASES[case]
    e = Engine(schema=parse_schema(schema))
    e.write_relationships(touch(*sorted(data(np.random.default_rng(seed)))))
    e.compiled()
    return e


def flat(cg):
    """The same graph with no schedule at all: every edge (dense blocks
    unfolded to their base edges) walked on every trip of one loop until
    nothing changes. The plainest statement of what the fixpoint is."""
    parts = [(cg.res_src, cg.res_dst, cg.res_exp, cg.res_cav)]
    for b in cg.blocks:
        dl, sl = ((b.base_dst_local, b.base_src_local) if b.closured
                  else (b.dst_local, b.src_local))
        parts.append((b.src_off + sl, b.dst_off + dl,
                      np.full(len(dl), np.inf, np.float32),
                      np.zeros(len(dl), np.int32)))
    src, dst, exp, cav = (np.concatenate(c) for c in zip(*parts))
    order = np.argsort(dst, kind="stable")
    return dataclasses.replace(
        cg, blocks=[], block_index={}, res_idx=np.arange(len(order)),
        res_src=src[order].astype(np.int32),
        res_dst=dst[order].astype(np.int32),
        res_exp=exp[order].astype(np.float32),
        res_cav=cav[order].astype(np.int32),
        res_level_bounds=None, n_levels=0, n_pre=0, range_levels=None,
        programs=[dataclasses.replace(p, level=0) for p in cg.programs],
        tier=None, _device={})


def whole_state(e, backend, now=None, context=None):
    """Every slot of ``V`` for every subject, and the trips it took."""
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds = np.asarray([cg.encode_subject(t, i, None, objs)
                        for t, i in SUBJECTS], dtype=np.int32)
    q = np.tile(np.arange(cg.M, dtype=np.int32), len(SUBJECTS))
    qb = np.repeat(np.arange(len(SUBJECTS), dtype=np.int32), cg.M)
    fut = backend.query_async(seeds, q, qb, now=now, context=context)
    return fut.result().reshape(len(SUBJECTS), cg.M), fut.iterations()


def assert_matches_oracle(e, now=None, context=None):
    o = e.oracle(now=now, context=context)
    snap = e.store.snapshot()
    items = []
    for tname, d in e.schema.definitions.items():
        tid = snap.types.lookup(tname)
        if tid is None or tid not in snap.objects:
            continue
        for oid in (snap.objects[tid].string(i)
                    for i in range(2, len(snap.objects[tid]))):
            for rel in list(d.permissions) + list(d.relations):
                items += [CheckItem(tname, oid, rel, st, sid)
                          for st, sid in SUBJECTS]
    assert len(items) > 50
    got = e.check_bulk(items, now=now, context=context)
    want = [o.check(i.resource_type, i.resource_id, i.permission,
                    i.subject_type, i.subject_id) for i in items]
    bad = [(i, w, g) for i, w, g in zip(items, want, got) if w != g]
    assert not bad, f"{len(bad)}/{len(items)} differ; first: {bad[:5]}"
    assert True in want and False in want


@pytest.mark.parametrize("dense", [False, True], ids=["sparse", "dense"])
@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_slot_equals_the_plain_loop_and_the_oracle(
        case, seed, dense, monkeypatch):
    e = build(case, seed, dense, monkeypatch)
    cg = e.compiled()
    lv = levels_by_name(cg)
    core = {r for r, k in lv.items() if k == 0}
    if dense:
        # a closured self-pair leaves the loop: it is a feeder with two
        # phases (in-edges at the odd one, the closure at the even one)
        closured = [b for b in cg.blocks if b.closured]
        assert bool(closured) == (case in ("chain", "nested"))  # no
        # condition on the self-pair: "conditional" has one, "program" no pair
        assert all(b.level < -1 and b.level % 2 == 0 for b in closured)
        assert all(lv_of(cg, b.dst_off) == b.level - 1 for b in closured)
    else:
        assert core == CASES[case][2]
        assert cg.n_pre >= 1 and lv["user#__self"] == -(cg.n_pre + 1)
        # nothing on the loop's slice starts outside the core
        assert {s for s, _ in slice_pairs(cg, 0)} <= core
        assert all(d in core and lv[s] < 0
                   for s, d in slice_pairs(cg, -1))
    assert all(b.level == -1 for b in cg.blocks
               if lv_of(cg, b.dst_off) == 0 and lv_of(cg, b.src_off) < 0)
    for context in CONTEXTS.get(case, (None,)):
        got, trips = whole_state(e, cg, NOW, context)
        want, plain_trips = whole_state(e, flat(cg), NOW, context)
        assert np.array_equal(got, want), np.argwhere(got != want)[:5]
        assert got.any()
        assert trips <= plain_trips
        assert_matches_oracle(e, NOW, context)


@pytest.mark.parametrize("case,expected", [
    # the users' edges are walked before the loop, so it starts with
    # every directly bound team or group set: the plain loop pays one
    # trip more for them, and two where a group stands in between
    ("chain", 1), ("nested", 1), ("program", 2), ("conditional", 1)])
def test_trips_fall_by_the_depth_of_what_was_hoisted(case, expected,
                                                     monkeypatch):
    """Against the plain loop; against the order before PR 29 (the cycle
    and its feeders in one loop) the fall is the same: that loop walked
    the feeder chain trip by trip as the plain one does."""
    e = build(case, 1, False, monkeypatch)
    cg = e.compiled()
    _, trips = whole_state(e, cg, NOW)
    _, plain_trips = whole_state(e, flat(cg), NOW)
    assert plain_trips - trips >= expected


# ---------------------------------------------------------------------------
# (c) writes against the frozen order
# ---------------------------------------------------------------------------

WRITES = """
definition user {}
definition group { relation member: user | folder#view }
definition folder {
  relation parent: folder
  relation viewer: user | group#member
  relation banned: user
  permission view = (viewer - banned) + parent->view
}
"""


def fallbacks() -> dict:
    return {r: metrics.counter("engine_graph_incremental_fallback_total",
                               reason=r).value
            for r in ("stratification-inversion", "layout", "overflow")}


@pytest.fixture()
def writes_engine():
    e = Engine(schema=parse_schema(WRITES))
    e.write_relationships(touch(
        "folder:root#viewer@user:u0", "folder:a#parent@folder:root",
        "folder:b#parent@folder:a", "folder:b#viewer@user:u1",
        # objects the later writes name, interned by the base
        "folder:a#viewer@user:u2", "folder:root#viewer@user:u3",
        "folder:b#viewer@group:g#member", "folder:root#viewer@user:u4"))
    cg = e.compiled()
    lv = levels_by_name(cg)
    assert lv["folder#view"] == 0 and lv["folder#viewer"] < -1
    return e


def check(e, folder, user):
    return e.check_bulk([CheckItem("folder", folder, "view", "user", user)])[0]


def test_a_write_from_a_feeder_into_the_core_or_a_feeder_rides_the_overlay(
        writes_engine):
    e = writes_engine
    base, before = e.compiled(), fallbacks()
    lv = levels_by_name(base)
    # no tuple used these pairs: the schema's admission ordered them
    assert lv["user#__self"] < lv["group#member"] < lv["folder#viewer"]
    assert lv["user#__self"] < lv["folder#banned"] < -1
    assert not check(e, "b", "u5") and check(e, "b", "u0")
    e.write_relationships(touch(
        "group:g#member@user:u5",        # first tuple into a feeder range
        "folder:root#banned@user:u0"))   # first tuple on a potential pair
    cg = e.compiled()
    assert cg.res_src is base.res_src and cg.n_delta == 2
    assert fallbacks() == before
    assert check(e, "b", "u5") and not check(e, "root", "u0")
    # u0 is banned at the root alone: the right it had there no longer
    # flows down, the one u1 holds at the leaf is untouched
    assert not check(e, "b", "u0") and check(e, "b", "u1")
    got, _ = whole_state(e, cg)
    want, _ = whole_state(e, flat(cg))
    assert np.array_equal(got, want)
    assert_matches_oracle(e)


def test_a_write_from_the_core_into_a_feeder_is_an_inversion(writes_engine):
    e = writes_engine
    base, before = e.compiled(), fallbacks()
    e.write_relationships(touch("group:g#member@folder:root#view"))
    cg = e.compiled()
    after = fallbacks()
    assert after["stratification-inversion"] == \
        before["stratification-inversion"] + 1
    assert cg.res_src is not base.res_src and cg.n_delta == 0
    # recompiled: what the cycle now passes through iterates with it
    lv = levels_by_name(cg)
    assert lv["group#member"] == lv["folder#viewer"] == lv["folder#view"] == 0
    assert lv["folder#banned"] < 0
    assert check(e, "b", "u0") and check(e, "b", "u3")
    got, _ = whole_state(e, cg)
    want, _ = whole_state(e, flat(cg))
    assert np.array_equal(got, want)
    assert_matches_oracle(e)


# ---------------------------------------------------------------------------
# (d) the mesh reads the same schedule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dense", [False, True], ids=["sparse", "dense"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_mesh_program_equals_the_single_chip_program(case, dense,
                                                         monkeypatch):
    from spicedb_kubeapi_proxy_tpu.parallel import ShardedGraph, make_mesh

    e = build(case, 2, dense, monkeypatch)
    cg = e.compiled()
    assert cg.n_pre >= 1
    sg = ShardedGraph(cg, make_mesh(4, data=2, graph=2))
    assert len(sg._level_edges) == cg.n_pre + cg.n_levels + 1
    for context in CONTEXTS.get(case, (None,)):
        want, trips = whole_state(e, cg, NOW, context)
        got, mesh_trips = whole_state(e, sg, NOW, context)
        assert np.array_equal(got, want), np.argwhere(got != want)[:5]
        assert mesh_trips == trips


# ---------------------------------------------------------------------------
# (e) edges out of a ``__self`` range are looked up from the seeds
# ---------------------------------------------------------------------------

SEEDED = """
use expiration
caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) { ip in allowed }
definition user {}
definition group {
  relation member: user | user:* | user with expiration
    | user with ip_allowlist | group#member
}
definition namespace {
  relation parent: namespace
  relation viewer: user | user:* | group#member
  relation banned: user
  permission view = (viewer - banned) + parent->view
}
definition pod {
  relation namespace: namespace
  relation creator: user
  permission view = creator + namespace->view
}
"""
WIDE_USERS, WIDE_GROUPS, WIDE_NS = 96, 40, 24


def seeded_graph(rng):
    """Wide enough for the table to be the shorter way at 1 and 8 rows:
    every user in 1-3 groups (a third of the memberships expiring,
    expired or caveated), ``user:*`` a member of 24 groups (one run
    longer than any user's), grants, bans and creators out of ``user``,
    parents and pods out of ``namespace``."""
    def stamp(t):
        return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t))

    traits = ("", "", "", "", f"[expiration:{stamp(NOW + 3600)}]",
              f"[expiration:{stamp(NOW - 3600)}]",
              '[ip_allowlist:{"allowed":["10.0.0.0/8"]}]')
    member = {}
    for u in range(WIDE_USERS):
        for g in rng.choice(WIDE_GROUPS, size=rng.integers(1, 4),
                            replace=False):
            member[(int(g), u)] = traits[rng.integers(len(traits))]
    ops = {f"group:g{g}#member@user:u{u}{t}" for (g, u), t in member.items()}
    ops |= {f"group:g{g}#member@user:*" for g in range(24)}
    ops |= {f"group:g{p}#member@group:g{c}#member"
            for c, p in _tree(rng, WIDE_GROUPS)}
    for n in range(WIDE_NS):
        ops.add(f"namespace:n{n}#viewer@group:g{rng.integers(WIDE_GROUPS)}"
                "#member")
        ops.add(f"namespace:n{n}#viewer@user:u{rng.integers(WIDE_USERS)}")
        ops.add(f"namespace:n{n}#banned@user:u{rng.integers(WIDE_USERS)}")
    ops.add("namespace:n3#viewer@user:*")
    ops |= {f"namespace:n{c}#parent@namespace:n{p}"
            for c, p in _tree(rng, WIDE_NS)}
    for p in range(60):
        ops.add(f"pod:p{p}#namespace@namespace:n{rng.integers(WIDE_NS)}")
        ops.add(f"pod:p{p}#creator@user:u{rng.integers(WIDE_USERS)}")
    return ops


@pytest.fixture(scope="module")
def seeded_engines(deployments):
    """name -> (engine, subjects, contexts): the wide random graph on
    three seeds, and the two device-bound deployments at rehearsal
    size."""
    cache: dict = {}

    def load(name):
        if name in cache:
            return cache[name]
        if name.startswith("wide"):
            e = Engine(schema=parse_schema(SEEDED))
            e.write_relationships(touch(*sorted(seeded_graph(
                np.random.default_rng(int(name[4:]))))))
            subjects = [("user", f"u{u}", None) for u in (0, 1, 17, 95)] + [
                ("group", "g0", "member"),    # a userset subject
                ("user", "nobody", None),     # unknown: the trash slot
                ("namespace", "n0", None)]    # seeds another __self range
            contexts = ({"ip": "10.0.0.5"}, {"ip": "8.8.8.8"})
        else:
            dep, _ = deployments(name)
            e = Engine(dep.text("bootstrap.yaml"))
            e.bulk_load(dep.columns())
            subjects = [("user", f"u{u}", None) for u in (0, 3, 50)] + [
                ("group", "g0", "member"), ("user", "nobody", None),
                ("namespace", "ns0", None)]
            contexts = (None,)
        e.compiled()
        cache[name] = e, subjects, contexts
        return cache[name]

    return load


def state_by_rows(e, backend, subjects, rows, now, context):
    """Every slot of ``V`` for every subject, ``rows`` subjects (padded
    with the last) a dispatch."""
    cg = e.compiled()
    objs = e._objects_by_name()
    seeds = np.asarray([cg.encode_subject(t, i, r, objs)
                        for t, i, r in subjects], dtype=np.int32)
    q = np.tile(np.arange(cg.M, dtype=np.int32), rows)
    qb = np.repeat(np.arange(rows, dtype=np.int32), cg.M)
    out = []
    for at in range(0, len(seeds), rows):
        chunk = seeds[at:at + rows]
        chunk = np.concatenate(
            [chunk, np.repeat(chunk[-1:], rows - len(chunk), axis=0)])
        got = backend.query_async(chunk, q, qb, now=now,
                                  context=context).result()
        out.append(got.reshape(rows, cg.M)[:len(seeds) - at])
    return np.concatenate(out)


def seed_counters() -> tuple:
    return (metrics.counter("engine_seed_lookups_total").value,
            metrics.counter("engine_seed_walks_total").value)


@pytest.mark.parametrize("rows,mode", [(1, "lookup"), (8, "lookup"),
                                       (64, "walk")],
                         ids=["B1", "B8", "B64-walks"])
@pytest.mark.parametrize("name", ["wide1", "wide2", "wide3",
                                  "nested-org-1m", "ns-tree-10hop"])
def test_seeded_program_equals_the_plain_loop_and_the_oracle(
        name, rows, mode, seeded_engines):
    """A user, a userset subject, an unknown subject and a subject of
    another type, at 1 and 8 rows a dispatch (the table) and at 64 (the
    same edges walked): every slot equals one plain loop over every
    edge; then the engine's own answers against the oracle."""
    e, subjects, contexts = seeded_engines(name)
    cg = e.compiled()
    assert cg.seed_edges() > 0 and cg.seed_mode(rows) == mode
    dispatches = -(-len(subjects) // rows)
    for context in contexts:
        before = seed_counters()
        got = state_by_rows(e, cg, subjects, rows, NOW, context)
        after = seed_counters()
        assert (after[0] - before[0], after[1] - before[1]) == (
            (dispatches, 0) if mode == "lookup" else (0, dispatches))
        want = state_by_rows(e, flat(cg), subjects, rows, NOW, context)
        assert np.array_equal(got, want), np.argwhere(got != want)[:5]
        assert got[0].sum() > 2  # more than its own two seeds
    if rows == 1:
        o = e.oracle(now=NOW, context=contexts[0])
        snap = e.store.snapshot()

        def first(tname, n):
            it = snap.objects.get(snap.types.lookup(tname), ())
            return [it.string(i) for i in range(2, min(len(it), 2 + n))]

        items = [CheckItem(rt, rid, "view", t, i, r)
                 for t, i, r in subjects
                 for rt, n in (("namespace", 60), ("pod", 20))
                 for rid in first(rt, n)]
        got = e.check_bulk(items, now=NOW, context=contexts[0])
        want = [o.check(i.resource_type, i.resource_id, i.permission,
                        i.subject_type, i.subject_id, i.subject_relation)
                for i in items]
        assert got == want and True in want and False in want


def test_a_long_run_stays_walked_and_leaves_the_fanout_alone(
        seeded_engines):
    """``user:*`` is a member of 24 groups and a viewer of one namespace
    (all entry edges: both ranges iterate), no user holds more than a
    handful: the entry phase's fan-out is what the users need, the
    wildcard's run lies in the walked part, and every user still gets
    it."""
    e, _, _ = seeded_engines("wide1")
    cg = e.compiled()
    meta = cg.run_meta()
    lo, mid, hi = meta.parts(-1)
    assert cg.seed.fanout[cg.n_pre - 1] == 8
    star = cg.slot_offset[("user", reachability.SELF_REL)] \
        + reachability.WILDCARD_IDX
    assert np.count_nonzero(cg.res_src[lo:mid] == star) == 25
    assert not np.any(cg.res_src[mid:hi] == star)
    runs = np.unique(cg.res_src[mid:hi][cg.res_dst[mid:hi] != cg.M],
                     return_counts=True)[1]
    assert 0 < runs.max() <= 8 and len(runs) > 64
    assert reachability._seed_fanout(np.append(runs, 24)) \
        == reachability._seed_fanout(runs) == 8
    # several long runs pay for themselves; one never does
    assert reachability._seed_fanout(np.asarray([3] * 50 + [24] * 5)) == 32
    assert reachability._seed_fanout(np.asarray([3] * 50 + [3000])) == 8
    # the wildcard's grant reaches any user, even one the store never saw
    assert e.check_bulk([CheckItem("group", "g5", "member", "user", "x")],
                        now=NOW) == [True]
    assert e.check_bulk([CheckItem("group", "g30", "member", "user", "x")],
                        now=NOW) == [False]


def test_expiring_and_caveated_edges_out_of_self_follow_the_one_rule(
        seeded_engines):
    """The table reads the activation array the walk reads: an expired
    membership is off, a caveated one follows the request's context,
    both through the lookup (one row) and through the walk (64)."""
    e, _, _ = seeded_engines("wide2")
    cg = e.compiled()
    lo, mid, hi = cg.run_meta().parts(-1)
    exp, cav = cg.res_exp[mid:hi], cg.res_cav[mid:hi]
    real = cg.res_dst[mid:hi] != cg.M
    assert np.any(real & np.isfinite(exp)) and np.any(real & (cav != 0))
    users = [("user", f"u{u}", None) for u in range(WIDE_USERS)]
    seen = []
    for rows in (1, 64):
        per_ctx = [state_by_rows(e, cg, users, rows, NOW, c)
                   for c in ({"ip": "10.0.0.5"}, {"ip": "8.8.8.8"})]
        late = state_by_rows(e, cg, users, rows, NOW + 7200,
                             {"ip": "10.0.0.5"})
        seen.append(per_ctx + [late])
        # the allowlisted address sees more, the later clock fewer
        assert per_ctx[0].sum() > per_ctx[1].sum()
        assert per_ctx[0].sum() > late.sum()
    for a, b in zip(*seen):
        assert np.array_equal(a, b)

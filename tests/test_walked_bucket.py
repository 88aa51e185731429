"""The padding of a phase's walked part (``ops/reachability._walked_bucket``).

Every dispatch gathers and scatters a phase's walked part whole, padding
included, so the part is padded to an eighth of an octave from
``WALKED_FINE_MIN`` edges up and to a power of two below; the seeded
part, looked up and not walked, stays on powers of two. Held here: the
ladder itself; the layout ``compile_graph`` bakes from it, on a nested
group graph whose loop and level walk more than the threshold; that the
engine's answers on that layout equal the oracle's, through an expiring
edge and a write that rides the delta overlay; and that the benchmark's
deployments, at full size, compile to one layout at three seeds.
"""

import time

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.models import parse_schema
from spicedb_kubeapi_proxy_tpu.models.tuples import Relationship
from spicedb_kubeapi_proxy_tpu.ops.reachability import (
    WALKED_FINE_MIN,
    _next_bucket,
    _walked_bucket,
)
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics

from test_recursive_arrow import _bench_module

SWEEP = sorted({1, 2, 7, 8, 9, 100, 1000, 2048, 2049, 4095, 4096, 4097,
                4607, 4608, 4609, 5000, 8191, 8192, 8193, 9900, 11000,
                20000, 22000, 98207, 98564, 150011, 200000, 262143,
                262144, 262145, 1 << 20, (1 << 20) + 1, 3_000_001})


@pytest.mark.parametrize("n", SWEEP)
def test_a_walked_part_pads_to_its_rung_of_the_ladder(n):
    b = _walked_bucket(n)
    assert b >= n
    if n < WALKED_FINE_MIN:
        assert b == _next_bucket(n)
    else:
        assert b % 512 == 0
        assert b * 8 < n * 9  # under an eighth of padding


@pytest.mark.parametrize("k", range(3, 23))
def test_an_octave_holds_at_most_eight_rungs(k):
    lo, hi = 1 << k, 1 << (k + 1)
    ns = list(range(lo - 1, hi + 2, max(1, lo >> 10)))
    ns += [m * (lo >> 3) + d for m in range(8, 17) for d in (-1, 0, 1)]
    ns = sorted(n for n in set(ns) if n >= 1)
    got = [_walked_bucket(n) for n in ns]
    assert got == sorted(got)  # monotone
    rungs = {b for b in got if lo <= b < hi}
    assert 1 <= len(rungs) <= (8 if lo >= WALKED_FINE_MIN else 1)


# ---------------------------------------------------------------------------
# A nested group graph whose loop and level walk more than the threshold
# ---------------------------------------------------------------------------

SCHEMA = """
use expiration

definition user {}
definition group { relation member: user | group#member | group#member with expiration }
definition namespace {
  relation viewer: group#member | group#member with expiration
  permission view = viewer
}
"""

LEAF, MID, TOP, USERS, NAMESPACES = 10_000, 1_000, 100, 20_000, 20_000
NOW = time.time()
LATER = NOW + 200.0  # past every expiring edge's expiration


def group(i: int) -> str:
    return (f"g2-{i}" if i < LEAF else f"g1-{i - LEAF}" if i < LEAF + MID
            else f"g0-{i - LEAF - MID}")


@pytest.fixture(scope="module")
def nested():
    """Leaf groups of users in mid groups in top groups, each namespace
    viewed by one top group: 20,000 user edges (seeded), 11,000 group in
    group edges (the loop), 20,000 viewer edges (the level after it).
    One in twenty of the nested and of the viewer edges expires between
    ``NOW`` and ``LATER``."""
    rng = np.random.default_rng(46)
    mid_of_leaf = LEAF + rng.integers(MID, size=LEAF)
    top_of_mid = LEAF + MID + rng.integers(TOP, size=MID)
    top_of_ns = LEAF + MID + rng.integers(TOP, size=NAMESPACES)
    leaf_of_user = rng.integers(LEAF, size=USERS)
    rows = ([("group", group(g), "member", "user", f"u{u}", "")
             for u, g in enumerate(leaf_of_user.tolist())]
            + [("group", group(m), "member", "group", group(g), "member")
               for g, m in enumerate(mid_of_leaf.tolist())]
            + [("group", group(t), "member", "group", group(LEAF + m),
                "member") for m, t in enumerate(top_of_mid.tolist())]
            + [("namespace", f"ns{n}", "viewer", "group", group(t),
                "member") for n, t in enumerate(top_of_ns.tolist())])
    cols = dict(zip(("resource_type", "resource_id", "relation",
                     "subject_type", "subject_id", "subject_relation"),
                    (np.asarray(c) for c in zip(*rows))))
    expiring = (np.arange(len(rows)) >= USERS) \
        & (rng.random(len(rows)) < 0.05)
    cols["expiration"] = np.where(expiring, NOW + 100.0, np.nan)
    e = Engine(schema=parse_schema(SCHEMA))
    e.bulk_load(cols)
    assert not e.compiled().blocks  # every edge on the walked path
    lasting_ns = ~expiring[-NAMESPACES:]
    return e, leaf_of_user, top_of_ns, lasting_ns


def phase_parts(cg) -> list:
    """Per phase: (walked padded, walked real, seeded padded, seeded
    real)."""
    b, st = cg.res_level_bounds, cg.seed.starts
    return [(st[i] - b[i], int((cg.res_dst[b[i]:st[i]] != cg.M).sum()),
             b[i + 1] - st[i], int((cg.res_dst[st[i]:b[i + 1]] != cg.M).sum()))
            for i in range(len(b) - 1)]


def test_the_slices_follow_the_ladder_and_the_gauges_say_so(nested):
    e = nested[0]
    cg = e.compiled()
    parts = phase_parts(cg)
    for walked, real, seeded, seeded_real in parts:
        assert walked == _walked_bucket(max(real, 1))
        assert seeded == (_next_bucket(seeded_real) if seeded_real else 0)
    meta = cg.run_meta()
    core = parts[cg.n_pre]
    level1 = parts[cg.n_pre + 1]
    # 11,000 loop edges on 11,264 (a power of two: 16,384), 20,000
    # viewer edges on 20,480 (32,768); the users' edges are seeded
    assert core[:2] == (11_264, LEAF + MID)
    assert level1[:2] == (20_480, NAMESPACES)
    assert meta.parts(0)[1] - meta.parts(0)[0] == 11_264
    entry = parts[cg.n_pre - 1]
    assert entry == (8, 0, _next_bucket(USERS), USERS)
    walked = sum(p[0] for p in parts)
    real = sum(p[1] for p in parts)
    assert cg.walked_edges() == walked
    assert cg.walked_padding_edges() == walked - real
    assert metrics.gauge("engine_walked_edges").value == walked
    assert metrics.gauge("engine_walked_padding_edges").value \
        == walked - real == 264 + 480 + 8 * (len(parts) - 2)


def test_lookups_and_checks_equal_the_oracle_through_expiry_and_overlay(
        nested):
    e, leaf_of_user, top_of_ns, lasting_ns = nested
    users = [f"u{u}" for u in (0, 1, 2, 3, 4, 5)]
    namespaces = [f"ns{n}" for n in range(0, NAMESPACES, 97)]
    groups = [group(g) for g in (0, 1, LEAF, LEAF + 1, LEAF + MID)]

    def agree(now):
        o = e.oracle(now=now)
        items = [CheckItem("namespace", n, "view", "user", u)
                 for u in users for n in namespaces] \
            + [CheckItem("group", g, "member", "user", u)
               for u in users for g in groups]
        want = [o.check(i.resource_type, i.resource_id, i.permission,
                        i.subject_type, i.subject_id) for i in items]
        assert e.check_bulk(items, now=now) == want
        # a lookup names what it names, and no sampled namespace more
        # (the oracle's own lookup walks all 20,000 at seconds a user)
        seen = []
        for u in users:
            got = set(e.lookup_resources("namespace", "view", "user", u,
                                         now=now))
            assert got == {n for n in sorted(got | set(namespaces))
                           if o.check("namespace", n, "view", "user", u)}
            seen.append(len(got))
        return seen

    before = agree(NOW)
    assert all(before)  # every user sees some namespaces
    after = agree(LATER)
    assert sum(after) < sum(before)  # the expired edges took some away

    # a write between existing objects rides the delta overlay: the
    # first user's leaf group joins, directly, the top group that views
    # a namespace the user could not see
    compiles = metrics.counter("engine_graph_compiles_total").value
    updates = metrics.counter("engine_graph_incremental_updates_total").value
    o = e.oracle(now=LATER)
    blind = next(n for n in range(0, NAMESPACES, 97) if lasting_ns[n]
                 and not o.check("namespace", f"ns{n}", "view", "user", "u0"))
    e.write_relationships([WriteOp("touch", Relationship(
        "group", group(int(top_of_ns[blind])), "member", "group",
        group(int(leaf_of_user[0])), "member"))])
    assert e.check(CheckItem("namespace", f"ns{blind}", "view", "user",
                             "u0"), now=LATER)
    agree(LATER)
    assert metrics.counter("engine_graph_compiles_total").value == compiles
    assert metrics.counter(
        "engine_graph_incremental_updates_total").value > updates


# ---------------------------------------------------------------------------
# The benchmark's deployments at full size: one layout at three seeds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,walked", [
    ("nested-org-1m", 235_536), ("ns-tree-10hop", 194_584),
    ("multi-tenant-100k", None)])
def test_full_size_layouts_agree_across_three_seeds(name, walked):
    """``nested-org-1m`` and ``ns-tree-10hop`` walk parts above the
    threshold, whose real edge counts do not move with the seed, so
    neither do their rungs; ``multi-tenant-100k``'s seeded part
    (98,207–98,564 edges at these seeds) straddles 98,304 = 3 * 2^15,
    where a finer ladder would give two seeds two programs. (A part
    below the threshold may still move: ``ns-tree-10hop``'s ``level2``
    walks 0 or 34 edges, 8 or 64 padded, by seed, as it did before.)
    Two to four seconds a graph on a CPU."""
    deployment = _bench_module("deployment").Deployment
    layouts = set()
    for seed in (1, 2, 3):
        dep = deployment(name, seed)
        e = Engine(dep.text("bootstrap.yaml"))
        e.bulk_load(dep.columns())
        cg = e.compiled()
        layouts.add((cg.res_level_bounds, cg.seed.starts, cg.seed.fanout))
        if walked is not None:
            assert cg.walked_edges() == walked
    assert len(layouts) == 1, layouts

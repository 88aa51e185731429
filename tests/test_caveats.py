"""Device-side caveat evaluation (ISSUE 9): the vectorized expression VM
vs the pure-Python AST interpreter (randomized differential), tri-state
missing-context semantics, expiry interaction, tuple-context round-trip
properties, decision-cache context digests, incremental caveated writes,
the remote wire's ctx field, and the end-to-end IP-allowlist /
time-window scenarios through the proxy middleware."""

import asyncio
import json
import random
import time

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.authz import AuthzDeps, authorize
from spicedb_kubeapi_proxy_tpu.caveats.ast import (
    Bin,
    CaveatDef,
    CaveatError,
    CaveatParam,
    CaveatType,
    Lit,
    StringInterner,
    Un,
    Var,
    interpret,
    parse_caveat_body,
)
from spicedb_kubeapi_proxy_tpu.caveats.compile import compile_caveat
from spicedb_kubeapi_proxy_tpu.caveats.vm import (
    build_caveat_table,
    eval_caveats,
)
from spicedb_kubeapi_proxy_tpu.engine import CheckItem, Engine, WriteOp
from spicedb_kubeapi_proxy_tpu.engine.engine import SchemaViolation
from spicedb_kubeapi_proxy_tpu.models.bootstrap import parse_bootstrap
from spicedb_kubeapi_proxy_tpu.models.schema import (
    SchemaError,
    parse_schema,
)
from spicedb_kubeapi_proxy_tpu.models.tuples import (
    Relationship,
    TupleError,
    canonical_context,
    parse_relationship,
)
from spicedb_kubeapi_proxy_tpu.proxy.requestinfo import parse_request_info
from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyRequest, json_response
from spicedb_kubeapi_proxy_tpu.rules import MapMatcher
from spicedb_kubeapi_proxy_tpu.rules.input import UserInfo
from spicedb_kubeapi_proxy_tpu.utils.metrics import metrics


# -- grammar / compiler -------------------------------------------------------


def test_parse_precedence_and_fold():
    e = parse_caveat_body("1 + 2 * 3 == 7 && !(false)")
    d = CaveatDef("t", (), e)
    prog = compile_caveat(d, StringInterner())
    # fully constant: folds to one CONST true
    assert len(prog.ops) == 1
    i = StringInterner()
    assert interpret(e, {}, {}, i) is True


def test_compiler_rejects_malformed():
    p_str = CaveatParam("day", CaveatType("string"))
    p_list = CaveatParam("tags", CaveatType("list", "string"))
    for body, params in [
        ("day + 1 == 2", (p_str,)),       # string arithmetic
        ("day < 'x'", (p_str,)),          # ordered string comparison
        ("tags == tags", (p_list,)),      # list outside 'in'
        ("nope == 1", ()),                # unknown parameter
        ("1 + 1", ()),                    # non-boolean body
        ("day in day", (p_str,)),         # 'in' needs a list rhs
    ]:
        with pytest.raises(CaveatError):
            compile_caveat(
                CaveatDef("t", params, parse_caveat_body(body)),
                StringInterner())


def test_schema_parses_typed_caveats_and_validates():
    s = parse_schema("""
    caveat ipal(ip ipaddress, allowed list<ipaddress>) { ip in allowed }
    caveat win(now timestamp, start timestamp, end timestamp) {
      now >= start && now < end
    }
    definition user {}
    definition doc {
      relation viewer: user | user with ipal
      permission view = viewer
    }
    """)
    assert set(s.caveat_defs) == {"ipal", "win"}
    ipal = s.caveat_defs["ipal"]
    assert [str(p.type) for p in ipal.params] == \
        ["ipaddress", "list<ipaddress>"]
    with pytest.raises(SchemaError, match="duplicate caveat"):
        parse_schema("caveat c(a int) { a == 1 }\n"
                     "caveat c(b int) { b == 1 }\ndefinition user {}")
    with pytest.raises(SchemaError):  # malformed body fails the PARSE
        parse_schema("caveat c(day string) { day + 1 == 2 }\n"
                     "definition user {}")
    with pytest.raises(SchemaError, match="parameter type"):
        parse_schema("caveat c(x frobnicator) { true }\ndefinition u {}")


# -- randomized differential: VM vs interpreter -------------------------------

_BASE_TS = 1_700_000_000.0

_PARAMS = (
    CaveatParam("a", CaveatType("int")),
    CaveatParam("b", CaveatType("int")),
    CaveatParam("day", CaveatType("string")),
    CaveatParam("ip", CaveatType("ipaddress")),
    CaveatParam("allowed", CaveatType("list", "ipaddress")),
    CaveatParam("tags", CaveatType("list", "string")),
    CaveatParam("now", CaveatType("timestamp")),
    CaveatParam("start", CaveatType("timestamp")),
)


def _gen_num(rng, depth):
    if depth <= 0 or rng.random() < 0.4:
        if rng.random() < 0.5:
            return Lit(float(rng.randint(-40, 40)), "double")
        return Var(rng.choice(["a", "b"]))
    op = rng.choice(["+", "-", "*"])
    return Bin(op, _gen_num(rng, depth - 1), _gen_num(rng, depth - 1))


def _gen_bool(rng, depth):
    r = rng.random()
    if depth <= 0 or r < 0.25:
        kind = rng.randrange(5)
        if kind == 0:
            return Bin(rng.choice(["==", "!=", "<", "<=", ">", ">="]),
                       _gen_num(rng, 1), _gen_num(rng, 1))
        if kind == 1:
            return Bin("==", Var("day"),
                       Lit(rng.choice(["mon", "tue", "wed"]), "string"))
        if kind == 2:
            return Bin("in", Var("ip"), Var("allowed"))
        if kind == 3:
            return Bin("in", Var("day"), Var("tags"))
        return Bin(rng.choice(["<", ">=", "=="]), Var("now"),
                   Var("start"))
    if r < 0.4:
        return Un("!", _gen_bool(rng, depth - 1))
    return Bin(rng.choice(["&&", "||"]),
               _gen_bool(rng, depth - 1), _gen_bool(rng, depth - 1))


def _rand_ctx(rng, full=False):
    ctx = {}
    p = 1.0 if full else 0.65

    def coin():
        return rng.random() < p

    if coin():
        ctx["a"] = rng.randint(-40, 40)
    if coin():
        ctx["b"] = rng.randint(-40, 40)
    if coin():
        ctx["day"] = rng.choice(["mon", "tue", "wed", "thu"])
    if coin():
        ctx["ip"] = "10.%d.%d.%d" % (rng.randrange(3), rng.randrange(3),
                                     rng.randrange(4))
    if coin():
        ctx["allowed"] = rng.sample(
            ["10.0.0.0/24", "10.1.0.0/16", "10.2.2.2", "10.0.1.3"],
            k=rng.randint(1, 3))
    if coin():
        ctx["tags"] = rng.sample(["mon", "tue", "xyz"],
                                 k=rng.randint(1, 2))
    if coin():
        ctx["now"] = _BASE_TS + rng.randint(-500, 500)
    if coin():
        ctx["start"] = _BASE_TS + rng.randint(-500, 500)
    return ctx


def test_vm_matches_interpreter_randomized():
    """The acceptance differential: for random expressions, random
    tuple contexts, and random request contexts, the vectorized VM's
    per-instance tri-state equals the scalar interpreter's — allow,
    deny, AND missing-context."""
    rng = random.Random(20260803)
    params = {p.name: p.type for p in _PARAMS}
    for trial in range(10):
        expr = _gen_bool(rng, 3)
        defn = CaveatDef("c", _PARAMS, expr)
        tuple_ctxs = [_rand_ctx(rng) for _ in range(6)]
        inst = [("", "")] + [
            ("c", canonical_context(c) or "") for c in tuple_ctxs]
        table = build_caveat_table({"c": defn}, inst,
                                   np.arange(1, len(inst)))
        stat = table.device_static()
        for _ in range(4):
            req_ctx = _rand_ctx(rng)
            req_ctx.setdefault("now", _BASE_TS)  # symmetric injection
            req, _ts = table.encode_request(req_ctx, _BASE_TS)
            ok, missing = eval_caveats(table.metas, stat, req,
                                       table.n_rows)
            ok = np.asarray(ok)
            n_missing = 0
            for i, tctx in enumerate(tuple_ctxs):
                merged = dict(req_ctx)
                merged.update(tctx)  # tuple context wins
                want = interpret(expr, merged, params, table.interner)
                row = int(table.inst_row[1 + i])
                got_allow = bool(ok[row])
                assert got_allow == (want is True), (
                    f"trial {trial}: expr {expr} ctx {merged} "
                    f"want {want} got allow={got_allow}")
                if want is None:
                    n_missing += 1
            assert int(missing) == n_missing


def test_division_by_zero_is_missing_context():
    params = (CaveatParam("a", CaveatType("int")),
              CaveatParam("b", CaveatType("int")))
    defn = CaveatDef("d", params, parse_caveat_body("a / b >= 1"))
    inst = [("", ""), ("d", canonical_context({"a": 4}))]
    table = build_caveat_table({"d": defn}, inst, np.array([1]))
    stat = table.device_static()
    pmap = {p.name: p.type for p in params}
    for b, want in [(2, True), (8, False), (0, None)]:
        req, _ = table.encode_request({"b": b}, 0.0)
        ok, missing = eval_caveats(table.metas, stat, req, table.n_rows)
        assert bool(np.asarray(ok)[1]) == (want is True)
        assert int(missing) == (1 if want is None else 0)
        assert interpret(defn.expr, {"a": 4, "b": b}, pmap,
                         table.interner) is want


def test_unseen_request_strings_never_compare_equal():
    """Two DIFFERENT strings that appear in no tuple context or literal
    must get DISTINCT codes — a shared match-all sentinel would make
    `user == owner` grant for arbitrary non-matching values (fail
    open). Review finding regression."""
    params = (CaveatParam("user", CaveatType("string")),
              CaveatParam("owner", CaveatType("string")))
    defn = CaveatDef("own", params, parse_caveat_body("user == owner"))
    inst = [("", ""), ("own", "")]  # context-free instance: both
    #                                 parameters come from the request
    table = build_caveat_table({"own": defn}, inst, np.array([1]))
    stat = table.device_static()
    pmap = {p.name: p.type for p in params}
    for ctx, want in [({"user": "mallory", "owner": "prod"}, False),
                      ({"user": "same", "owner": "same"}, True)]:
        req, _ = table.encode_request(ctx, 0.0)
        ok, _m = eval_caveats(table.metas, stat, req, table.n_rows)
        assert bool(np.asarray(ok)[1]) is want, ctx
        assert interpret(defn.expr, ctx, pmap, table.interner) is want
    # membership over unseen strings: no cross-matching either
    defn2 = CaveatDef("mem", (CaveatParam("u", CaveatType("string")),
                              CaveatParam("us", CaveatType("list",
                                                           "string"))),
                      parse_caveat_body("u in us"))
    t2 = build_caveat_table({"mem": defn2}, [("", ""), ("mem", "")],
                            np.array([1]))
    s2 = t2.device_static()
    req, _ = t2.encode_request({"u": "eve", "us": ["adam", "bob"]}, 0.0)
    ok, _m = eval_caveats(t2.metas, s2, req, t2.n_rows)
    assert not bool(np.asarray(ok)[1])
    req, _ = t2.encode_request({"u": "bob", "us": ["adam", "bob"]}, 0.0)
    ok, _m = eval_caveats(t2.metas, s2, req, t2.n_rows)
    assert bool(np.asarray(ok)[1])


def test_literal_cidr_list_engine_oracle_parity():
    """A CONSTANT CIDR allowlist in the caveat body (not a parameter)
    must evaluate as IP ranges in both the VM and the oracle
    interpreter. Review finding regression (the oracle used to compare
    interner codes)."""
    e = Engine(bootstrap="""
schema: |-
  caveat vpn_only(ip ipaddress) { ip in ["10.8.0.0/16", "172.16.0.9"] }
  definition user {}
  definition doc {
    relation viewer: user with vpn_only
    permission view = viewer
  }
relationships: |-
  doc:d#viewer@user:u[vpn_only]
""")
    u = CheckItem("doc", "d", "view", "user", "u")
    for ip, want in [("10.8.3.4", True), ("10.9.0.1", False),
                     ("172.16.0.9", True), ("172.16.0.8", False),
                     ("0.0.0.3", False)]:
        ctx = {"ip": ip}
        got = e.check(u, context=ctx)
        assert got is want, (ip, got)
        assert e.oracle(context=ctx).check(
            "doc", "d", "view", "user", "u") is want, ip


def test_request_list_capacity_floor():
    """Request-only list parameters (no tuple-side sizing signal, e.g.
    the middleware's `groups`) must accept realistic lengths instead of
    silently going missing-context at 5 elements."""
    defn = CaveatDef(
        "grp", (CaveatParam("team", CaveatType("string")),
                CaveatParam("groups", CaveatType("list", "string"))),
        parse_caveat_body("team in groups"))
    table = build_caveat_table({"grp": defn}, [("", ""), ("grp",
                               canonical_context({"team": "g7"}))],
                               np.array([1]))
    stat = table.device_static()
    groups = [f"g{i}" for i in range(12)]  # > the old floor of 4
    req, _ = table.encode_request({"groups": groups}, 0.0)
    ok, missing = eval_caveats(table.metas, stat, req, table.n_rows)
    assert bool(np.asarray(ok)[1]) and int(missing) == 0


# -- engine: tri-state, expiry interaction, metrics ---------------------------

IP_BOOT = """
schema: |-
  use expiration
  caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }
  definition user {}
  definition doc {
    relation viewer: user | user with ip_allowlist and expiration
    permission view = viewer
  }
relationships: |-
  doc:readme#viewer@user:alice
  doc:readme#viewer@user:bob[ip_allowlist:{"allowed":["10.0.0.0/8"]}]
"""


def test_missing_context_fails_closed_and_counts():
    e = Engine(bootstrap=IP_BOOT)
    c0 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    bob = CheckItem("doc", "readme", "view", "user", "bob")
    assert not e.check(bob)  # no ip: fail closed
    assert metrics.counter(
        "engine_caveat_denied_missing_context_total").value > c0
    assert e.check(bob, context={"ip": "10.2.3.4"})
    assert not e.check(bob, context={"ip": "11.2.3.4"})
    # context with a malformed value is missing context, not an error
    assert not e.check(bob, context={"ip": "not-an-ip"})


def test_caveat_and_expiry_interaction():
    e = Engine(bootstrap=IP_BOOT)
    soon = time.time() + 0.8
    rel = Relationship("doc", "readme", "viewer", "user", "carol", None,
                       soon, "ip_allowlist",
                       canonical_context({"allowed": ["10.0.0.0/8"]}))
    e.write_relationships([WriteOp("touch", rel)])
    carol = CheckItem("doc", "readme", "view", "user", "carol")
    ctx = {"ip": "10.1.1.1"}
    # live + satisfying context -> allow; live + missing -> deny
    assert e.check(carol, now=soon - 0.5, context=ctx)
    assert not e.check(carol, now=soon - 0.5)
    # expired -> deny even with a satisfying context
    assert not e.check(carol, now=soon + 0.5, context=ctx)
    # oracle agrees on every cell
    for now, c in [(soon - 0.5, ctx), (soon - 0.5, None),
                   (soon + 0.5, ctx)]:
        o = e.oracle(now=now, context=c)
        assert o.check("doc", "readme", "view", "user", "carol") == \
            e.check(carol, now=now, context=c)


def test_prefiltered_lookup_and_lookup_subjects_with_context():
    e = Engine(bootstrap=IP_BOOT)
    assert e.lookup_resources("doc", "view", "user", "bob",
                              context={"ip": "10.0.0.1"}) == ["readme"]
    assert e.lookup_resources("doc", "view", "user", "bob",
                              context={"ip": "172.16.0.1"}) == []
    subs = e.lookup_subjects("doc", "readme", "view", "user",
                             context={"ip": "10.0.0.1"})
    assert subs == ["alice", "bob"]
    subs = e.lookup_subjects("doc", "readme", "view", "user")
    assert subs == ["alice"]  # conditional grant missing context


def test_batched_lookup_counts_missing_context():
    """Context-free lookups FUSE through the batcher (the watch-hub
    recompute path): their fail-closed conditional denials must tick
    the missing-context counter like every other path."""
    from fusing import hold, release, warm
    from spicedb_kubeapi_proxy_tpu.engine.engine import mask_to_ids

    e = Engine(bootstrap=IP_BOOT)
    b = e._batcher
    warm(e, "doc")
    c0 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    f0 = metrics.counter("engine_lookup_batches_total").value
    hold(b)
    futs = [e.lookup_resources_mask_async("doc", "view", "user", u)
            for u in ("bob", "alice", "carol")]  # three wait: they fuse
    release(b, 3)
    bob, alice, carol = (mask_to_ids(*f.result()) for f in futs)
    assert metrics.counter("engine_lookup_batches_total").value == f0 + 1
    assert bob == [] and alice == ["readme"] and carol == []
    assert metrics.counter(
        "engine_caveat_denied_missing_context_total").value > c0


# -- write validation ---------------------------------------------------------


def test_write_validation_typed_contexts():
    e = Engine(bootstrap=IP_BOOT)
    # well-typed context accepted
    e.write_relationships([WriteOp("touch", parse_relationship(
        'doc:x#viewer@user:d[ip_allowlist:{"allowed":["1.2.3.4"]}]'))])
    # unknown parameter rejected
    with pytest.raises(SchemaViolation, match="no parameter"):
        e.write_relationships([WriteOp("touch", parse_relationship(
            'doc:x#viewer@user:d2[ip_allowlist:{"nope":1}]'))])
    # wrong type rejected
    with pytest.raises(SchemaViolation):
        e.write_relationships([WriteOp("touch", parse_relationship(
            'doc:x#viewer@user:d3[ip_allowlist:{"allowed":"10.0.0.1"}]'
        ))])
    # an entry REQUIRING a caveat never accepts an unconditional tuple
    e3 = Engine(schema=parse_schema("""
      caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
        ip in allowed
      }
      definition user {}
      definition doc {
        relation viewer: user with ip_allowlist
        permission view = viewer
      }
    """))
    with pytest.raises(SchemaViolation, match="does not allow"):
        e3.write_relationships([WriteOp("touch", parse_relationship(
            "doc:x#viewer@user:plain"))])


# -- tuple round-trip properties (satellite) ----------------------------------


def _rand_json_value(rng, depth=2):
    r = rng.random()
    if depth <= 0 or r < 0.45:
        return rng.choice([
            rng.randint(-10_000, 10_000),
            round(rng.uniform(-5, 5), 3),
            rng.random() < 0.5,
            "".join(rng.choice("abc]de[f:#@/.\\\" 日本") for _ in
                    range(rng.randint(0, 6))),
        ])
    if r < 0.75:
        return [_rand_json_value(rng, 0) for _ in range(rng.randint(0, 3))]
    return {f"k{i}": _rand_json_value(rng, depth - 1)
            for i in range(rng.randint(0, 3))}


def test_relationship_context_round_trip_property():
    """parse ∘ format == identity for caveated relationships with
    arbitrary JSON contexts (nested brackets, escapes, unicode) — the
    satellite: JSON-array contexts used to parse leniently but not
    serialize back losslessly."""
    rng = random.Random(7)
    for _ in range(120):
        ctx = {f"p{i}": _rand_json_value(rng)
               for i in range(rng.randint(0, 3))}
        rel = Relationship(
            "doc", "x", "viewer", "user", "u", None,
            1893456000.0 if rng.random() < 0.3 else None,
            "some_caveat", canonical_context(ctx))
        back = parse_relationship(str(rel))
        assert back == rel, (str(rel), back)
        # format ∘ parse ∘ format is idempotent
        assert str(parse_relationship(str(back))) == str(rel)


def test_canonical_context_normalizes():
    a = canonical_context({"b": 1, "a": [2, 3]})
    b = canonical_context('{"a": [2, 3], "b": 1}')
    assert a == b == '{"a":[2,3],"b":1}'
    assert canonical_context(None) is None
    assert canonical_context("") is None
    assert canonical_context({}) is None
    with pytest.raises(TupleError):
        canonical_context("[1, 2]")  # not an object
    with pytest.raises(TupleError):
        canonical_context("{nope")


def test_caveat_survives_snapshot_and_watch_log(tmp_path):
    e = Engine(bootstrap=IP_BOOT)
    path = str(tmp_path / "s.npz")
    e.save_snapshot(path)
    e2 = Engine(bootstrap=IP_BOOT.split("relationships")[0]
                + "relationships: ''")
    e2.load_snapshot(path)
    bob = CheckItem("doc", "readme", "view", "user", "bob")
    assert e2.check(bob, context={"ip": "10.0.0.1"})
    assert not e2.check(bob)
    # watch log round-trips the caveat fields
    rel = parse_relationship(
        'doc:z#viewer@user:w[ip_allowlist:{"allowed":["10.9.9.9"]}]')
    rev0 = e.revision
    e.write_relationships([WriteOp("touch", rel)])
    evs = e.watch_since(rev0)
    assert evs[-1].relationship.caveat == "ip_allowlist"
    assert evs[-1].relationship.caveat_context == \
        '{"allowed":["10.9.9.9"]}'


# -- decision cache: context digest + time bounds -----------------------------


def test_cache_context_digest_no_leakage():
    e = Engine(bootstrap=IP_BOOT)
    e.enable_decision_cache()
    bob = CheckItem("doc", "readme", "view", "user", "bob")
    in_ctx = {"ip": "10.0.0.1"}
    out_ctx = {"ip": "9.9.9.9"}
    # warm both contexts, then assert repeats stay correct (a digest
    # collision would leak one context's verdict into the other)
    for _ in range(3):
        assert e.check(bob, context=in_ctx)
        assert not e.check(bob, context=out_ctx)
        assert not e.check(bob)  # context-free key is its own entry
    hits = metrics.counter("engine_decision_cache_hits_total",
                           kind="check").value
    assert e.check(bob, context=in_ctx)
    assert metrics.counter("engine_decision_cache_hits_total",
                           kind="check").value > hits
    # the event-loop probe honors the digest too
    assert e.try_cached_check([bob], context=in_ctx) == [True]
    assert e.try_cached_check([bob], context=out_ctx) == [False]


def test_time_window_cache_deadline():
    """A time-window caveat revokes/grants without a write: cached
    entries must die at the window boundary, exactly like the store's
    expiration watermark."""
    now = time.time()
    start, end = now + 3600, now + 7200
    boot = f"""
schema: |-
  caveat win(now timestamp, start timestamp, end timestamp) {{
    now >= start && now < end
  }}
  definition user {{}}
  definition doc {{
    relation viewer: user with win | user
    permission view = viewer
  }}
relationships: |-
  doc:d#viewer@user:u[win:{{"end":{end},"start":{start}}}]
"""
    e = Engine(bootstrap=boot)
    u = CheckItem("doc", "d", "view", "user", "u")
    assert not e.check(u)  # before the window (auto-injected now)
    cg = e.compiled()
    assert cg.caveats.any_now
    # next verdict flip after "now" is the window start; after start,
    # the window end
    assert e._cache_deadline(cg, now, None) == pytest.approx(start)
    assert e._cache_deadline(cg, start + 1, None) == pytest.approx(end)
    assert e._cache_deadline(cg, end + 1, None) == float("inf")
    # request-supplied timestamps bound the deadline too
    d = e._cache_deadline(cg, now, {"start": now + 60.0})
    assert d == pytest.approx(now + 60.0)


def test_cache_digest_scoped_to_declared_params():
    """Only declared caveat parameters join the digest: per-request
    middleware fields (name/verb/...) must not fragment the cache when
    the graph's caveats only read `ip`. Review finding regression."""
    e = Engine(bootstrap=IP_BOOT)
    e.enable_decision_cache()
    bob = CheckItem("doc", "readme", "view", "user", "bob")
    base = {"ip": "10.0.0.1", "verb": "get", "name": "a",
            "user": "bob", "groups": []}
    assert e.check(bob, context=base)
    hits0 = metrics.counter("engine_decision_cache_hits_total",
                            kind="check").value
    # same ip, DIFFERENT request-shaped noise: must be a cache HIT
    assert e.check(bob, context={**base, "verb": "list", "name": "b"})
    assert metrics.counter("engine_decision_cache_hits_total",
                           kind="check").value > hits0
    # different ip: still its own entry (correctness)
    assert not e.check(bob, context={**base, "ip": "9.9.9.9"})


def test_bulk_load_validates_caveat_columns():
    e = Engine(bootstrap=IP_BOOT)
    ok_cols = {
        "resource_type": ["doc"], "resource_id": ["bk"],
        "relation": ["viewer"], "subject_type": ["user"],
        "subject_id": ["zed"], "caveat": ["ip_allowlist"],
        "caveat_context": ['{"allowed":["10.0.0.0/8"]}'],
    }
    e.bulk_load(ok_cols)
    assert e.check(CheckItem("doc", "bk", "view", "user", "zed"),
                   context={"ip": "10.1.1.1"})
    # an undeclared name / mistyped context must fail the LOAD, not
    # brick the next compile (review finding regression)
    with pytest.raises(SchemaViolation):
        e.bulk_load({**ok_cols, "resource_id": ["bk2"],
                     "caveat": ["ip_allowlst"]})
    with pytest.raises(SchemaViolation):
        e.bulk_load({**ok_cols, "resource_id": ["bk3"],
                     "caveat_context": ['{"allowed":"not-a-list"}']})
    # engine still serves
    assert e.check(CheckItem("doc", "readme", "view", "user", "alice"))


def test_incremental_append_extends_time_bounds():
    """A time-window tuple added via the INCREMENTAL path must extend
    the verdict-flip watermark — otherwise a cached ALLOW filled before
    the write outlives the new tuple's window (fail open). Review
    finding regression."""
    now = time.time()
    t1 = now + 7200
    boot = f"""
schema: |-
  caveat win(now timestamp, until timestamp) {{ now < until }}
  definition user {{}}
  definition doc {{
    relation viewer: user with win | user
    permission view = viewer
  }}
relationships: |-
  doc:a#viewer@user:u[win:{{"until":{t1}}}]
"""
    e = Engine(bootstrap=boot)
    assert e.check(CheckItem("doc", "a", "view", "user", "u"))
    cg = e.compiled()
    assert e._cache_deadline(cg, now, None) == pytest.approx(t1)
    # incremental write of a NEW instance with an EARLIER window end
    t2 = now + 1800
    e.write_relationships([WriteOp("touch", Relationship(
        "doc", "b", "viewer", "user", "u", None, None, "win",
        canonical_context({"until": t2})))])
    cg2 = e.compiled()
    assert cg2.caveats is cg.caveats  # same shared table (incremental)
    assert e._cache_deadline(cg2, now, None) == pytest.approx(t2)


# -- incremental caveated churn ----------------------------------------------


def test_incremental_caveated_churn_oracle_parity():
    """Randomized touch/delete churn over caveated + plain tuples:
    after EVERY mutation the device verdicts match the oracle under a
    fixed request context, and steady-state churn (reused contexts)
    stays on the incremental path."""
    rng = random.Random(99)
    e = Engine(bootstrap=IP_BOOT)
    ctxs = ['{"allowed":["10.0.0.0/8"]}', '{"allowed":["172.16.0.0/12"]}']
    users = [f"u{i}" for i in range(6)]
    live: dict = {}
    req = {"ip": "10.5.5.5"}
    e.check(CheckItem("doc", "readme", "view", "user", "alice"))  # warm
    compiles0 = metrics.counter("engine_graph_compiles_total").value
    for step in range(25):
        u = rng.choice(users)
        if u in live and rng.random() < 0.35:
            from spicedb_kubeapi_proxy_tpu.engine.store import (
                RelationshipFilter,
            )

            e.delete_relationships(RelationshipFilter(
                resource_type="doc", resource_id="r", relation="viewer",
                subject_id=u))
            live.pop(u)
        else:
            cav = rng.random() < 0.7
            ctx = rng.choice(ctxs) if cav else None
            rel = Relationship("doc", "r", "viewer", "user", u, None,
                               None, "ip_allowlist" if cav else None,
                               ctx)
            e.write_relationships([WriteOp("touch", rel)])
            live[u] = ctx
        got = e.check_bulk(
            [CheckItem("doc", "r", "view", "user", u2) for u2 in users],
            context=req)
        o = e.oracle(context=req)
        want = [o.check("doc", "r", "view", "user", u2) for u2 in users]
        assert got == want, f"step {step}: {got} != {want}"
    # reused contexts ride the overlay: no per-write full recompiles
    # (the two distinct contexts at most add instance rows once)
    assert metrics.counter("engine_graph_compiles_total").value \
        <= compiles0 + 1


def test_first_ever_caveat_falls_back_counted():
    """A caveated write against a graph compiled with NO instances of
    that caveat cannot be expressed on the frozen instance tables: the
    incremental path declines with reason=caveat and the read-path
    recompile serves it correctly."""
    e = Engine(bootstrap="""
schema: |-
  caveat c1(x int) { x > 3 }
  definition user {}
  definition doc {
    relation viewer: user | user with c1
    permission view = viewer
  }
relationships: |-
  doc:a#viewer@user:plain
""")
    assert e.check(CheckItem("doc", "a", "view", "user", "plain"))
    fb0 = metrics.counter("engine_graph_incremental_fallback_total",
                          reason="caveat").value
    e.write_relationships([WriteOp("touch", parse_relationship(
        'doc:a#viewer@user:cond[c1:{"x":5}]'))])
    assert metrics.counter("engine_graph_incremental_fallback_total",
                           reason="caveat").value == fb0 + 1
    assert e.check(CheckItem("doc", "a", "view", "user", "cond"))
    # a second same-context caveated write now reuses the instance row
    fb1 = metrics.counter("engine_graph_incremental_fallback_total",
                          reason="caveat").value
    e.write_relationships([WriteOp("touch", parse_relationship(
        'doc:b#viewer@user:cond2[c1:{"x":9}]'))])
    assert metrics.counter("engine_graph_incremental_fallback_total",
                           reason="caveat").value == fb1
    assert e.check(CheckItem("doc", "b", "view", "user", "cond2"))


# -- remote wire --------------------------------------------------------------


def test_remote_engine_carries_context():
    from spicedb_kubeapi_proxy_tpu.engine.remote import (
        EngineServer,
        RemoteEngine,
    )

    e = Engine(bootstrap=IP_BOOT)

    async def go():
        server = EngineServer(e)
        port = await server.start()
        remote = RemoteEngine("127.0.0.1", port)
        try:
            bob = CheckItem("doc", "readme", "view", "user", "bob")
            got = await asyncio.to_thread(
                remote.check_bulk, [bob], None, {"ip": "10.0.0.1"})
            assert got == [True]
            got = await asyncio.to_thread(remote.check_bulk, [bob])
            assert got == [False]
            ids = await asyncio.to_thread(
                lambda: remote.lookup_resources(
                    "doc", "view", "user", "bob",
                    context={"ip": "10.0.0.1"}))
            assert ids == ["readme"]
            mask, interner = await asyncio.to_thread(
                lambda: remote.lookup_resources_mask(
                    "doc", "view", "user", "bob",
                    context={"ip": "10.0.0.1"}))
            from spicedb_kubeapi_proxy_tpu.engine.engine import mask_to_ids
            assert mask_to_ids(mask, interner) == ["readme"]
            subs = await asyncio.to_thread(
                lambda: remote.lookup_subjects(
                    "doc", "readme", "view", "user",
                    context={"ip": "10.0.0.1"}))
            assert subs == ["alice", "bob"]
        finally:
            remote.close()
            await server.stop()
    asyncio.run(go())


# -- end to end through the proxy middleware ----------------------------------

E2E_RULES = """
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: namespace-get
match:
  - apiVersion: v1
    resource: namespaces
    verbs: [get]
check:
  - tpl: "namespace:{{name}}#view@user:{{user.name}}"
---
apiVersion: authzed.com/v1alpha1
kind: ProxyRule
metadata:
  name: namespace-list
match:
  - apiVersion: v1
    resource: namespaces
    verbs: [list]
prefilter:
  - fromObjectIDNameExpr: "{{resourceId}}"
    lookupMatchingResources:
      tpl: "namespace:$#view@user:{{user.name}}"
"""

E2E_BOOT = """
schema: |-
  caveat ip_allowlist(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }
  caveat office_hours(now timestamp, start timestamp, end timestamp) {
    now >= start && now < end
  }
  definition user {}
  definition namespace {
    relation viewer: user | user with ip_allowlist | user with office_hours
    permission view = viewer
  }
relationships: |-
  namespace:public#viewer@user:alice
  namespace:internal#viewer@user:alice[ip_allowlist:{"allowed":["10.0.0.0/8","192.168.1.0/24"]}]
"""


def _req(method, path, user="alice", headers=None):
    return ProxyRequest(
        method=method, path=path, query={},
        headers={"Content-Type": "application/json", **(headers or {})},
        body=b"", user=UserInfo(name=user),
        request_info=parse_request_info(method, path, {}))


async def _upstream_ns_list(req):
    return json_response(200, {"kind": "NamespaceList", "items": [
        {"metadata": {"name": "public"}},
        {"metadata": {"name": "internal"}},
    ]})


def test_e2e_ip_allowlist_prefiltered_list():
    """The acceptance scenario: schema declaring an IP-allowlist caveat
    plus caveated tuples serves a correct conditional verdict end to
    end through the proxy's prefiltered list — allow with matching
    context, deny with non-matching, fail-closed deny with missing
    context — with the caveat mask evaluated on-device in the same
    dispatch as the fixpoint."""
    b = parse_bootstrap(E2E_BOOT)
    e = Engine(schema=b.schema)
    e.write_relationships([WriteOp("touch", r) for r in b.relationships])
    deps = AuthzDeps(matcher=MapMatcher.from_yaml(E2E_RULES), engine=e,
                     upstream=_upstream_ns_list)

    async def names(headers):
        resp = await authorize(
            _req("GET", "/api/v1/namespaces", headers=headers), deps)
        assert resp.status == 200
        doc = json.loads(resp.body)
        return sorted(i["metadata"]["name"] for i in doc["items"])

    async def go():
        # matching client IP: the conditional namespace appears
        assert await names({"X-Forwarded-For": "10.20.30.40"}) == \
            ["internal", "public"]
        # LB chain: the LAST hop (appended by the trusted proxy) wins —
        # a client-forged leading entry must NOT spoof the allowlist
        assert await names(
            {"X-Forwarded-For": "8.8.8.8, 192.168.1.7"}) == \
            ["internal", "public"]
        assert await names(
            {"X-Forwarded-For": "10.0.0.1, 8.8.8.8"}) == ["public"]
        # non-matching IP: conditional grant filtered out
        assert await names({"X-Forwarded-For": "8.8.8.8"}) == ["public"]
        # no trusted header at all: missing context fails closed
        assert await names({}) == ["public"]
        # GET of the conditional namespace follows the same verdicts
        ok = await authorize(_req(
            "GET", "/api/v1/namespaces/internal",
            headers={"X-Forwarded-For": "10.1.1.1"}), deps)
        assert ok.status == 200
        denied = await authorize(_req(
            "GET", "/api/v1/namespaces/internal",
            headers={"X-Forwarded-For": "8.8.8.8"}), deps)
        assert denied.status == 403
        denied2 = await authorize(
            _req("GET", "/api/v1/namespaces/internal"), deps)
        assert denied2.status == 403
    asyncio.run(go())


def test_e2e_time_window_grant():
    b = parse_bootstrap(E2E_BOOT)
    e = Engine(schema=b.schema)
    now = time.time()
    inside = canonical_context(
        {"start": now - 3600, "end": now + 3600})
    outside = canonical_context(
        {"start": now + 3600, "end": now + 7200})
    e.write_relationships([WriteOp("touch", Relationship(
        "namespace", "live", "viewer", "user", "alice", None, None,
        "office_hours", inside))])
    e.write_relationships([WriteOp("touch", Relationship(
        "namespace", "later", "viewer", "user", "alice", None, None,
        "office_hours", outside))])
    deps = AuthzDeps(matcher=MapMatcher.from_yaml(E2E_RULES), engine=e,
                     upstream=_upstream_ns_list)

    async def go():
        # the wall clock is auto-injected as `now`: the in-window grant
        # holds, the future-window one does not — with NO context from
        # the caller at all
        ok = await authorize(
            _req("GET", "/api/v1/namespaces/live"), deps)
        assert ok.status == 200
        denied = await authorize(
            _req("GET", "/api/v1/namespaces/later"), deps)
        assert denied.status == 403
    asyncio.run(go())


def test_caveat_context_disabled_fails_closed():
    b = parse_bootstrap(E2E_BOOT)
    e = Engine(schema=b.schema)
    e.write_relationships([WriteOp("touch", r) for r in b.relationships])
    deps = AuthzDeps(matcher=MapMatcher.from_yaml(E2E_RULES), engine=e,
                     upstream=_upstream_ns_list,
                     caveat_context_enabled=False)

    async def go():
        resp = await authorize(_req(
            "GET", "/api/v1/namespaces/internal",
            headers={"X-Forwarded-For": "10.1.1.1"}), deps)
        assert resp.status == 403  # context never forwarded: fail closed
    asyncio.run(go())


# -- IPv6 in the ipaddress type (ISSUE 11 satellite) -------------------------

IP6_BOOT = """\
schema: |-
  caveat office_net(ip ipaddress) {
    ip in ['2001:db8::/64', '10.0.0.0/8', '192.168.1.7']
  }
  caveat same_addr(ip ipaddress, peer ipaddress) {
    ip == peer
  }
  caveat below(ip ipaddress, peer ipaddress) {
    ip < peer
  }
  caveat dyn_list(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }

  definition user {}

  definition doc {
    relation viewer: user with office_net
    relation editor: user with same_addr
    relation ranker: user with below
    relation lister: user with dyn_list
  }
relationships: |-
  doc:d#viewer@user:al[office_net]
  doc:e#editor@user:al[same_addr:{"peer": "2001:db8::42"}]
  doc:r#ranker@user:al[below:{"peer": "2001:db8::100"}]
  doc:l#lister@user:al[dyn_list:{"allowed": ["10.1.0.0/16", "2001:db8::/64"]}]
  doc:l4#lister@user:al[dyn_list:{"allowed": ["10.1.0.0/16"]}]
"""

_IP6_RELS = {"d": "viewer", "e": "editor", "r": "ranker",
             "l": "lister", "l4": "lister"}


def _ip6_engine():
    b = parse_bootstrap(IP6_BOOT)
    e = Engine(schema=b.schema)
    e.write_relationships([WriteOp("touch", r) for r in b.relationships])
    return e


def _ip6_check(e, doc, ip):
    ctx = {"ip": ip} if ip is not None else None
    return e.check_bulk([CheckItem("doc", doc, _IP6_RELS[doc], "user",
                                   "al")], context=ctx)[0]


def test_ipv6_literal_cidr_exact_lexicographic_boundaries():
    """Literal CIDR allowlists lower to exact word-wise lexicographic
    range checks in the mapped 128-bit space: the /64 boundary addresses
    split EXACTLY, v4 members keep working, and a v6 address never
    matches a v4 block (distinct mapped ranges)."""
    e = _ip6_engine()
    # inside the /64: first and last address of the block
    assert _ip6_check(e, "d", "2001:db8::")
    assert _ip6_check(e, "d", "2001:db8::ffff:ffff:ffff:ffff")
    # one past either edge: exact misses (low 64 bits all-ones + 1)
    assert not _ip6_check(e, "d", "2001:db8:0:1::")
    assert not _ip6_check(e, "d", "2001:db7:ffff:ffff:ffff:ffff:ffff:ffff")
    # v4 members of the same list
    assert _ip6_check(e, "d", "10.255.255.255")
    assert _ip6_check(e, "d", "192.168.1.7")
    assert not _ip6_check(e, "d", "192.168.1.8")
    # a v6 address inside the v4 block's MAPPED range only via ::ffff —
    # the mapped form of a member matches (families share one space)
    assert _ip6_check(e, "d", "::ffff:10.0.0.1")
    # garbage -> missing context -> fail closed
    assert not _ip6_check(e, "d", "not-an-ip")
    assert not _ip6_check(e, "d", None)


def test_ipv6_wide_compare_eq_and_ordering():
    e = _ip6_engine()
    # equality across all four words: low-bit differences matter
    assert _ip6_check(e, "e", "2001:db8::42")
    assert not _ip6_check(e, "e", "2001:db8::43")
    assert not _ip6_check(e, "e", "2001:db8:0:0:1::42")
    # lexicographic ordering: below 2001:db8::100 in the HIGH words and
    # in the LOW words; v4 is always below any non-mapped v6
    assert _ip6_check(e, "r", "2001:db8::ff")
    assert not _ip6_check(e, "r", "2001:db8::100")
    assert not _ip6_check(e, "r", "2001:db8::101")
    assert _ip6_check(e, "r", "9.9.9.9")  # mapped v4 < 2001:db8::


def test_ipv6_param_list_v4_gate_and_unencodable_counter():
    before = metrics.counter(
        "engine_caveat_ipv6_unencodable_total").value
    e = _ip6_engine()
    # a PURE-v4 param list keeps working exactly
    assert _ip6_check(e, "l4", "10.1.2.3")
    assert not _ip6_check(e, "l4", "10.2.0.1")
    # the tuple's list held a v6 element: the WHOLE list is
    # unencodable -> UNKNOWN -> fail closed (even for v4 members that a
    # narrowed list would have admitted: a KNOWN narrowed answer would
    # fail OPEN under '!(ip in blocked)' denylists), and counted
    miss0 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    assert not _ip6_check(e, "l", "10.1.2.3")
    assert metrics.counter(
        "engine_caveat_denied_missing_context_total").value > miss0
    after = metrics.counter(
        "engine_caveat_ipv6_unencodable_total").value
    assert after > before
    # a v6 request address against a v4-only list is a KNOWN miss (the
    # sentinel lowering): denied WITHOUT a missing-context tick — the
    # true answer, not an unknown. Isolated engine: the combined
    # fixture's v6-bearing instance is legitimately missing on every
    # dispatch and would tick the counter regardless of the doc asked
    b4 = parse_bootstrap("""\
schema: |-
  caveat dyn_list(ip ipaddress, allowed list<ipaddress>) {
    ip in allowed
  }

  definition user {}

  definition doc {
    relation lister: user with dyn_list
  }
relationships: |-
  doc:l4#lister@user:al[dyn_list:{"allowed": ["10.1.0.0/16"]}]
""")
    e4 = Engine(schema=b4.schema)
    e4.write_relationships([WriteOp("touch", r)
                            for r in b4.relationships])
    miss1 = metrics.counter(
        "engine_caveat_denied_missing_context_total").value
    assert not e4.check_bulk([CheckItem("doc", "l4", "lister", "user",
                                        "al")],
                             context={"ip": "2001:db8::1"})[0]
    assert metrics.counter(
        "engine_caveat_denied_missing_context_total").value == miss1
    # writes carrying v6 list elements are ACCEPTED (well-typed; they
    # resolve UNKNOWN at evaluation), never a SchemaViolation
    from spicedb_kubeapi_proxy_tpu.models.tuples import parse_relationship
    e.write_relationships([WriteOp("touch", parse_relationship(
        'doc:lw#lister@user:al'
        '[dyn_list:{"allowed": ["fe80::/10"]}]'))])


def test_ipv6_vm_matches_interpreter_over_address_corpus():
    """Differential over both families and every caveat shape: the VM's
    verdict equals the tri-state oracle's for literal lists, wide
    compares, and the param-list v4 gate."""
    e = _ip6_engine()
    b = parse_bootstrap(IP6_BOOT)
    defs = b.schema.caveat_defs
    corpus = [
        "2001:db8::", "2001:db8::1", "2001:db8::42", "2001:db8::100",
        "2001:db8::ffff:ffff:ffff:ffff", "2001:db8:0:1::", "fe80::1",
        "::1", "::", "ffff:ffff:ffff:ffff:ffff:ffff:ffff:ffff",
        "10.0.0.0", "10.1.2.3", "10.255.255.255", "11.0.0.0",
        "192.168.1.7", "0.0.0.0", "255.255.255.255",
        "::ffff:10.0.0.1", "::ffff:192.168.1.7",
    ]
    tuple_ctx = {"e": {"peer": "2001:db8::42"},
                 "r": {"peer": "2001:db8::100"},
                 "l": {"allowed": ["10.1.0.0/16", "2001:db8::/64"]}}
    cav_of = {"d": "office_net", "e": "same_addr", "r": "below",
              "l": "dyn_list"}
    for doc, cname in cav_of.items():
        defn = defs[cname]
        params = {p.name: p.type for p in defn.params}
        for ip in corpus:
            got = _ip6_check(e, doc, ip)
            ctx = dict(tuple_ctx.get(doc, {}))
            ctx["ip"] = ip
            want = interpret(defn.expr, ctx, params, StringInterner())
            assert got == (want is True), (doc, ip, want, got)


def test_ipaddress_type_misuse_rejected():
    for body, params in (
            ("ip + 1 > 5", (CaveatParam("ip", CaveatType("ipaddress")),)),
            ("ip > 5", (CaveatParam("ip", CaveatType("ipaddress")),)),
            ("5 in allowed", (CaveatParam(
                "allowed", CaveatType("list", "ipaddress")),)),
            ("ip in [7]", (CaveatParam("ip", CaveatType("ipaddress")),)),
    ):
        defn = CaveatDef("bad", params, parse_caveat_body(body))
        with pytest.raises(CaveatError):
            compile_caveat(defn, StringInterner())


def test_ipv6_unencodable_list_never_fails_open_under_negation():
    """The denylist polarity pin: '!(ip in blocked)' with a v6 element
    in the blocked PARAM list must DENY (the list is UNKNOWN, and
    Kleene NOT(unknown) = unknown = fail closed) — a dropped-element
    narrowing would have answered known-False and GRANTED."""
    boot = """\
schema: |-
  caveat not_blocked(ip ipaddress, blocked list<ipaddress>) {
    !(ip in blocked)
  }

  definition user {}

  definition doc {
    relation viewer: user with not_blocked
    permission view = viewer
  }
relationships: |-
  doc:v6#viewer@user:al[not_blocked:{"blocked": ["2001:db8::/64"]}]
  doc:v4#viewer@user:al[not_blocked:{"blocked": ["10.0.0.0/8"]}]
"""
    b = parse_bootstrap(boot)
    e = Engine(schema=b.schema)
    e.write_relationships([WriteOp("touch", r) for r in b.relationships])

    def chk(doc, ip):
        return e.check_bulk([CheckItem("doc", doc, "viewer", "user",
                                       "al")], context={"ip": ip})[0]

    # v6-bearing denylist: UNKNOWN -> denied for EVERYONE (the blocked
    # v6 client above all — never granted by a narrowed known-False)
    assert not chk("v6", "2001:db8::1")   # explicitly blocked: denied
    assert not chk("v6", "9.9.9.9")       # fail closed, not fail open
    # pure-v4 denylist keeps exact semantics either family
    assert not chk("v4", "10.1.2.3")      # blocked
    assert chk("v4", "11.0.0.1")          # not blocked: granted
    assert chk("v4", "2001:db8::1")       # v6 truly not in a v4 list
    # and the oracle agrees on the unknown polarity
    defn = b.schema.caveat_defs["not_blocked"]
    params = {p.name: p.type for p in defn.params}
    assert interpret(defn.expr,
                     {"ip": "9.9.9.9", "blocked": ["2001:db8::/64"]},
                     params, StringInterner()) is None

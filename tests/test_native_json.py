"""Native JSON list filter (graphcore.cpp json_list_filter): the
wire-level filter must agree with the Python json path on every input —
differential-fuzzed over documents with escapes, unicode, nested
containers, odd whitespace, and missing/duplicate fields; anything the
scanner cannot prove structurally identical must BAIL (return None) so
the Python path keeps authority."""

from __future__ import annotations

import json
import random
import string

import pytest

from spicedb_kubeapi_proxy_tpu import native
from spicedb_kubeapi_proxy_tpu.authz.filterer import (
    FilterError,
    _filter_list_wire,
    filter_body,
)
from spicedb_kubeapi_proxy_tpu.authz.lookups import AllowedSet
from spicedb_kubeapi_proxy_tpu.rules.input import (
    RequestInfo,
    ResolveInput,
    UserInfo,
)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

INPUT = ResolveInput.create(
    RequestInfo(verb="list", api_version="v1", resource="pods",
                path="/api/v1/pods"),
    UserInfo(name="a"))


def py_filter(body: bytes, allowed: AllowedSet, monkeypatch=None):
    """The pure-Python path, with the wire path forced off."""
    import spicedb_kubeapi_proxy_tpu.authz.filterer as f

    orig = f._filter_list_wire
    f._filter_list_wire = lambda *a: None
    try:
        return filter_body(body, allowed, INPUT)
    finally:
        f._filter_list_wire = orig


NAMES = ["plain", "with/slash", 'quo"te', "back\\slash", "uni-\u65e5\u672c", "tab\there", "new\nline", "\u2028sep", "na\x00me"]


def rand_value(rng, depth=0):
    r = rng.random()
    if depth > 2 or r < 0.3:
        return rng.choice([
            1, -2.5, 1e10, True, False, None, "s", 'esc"aped',
            "unié", rng.random()])
    if r < 0.55:
        return [rand_value(rng, depth + 1) for _ in range(rng.randrange(3))]
    return {f"k{i}": rand_value(rng, depth + 1)
            for i in range(rng.randrange(3))}


def rand_doc(rng):
    items = []
    for _ in range(rng.randrange(6)):
        item = {"metadata": {}}
        if rng.random() < 0.9:
            item["metadata"]["name"] = rng.choice(NAMES)
        if rng.random() < 0.6:
            item["metadata"]["namespace"] = rng.choice(NAMES)
        if rng.random() < 0.5:
            item["metadata"]["labels"] = {
                "".join(rng.choices(string.ascii_letters, k=3)):
                rand_value(rng)}
        if rng.random() < 0.5:
            item["spec"] = rand_value(rng)
        if rng.random() < 0.2:
            del item["metadata"]
        items.append(item)
    doc = {"kind": "PodList", "apiVersion": "v1",
           "metadata": {"resourceVersion": "7"},
           "items": items}
    if rng.random() < 0.3:
        doc["extra"] = rand_value(rng)
    sep = rng.choice([(",", ":"), (", ", ": "), (",\n ", " : ")])
    ea = rng.random() < 0.5
    return json.dumps(doc, separators=sep, ensure_ascii=ea).encode(), items


def test_differential_fuzz_against_python_path():
    rng = random.Random(1234)
    for trial in range(300):
        body, items = rand_doc(rng)
        # random allowed set over the names present (+ noise)
        pool = [((i.get("metadata") or {}).get("namespace") or "",
                 (i.get("metadata") or {}).get("name") or "")
                for i in items]
        allowed = AllowedSet(set(
            p for p in pool if rng.random() < 0.6) | {("x", "noise")})
        py_status, py_out = py_filter(body, allowed)
        wire = _filter_list_wire(body, allowed)
        assert wire is not None, f"trial {trial}: scanner bailed on {body!r}"
        w_status, w_out = wire
        assert w_status == py_status == 200
        assert json.loads(w_out) == json.loads(py_out), \
            f"trial {trial}: {body!r}"
        if w_out != body:
            doc = json.loads(body)
            for i, item in enumerate(doc["items"]):
                pair = ((item.get("metadata") or {}).get("namespace") or "",
                        (item.get("metadata") or {}).get("name") or "")
                if allowed.allows(*pair):
                    frag = json.dumps(
                        item, separators=(",", ":")).encode()
                    # spans carry the ORIGINAL bytes; reparse equality
                    # is already asserted above — here just ensure the
                    # kept item's name appears in the output
                    assert json.loads(frag) in json.loads(w_out)["items"]


def test_wire_no_drop_is_byte_identical_and_drop_splices():
    body = (b'{"kind":"PodList", "items":[\n'
            b'  {"metadata":{"name":"a","namespace":"n1"},"x":1.50},\n'
            b'  {"metadata":{"namespace":"n2","name":"b"}}\n]}')
    both = AllowedSet({("n1", "a"), ("n2", "b")})
    assert _filter_list_wire(body, both) == (200, body)
    one = AllowedSet({("n2", "b")})
    status, out = _filter_list_wire(body, one)
    assert status == 200
    # the kept item's original bytes are spliced verbatim
    assert b'{"metadata":{"namespace":"n2","name":"b"}}' in out
    assert json.loads(out)["items"] == [
        {"metadata": {"namespace": "n2", "name": "b"}}]
    # zero kept: the array empties, wrapper intact
    status, out = _filter_list_wire(body, AllowedSet(set()))
    assert json.loads(out) == {"kind": "PodList", "items": []}


def test_escaped_names_decode_exactly():
    name = 'quo"te\\pathé\n'
    body = json.dumps({"kind": "PodList", "items": [
        {"metadata": {"name": name, "namespace": "ns"}}]}).encode()
    allowed = AllowedSet({("ns", name)})
    assert _filter_list_wire(body, allowed) == (200, body)
    assert _filter_list_wire(
        body, AllowedSet({("ns", "other")}))[1] is not None


@pytest.mark.parametrize("body", [
    b'{"items":[1,2]}',                          # non-object items: bail
    b'{"items":[{}],"items":[{}]}',              # duplicate items: bail
    b'{"items":[{}]} trailing',                  # trailing garbage: bail
    b'{"items":[{"metadata":{"name":123}}]}',    # non-string name: bail
    b'{"items":[{"metadata":{"na\\u006de":"x"}}]}',  # escaped key: bail
    b'not json at all',
    b'{"kind":"Pod","metadata":{"name":"x"}}',   # single object
    b'[1,2,3]',                                  # root array
    # malformed tokens inside SKIPPED values must bail, not be spliced
    # into a 200 (review finding)
    b'{"kind":"PodList","items":['
    b'{"metadata":{"name":"x"},"spec":{"a":@@@}}]}',
    b'{"kind":"PodList","items":['
    b'{"metadata":{"name":"x"},"n":1e+e+5}]}',
    b'{"kind":"PodList","items":[{"metadata":{"name":"x"},"n":01}]}',
    b'{"kind":"PodList","items":[{"metadata":{"name":"x"},"n":+1}]}',
    # invalid escape in a judged name: json.loads rejects the body, so
    # the wire path must yield to the Python path's clean error
    b'{"kind":"PodList","items":[{"metadata":{"name":"a\\qb"}}]}',
    # invalid utf-8 inside an escaped record
    b'{"kind":"PodList","items":[{"metadata":'
    b'{"name":"a\\tb","namespace":"\xff\xfe"}}]}',
])
def test_scanner_bails_conservatively(body):
    """Anything structurally surprising returns None (Python keeps
    authority) — and combined filter_body behavior matches pure-Python."""
    allowed = AllowedSet({("", "x")})
    assert _filter_list_wire(body, allowed) is None
    try:
        py = py_filter(body, allowed)
    except FilterError:
        py = "error"
    try:
        combined = filter_body(body, allowed, INPUT)
    except FilterError:
        combined = "error"
    assert combined == py


def test_table_rows_filter_at_the_wire():
    """JSON Tables route through a rows-keyed rescan: metadata reads
    from each row's ``object``; kept rows stay byte-identical and the
    results match the Python Table path."""
    rng = random.Random(77)
    for _ in range(60):
        rows = []
        for _ in range(rng.randrange(5)):
            row = {"cells": [rng.choice(NAMES), rng.randrange(9)]}
            if rng.random() < 0.85:
                row["object"] = {"kind": "PartialObjectMetadata",
                                 "metadata": {}}
                if rng.random() < 0.9:
                    row["object"]["metadata"]["name"] = rng.choice(NAMES)
                if rng.random() < 0.5:
                    row["object"]["metadata"]["namespace"] = \
                        rng.choice(NAMES)
            rows.append(row)
        doc = {"kind": "Table", "apiVersion": "meta.k8s.io/v1",
               "columnDefinitions": [{"name": "Name", "type": "string"}],
               "rows": rows}
        body = json.dumps(doc,
                          ensure_ascii=rng.random() < 0.5).encode()
        pool = [(((r.get("object") or {}).get("metadata") or {})
                 .get("namespace") or "",
                 ((r.get("object") or {}).get("metadata") or {})
                 .get("name") or "")
                for r in rows]
        allowed = AllowedSet(set(
            p for p in pool if rng.random() < 0.6))
        py = py_filter(body, allowed)
        wire = _filter_list_wire(body, allowed)
        assert wire is not None
        assert wire[0] == py[0] == 200
        assert json.loads(wire[1]) == json.loads(py[1])
        if py[1] == body:
            # nothing dropped: the wire path must be byte-identical too
            assert wire[1] == body
    # empty table passes through byte-identically
    empty = b'{"kind":"Table","rows":[],"items":[]}'
    assert _filter_list_wire(empty, AllowedSet(set())) == (200, empty)


def test_lone_surrogate_names_ride_escaped_records():
    """json.loads accepts lone-surrogate \\u escapes; such names cannot
    UTF-8-encode into the bytes record set, so they compare via the
    decoded-str path — kept and dropped both match the Python path."""
    body = (b'{"kind":"PodList","items":'
            b'[{"metadata":{"name":"a\\ud800b"}}]}')
    name = json.loads('"a\\ud800b"')
    allowed = AllowedSet({("", name)})
    assert _filter_list_wire(body, allowed) == (200, body)
    status, out = _filter_list_wire(body, AllowedSet({("", "z")}))
    assert status == 200 and json.loads(out)["items"] == []
    # invalid utf-8 raw bytes, by contrast, bail (json.loads rejects)
    bad = (b'{"kind":"PodList","items":'
           b'[{"metadata":{"name":"\xed\xa0\x80"}}]}')
    assert _filter_list_wire(bad, allowed) is None


def test_proto_list_native_matches_python_walker():
    """The native proto scanner must produce byte-identical output to
    kubeproto.filter_list_raw across fuzzing: extra fields, duplicate
    metadata, non-length-delimited fields sharing the field numbers."""
    from spicedb_kubeapi_proxy_tpu.authz.filterer import filter_body_proto
    from spicedb_kubeapi_proxy_tpu.proxy import kubeproto

    def ld(fno, payload):
        return (kubeproto._encode_varint((fno << 3) | 2)
                + kubeproto._encode_varint(len(payload)) + payload)

    def vint(fno, v):
        return kubeproto._encode_varint(fno << 3) \
            + kubeproto._encode_varint(v)

    rng = random.Random(99)
    for trial in range(150):
        items = []
        metas = []
        for _ in range(rng.randrange(6)):
            name = rng.choice([n for n in NAMES
                               if "\x00" not in n]) \
                if rng.random() < 0.9 else None
            ns = rng.choice(["", "ns1", "uni-日本"]) \
                if rng.random() < 0.7 else None
            meta = b""
            if rng.random() < 0.3:
                meta += vint(2, rng.randrange(99))  # unrelated varint
            if name is not None:
                meta += ld(1, name.encode())
            if ns:
                meta += ld(3, ns.encode())
            item = b""
            if rng.random() < 0.3:
                item += vint(1, 7)  # field 1 with WRONG wire type first
            item += ld(1, meta)
            if rng.random() < 0.4:
                item += ld(1, ld(1, b"duplicate-meta-ignored"))
            if rng.random() < 0.5:
                item += ld(2, b"\x0a\x03xyz")  # spec-ish nested bytes
            items.append(ld(2, item))
            metas.append((ns or "", name or ""))
        raw = ld(1, b"\x0a\x021")  # ListMeta-ish
        raw += b"".join(items)
        if rng.random() < 0.3:
            raw += vint(9, 5)  # trailing unrelated field
        body = kubeproto.encode_unknown("v1", "PodList", raw)
        allowed = AllowedSet(set(
            p for p in metas if rng.random() < 0.6))
        py_raw = kubeproto.filter_list_raw(raw, allowed.allows)
        py_body = kubeproto.replace_unknown_raw(body, py_raw)
        status, native_body = filter_body_proto(body, allowed, INPUT)
        assert status == 200
        assert native_body == py_body or (
            py_raw == raw and native_body == body), trial
        # no-drop must be byte-identical to the ORIGINAL body
        every = AllowedSet(set(metas) | {("", "")})
        status, out = filter_body_proto(body, every, INPUT)
        assert (status, out) == (200, body)

    # control bytes / invalid utf-8 in a proto name: native bails, the
    # Python walker (errors='replace') keeps authority
    bad_raw = ld(2, ld(1, ld(1, b"\x01ctl")))
    bad_body = kubeproto.encode_unknown("v1", "PodList", bad_raw)
    from spicedb_kubeapi_proxy_tpu import native as _native

    assert _native.proto_list_spans(bad_raw) is None
    status, out = filter_body_proto(bad_body, AllowedSet(set()), INPUT)
    py = kubeproto.replace_unknown_raw(
        bad_body, kubeproto.filter_list_raw(
            bad_raw, AllowedSet(set()).allows))
    assert (status, out) == (200, py)
    bad_utf8 = ld(2, ld(1, ld(1, b"\xff\xfe")))
    assert _native.proto_list_spans(bad_utf8) is None


def test_proto_table_native_matches_python_walker():
    """proto_table_spans must agree with kubeproto.filter_table_raw on
    fuzzing over both object encodings (nested magic Unknown and bare
    PartialObjectMetadata), and bail wherever the walker raises."""
    from spicedb_kubeapi_proxy_tpu.authz.filterer import filter_body_proto
    from spicedb_kubeapi_proxy_tpu.proxy import kubeproto
    from test_kubeproto import table, table_row, unknown as t_unknown

    rng = random.Random(4242)
    for trial in range(120):
        rows = []
        metas = []
        for _ in range(rng.randrange(5)):
            name = rng.choice(["a", "b-2", "uni-日本", "x/y"])
            ns = rng.choice(["", "ns1", "ns2"])
            rows.append(table_row(name, ns,
                                  wrap_unknown=rng.random() < 0.5))
            metas.append((ns, name))
        raw = table(rows)
        body = t_unknown("Table", raw, api_version="meta.k8s.io/v1")
        allowed = AllowedSet(set(
            p for p in metas if rng.random() < 0.6))
        py_raw = kubeproto.filter_table_raw(raw, allowed.allows)
        py_body = kubeproto.replace_unknown_raw(body, py_raw)
        status, out = filter_body_proto(body, allowed, INPUT)
        assert status == 200
        assert out == py_body or (py_raw == raw and out == body), trial
        # no-drop: byte-identical to the ORIGINAL body
        status, out = filter_body_proto(
            body, AllowedSet(set(metas)), INPUT)
        assert (status, out) == (200, body)
    # a row without a keyable object: scanner bails; the walker raises
    # ProtoError -> FilterError (clean 401 upstream)
    from spicedb_kubeapi_proxy_tpu import native as _native
    from spicedb_kubeapi_proxy_tpu.authz.filterer import FilterError

    bare = table([b"\x0a\x03abc"])  # row with cells only, no object
    assert _native.proto_table_spans(bare) is None
    with pytest.raises(FilterError):
        filter_body_proto(
            t_unknown("Table", bare, api_version="meta.k8s.io/v1"),
            AllowedSet(set()), INPUT)


def test_proto_scanner_adversarial_wire():
    """Crafted wire data that would loop/overflow a naive scanner must
    BAIL cleanly (review finding: huge length varints cancel the cursor
    advance; >32-bit field numbers alias onto the items field)."""
    from spicedb_kubeapi_proxy_tpu import native as _native
    from spicedb_kubeapi_proxy_tpu.proxy import kubeproto

    def ld(fno, payload):
        return (kubeproto._encode_varint((fno << 3) | 2)
                + kubeproto._encode_varint(len(payload)) + payload)

    # length varint 2^64-11: i += (int64)len would step BACKWARD
    huge = kubeproto._encode_varint(10)[:0]  # build by hand:
    huge = bytes([0x0A]) + bytes([0xF5] + [0xFF] * 8 + [0x01])
    assert _native.proto_list_spans(huge + b"xxxx") is None
    # same huge length on the items field itself
    evil_item = bytes([0x12]) + bytes([0xF5] + [0xFF] * 8 + [0x01])
    assert _native.proto_list_spans(evil_item + b"xxxx") is None
    # a >32-bit field number whose low bits alias to field 2: Python
    # copies it through; the native scanner must NOT key it as an item
    big_fno = ((1 << 32) + 2)
    tag = kubeproto._encode_varint((big_fno << 3) | 2)
    chunk = tag + kubeproto._encode_varint(4) + b"zzzz"
    item = ld(2, ld(1, ld(1, b"keepme")))
    raw = chunk + item
    scan = _native.proto_list_spans(raw)
    assert scan is not None
    item_spans, keys = scan
    assert len(item_spans) == 1  # only the REAL item keyed
    assert keys == b"0\x1fkeepme\x1e"
    # truncated payload lengths at every nesting level bail
    assert _native.proto_list_spans(ld(2, ld(1, b"\x0a\x7fshort"))) is None


def test_kind_and_whitespace_variants():
    body = (b'  {  "apiVersion" : "v1" ,\n "items" : [ '
            b'{ "metadata" : { "name" : "w" } } ] , "kind" : "PodList" }  ')
    allowed = AllowedSet({("", "w")})
    assert _filter_list_wire(body, allowed) == (200, body)
    status, out = _filter_list_wire(body, AllowedSet(set()))
    assert json.loads(out)["items"] == []


# -- the fused call against the json.loads path, case by case -----------------

COMPACT, SPACED = (",", ":"), (",\n  ", " : ")


def _wrap(kind: str, metas, sep, ensure_ascii=False) -> bytes:
    """A ``*List`` of items or a ``Table`` of rows over ``metas`` (each a
    metadata dict, or None for an entry without one)."""
    def entry(i, meta):
        obj = {"spec": {"n": i}} if meta is None \
            else {"metadata": meta, "spec": {"n": i}}
        return {"cells": [i], "object": obj} if kind == "Table" else obj
    doc = {"kind": kind, "apiVersion": "v1",
           "metadata": {"resourceVersion": "9"},
           "rows" if kind == "Table" else "items":
               [entry(i, m) for i, m in enumerate(metas)]}
    return json.dumps(doc, separators=sep, ensure_ascii=ensure_ascii).encode()


def _pair(meta) -> tuple:
    return ((meta or {}).get("namespace") or "", (meta or {}).get("name") or "")


def _generated_cases():
    plain = [{"name": f"p{i}", "namespace": f"ns{i % 3}"} for i in range(9)]
    escaped = [{"name": n, "namespace": "ns"} for n in
               ['quo"te', "back\\slash", "tab\there", "new\nline", "plain"]]
    unicode_ = [{"name": n, "namespace": "ns-日本"} for n in
                ["uni-日本", "café", "\U0001f600", "plain"]]
    cluster = [{"name": "a"}, {"name": "b", "namespace": ""}, None,
               {"namespace": "only-ns"}, {}]
    # more kept runs than the first guess (records + 64): one allowed
    # name, 100 times, each time behind an item that is dropped
    many_runs = [{"name": "dup" if i % 2 else f"drop{i}"}
                 for i in range(200)]
    # more escape-flagged items than the first guess (64)
    many_esc = [{"name": f"e\t{i}"} for i in range(150)]
    for kind in ("PodList", "Table"):
        for sep_id, sep in (("compact", COMPACT), ("spaced", SPACED)):
            def case(name, metas, allowed, **kw):
                return pytest.param(
                    _wrap(kind, metas, sep, **kw), allowed, "fused",
                    id=f"{kind}-{sep_id}-{name}")
            yield case("some", plain,
                       {_pair(m) for m in plain[::2]} | {("x", "noise")})
            yield case("adjacent", plain, {_pair(m) for m in plain[2:7]})
            yield case("escaped", escaped,
                       {_pair(m) for m in escaped[1::2]})
            yield case("escaped-ascii", escaped + unicode_,
                       {_pair(m) for m in (escaped + unicode_)[::2]},
                       ensure_ascii=True)
            yield case("non-ascii", unicode_,
                       {_pair(m) for m in unicode_[:2]})
            yield case("no-namespace", cluster, {("", "a"), ("", "")})
            yield case("nothing", plain, set())
            yield case("everything", plain, {_pair(m) for m in plain})
            yield case("grow-runs", many_runs, {("", "dup")})
            yield case("grow-escapes", many_esc,
                       {("", f"e\t{i}") for i in range(0, 150, 3)})


def _handwritten_cases():
    def case(name, body, allowed, expect="fused"):
        return pytest.param(body, allowed, expect, id=name)
    # duplicate keys: the last one wins, as in dict construction
    yield case("List-duplicate-metadata-last-wins",
               b'{"kind":"PodList","items":[{"metadata":{"name":"a"},'
               b'"metadata":{"name":"b"}},{"metadata":{"name":"a"}}]}',
               {("", "b")})
    yield case("List-duplicate-name-last-wins",
               b'{"kind":"PodList","items":[{"metadata":{"name":"a",'
               b'"namespace":"n","name":"b"}},{"metadata":{"name":"c"}}]}',
               {("n", "a")})
    yield case("Table-duplicate-object-last-wins",
               b'{"kind":"Table","rows":[{"object":{"metadata":{"name":"a"}},'
               b'"object":{"spec":1}},{"object":{"metadata":{"name":"a"}}}]}',
               {("", "a")})
    for kind, key in (("PodList", "items"), ("Table", "rows")):
        k = kind.encode()
        yield case(f"{kind}-array-absent",
                   b'{"kind":"%s","metadata":{}}' % k, {("", "a")})
        yield case(f"{kind}-array-empty",
                   b'{"kind":"%s","%s":[]}' % (k, key.encode()), {("", "a")})
        yield case(f"{kind}-array-null",
                   b'{"kind":"%s","%s":null}' % (k, key.encode()),
                   {("", "a")}, "python")
        item = b'{"metadata":{"name":"%s"}}'
        row = b'{"object":' + item + b'}' if kind == "Table" else item
        yield case(f"{kind}-invalid-escape",
                   b'{"kind":"%s","%s":[%s]}' % (k, key.encode(),
                                                 row % b'a\\qb'),
                   {("", "a")}, "python")
        yield case(f"{kind}-invalid-utf8",
                   b'{"kind":"%s","%s":[%s]}' % (k, key.encode(),
                                                 row % b'\xff\xfe'),
                   {("", "a")}, "python")
    # a Table whose kind the sniff cannot see: the rescan under "rows"
    yield case("Table-kind-spaced-oddly",
               b'{"kind"  :  "Table","rows":[{"object":{"metadata":'
               b'{"name":"a"}}},{"object":{"metadata":{"name":"b"}}}],'
               b'"items":[]}', {("", "b")})


@pytest.mark.parametrize(
    "body,allowed,expect",
    list(_generated_cases()) + list(_handwritten_cases()))
def test_fused_filter_agrees_with_the_json_path(body, allowed, expect):
    """The one native call (``_filter_list_wire``) against the
    ``json.loads`` path of ``filter_body`` on the same body: the same
    status and the same document; kept entries byte for byte; nothing
    dropped gives back the very ``body``; and a body the scanner
    refuses (``expect`` "python") gets the Python path's answer, its
    401 included, through ``apply_filter``."""
    from spicedb_kubeapi_proxy_tpu.authz.filterer import apply_filter
    from spicedb_kubeapi_proxy_tpu.proxy.types import ProxyResponse

    allowed = AllowedSet(set(allowed))
    wire = _filter_list_wire(body, allowed)
    if expect == "python":
        assert wire is None
        import spicedb_kubeapi_proxy_tpu.authz.filterer as f

        resp = ProxyResponse(status=200, headers={}, body=body)
        both = apply_filter(resp, allowed, INPUT)
        orig, f._filter_list_wire = f._filter_list_wire, lambda *a: None
        try:
            alone = apply_filter(resp, allowed, INPUT)
        finally:
            f._filter_list_wire = orig
        assert (both.status, both.body) == (alone.status, alone.body)
        assert both.status in (200, 401)
        return
    assert wire is not None, "the scanner bailed"
    py_status, py_out = py_filter(body, allowed)
    assert wire[0] == py_status == 200
    assert json.loads(wire[1]) == json.loads(py_out)
    if py_out is body:
        assert wire[1] is body
        return
    # kept entries are the upstream's own bytes, joined by a bare comma
    doc = json.loads(body)
    key = "rows" if doc["kind"] == "Table" else "items"
    sep = COMPACT if b",\n" not in body else SPACED
    kept = [json.dumps(e, separators=sep, ensure_ascii=False).encode()
            for e in json.loads(py_out)[key]]
    if all(k in body for k in kept):  # (not so under ensure_ascii)
        assert b",".join(kept) in wire[1]

"""ctypes loader for the native graph-builder core (graphcore.cpp).

The shared library is built on first use with the system toolchain and
cached next to the source. Every entry point degrades to a numpy fallback
when the toolchain or library is unavailable, and ``SDBKP_NATIVE=0``
disables the native path outright — the numpy and native implementations
are behaviorally identical (tests assert parity).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("sdbkp.native")

_SRC = os.path.join(os.path.dirname(__file__), "graphcore.cpp")
_LIB = os.path.join(os.path.dirname(__file__), "libgraphcore.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build() -> bool:
    # build to a private temp path and publish atomically: a killed or
    # concurrent compile must never leave a truncated .so that poisons the
    # mtime-based cache for every later process
    tmp = f"{_LIB}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native build failed (%s); using numpy fallbacks", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("SDBKP_NATIVE", "1") == "0":
            _load_failed = True
            return None
        if not os.path.exists(_LIB) or (
            os.path.exists(_SRC)
            and os.path.getmtime(_SRC) > os.path.getmtime(_LIB)
        ):
            if not _build():
                _load_failed = True
                return None
        try:
            lib = _bind(ctypes.CDLL(_LIB))
        except OSError as e:
            log.warning("native load failed (%s); using numpy fallbacks", e)
            _load_failed = True
            return None
        except AttributeError:
            # a cached .so from an older source revision can be missing
            # newer symbols even when mtimes look fresh (archive/rsync -a
            # deploys preserve old source mtimes): rebuild once, then
            # degrade to numpy as documented instead of crashing callers
            log.warning("cached native library is stale; rebuilding")
            if not _build():
                _load_failed = True
                return None
            try:
                lib = _bind(ctypes.CDLL(_LIB))
            except (OSError, AttributeError) as e:
                log.warning("native reload failed (%s); using numpy "
                            "fallbacks", e)
                _load_failed = True
                return None
        _lib = lib
        return _lib


# bumped together with graphcore_abi_version() in graphcore.cpp on ANY
# exported-signature change; _bind refuses a mismatching cached .so (the
# rebuild path then fires) — binding by symbol NAME alone would let a
# stale library misread argument slots silently
_ABI_VERSION = 6


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every entry point's signature; raises AttributeError when
    the library predates a symbol or its ABI version differs."""
    lib.graphcore_abi_version.restype = ctypes.c_int64
    lib.graphcore_abi_version.argtypes = []
    got = lib.graphcore_abi_version()
    if got != _ABI_VERSION:
        raise AttributeError(
            f"graphcore ABI {got} != expected {_ABI_VERSION}")
    lib.unique_inverse_fixed.restype = ctypes.c_int64
    lib.unique_inverse_fixed.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.sort_perm_i64.restype = None
    lib.sort_perm_i64.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.index_build_u64.restype = None
    lib.index_build_u64.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
    ]
    p64 = ctypes.POINTER(ctypes.c_int64)
    lib.json_list_filter.restype = ctypes.c_int64
    lib.json_list_filter.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, p64, ctypes.c_int64,
        p64, p64, ctypes.c_int64, p64, ctypes.c_int64, p64,
    ]
    p32 = ctypes.POINTER(ctypes.c_int32)
    lib.json_list_keys.restype = ctypes.c_int64
    lib.json_list_keys.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        p64, p64, p32, ctypes.c_int64, ctypes.c_char_p, p32, p64,
    ]
    lib.proto_list_spans.restype = ctypes.c_int64
    lib.proto_list_spans.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.proto_table_spans.restype = ctypes.c_int64
    lib.proto_table_spans.argtypes = lib.proto_list_spans.argtypes
    return lib


def available() -> bool:
    return _load() is not None


def unique_inverse(arr: np.ndarray):
    """Hash-based ``np.unique(arr, return_inverse=True)`` over a bytes ('S')
    column, except uniques come back in FIRST-OCCURRENCE order (callers never
    depend on ordering). Returns (uniq_rows int64[k], inv int32[n]) or None
    when the native path does not apply."""
    lib = _load()
    if lib is None or arr.dtype.kind != "S" or arr.ndim != 1:
        return None
    width = arr.dtype.itemsize
    n = len(arr)
    if width == 0 or n == 0:
        return None
    data = np.ascontiguousarray(arr)
    inv = np.empty(n, dtype=np.int32)
    uniq_rows = np.empty(n, dtype=np.int64)
    k = lib.unique_inverse_fixed(
        data.ctypes.data_as(ctypes.c_char_p), width, n,
        inv.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        uniq_rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return uniq_rows[:k], inv


def index_build(rt, rid, rl, st, sid, srl):
    """Row-key index build for the relationship store: hashes the six
    int32 key columns (same mix as store._hash_key_cols) and returns
    (sorted_hashes uint64[n], order int64[n]) via a multithreaded radix
    sort. None when the native path does not apply."""
    lib = _load()
    if lib is None:
        return None
    cols = [np.ascontiguousarray(c, dtype=np.int32)
            for c in (rt, rid, rl, st, sid, srl)]
    n = len(cols[0])
    hashes = np.empty(n, dtype=np.uint64)
    order = np.empty(n, dtype=np.int64)
    p32 = ctypes.POINTER(ctypes.c_int32)
    lib.index_build_u64(
        *(c.ctypes.data_as(p32) for c in cols), n,
        hashes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        order.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return hashes, order


def json_list_filter(body: bytes, records: bytes, offsets: np.ndarray):
    """A kube *List or Table response body decided against the allowed
    records in ONE native call that holds no interpreter lock
    (graphcore.cpp json_list_filter). ``records`` are the allowed
    ``'0' ns 0x1f name`` records back to back, ``offsets`` their
    ``len + 1`` int64 offsets (``AllowedSet.packed_records``). Returns
    ``(arr_span, runs, esc, dropped)``: ``arr_span`` the byte span inside
    the array's brackets, two ints (``arr_span[0] < 0`` when the array
    key is absent); ``runs`` an int64 ``[k, 2]`` array of the byte runs of
    ``body`` to keep, in order, to be joined with ``b","``; ``esc`` an
    int64 ``[m, 5]`` array, one row per item whose name or namespace
    holds an escape — (index of its run, namespace span, name span; an
    empty span where the key is missing), raw string content the
    caller decodes and decides, removing the run where it denies;
    ``dropped`` the count of items already left out. None when the
    native path does not apply, the body is neither a Table nor a
    *List, or the scanner bailed (caller falls back to json.loads; the
    scanner is strictly conservative)."""
    lib = _load()
    if lib is None or not isinstance(body, bytes) or not body:
        return None
    p64 = ctypes.POINTER(ctypes.c_int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n_recs = len(offsets) - 1
    if n_recs < 0 or offsets[0] != 0 or offsets[-1] != len(records):
        raise ValueError("record offsets do not span the record buffer")
    arr_span = np.empty(2, dtype=np.int64)
    counts = np.zeros(3, dtype=np.int64)  # items dropped, runs, undecided
    # a kept item names an allowed record, so there are rarely more runs
    # than records and escapes are rare (kube names are DNS labels): start
    # there and grow to what the scanner counted on its overflow code
    max_runs, max_esc = n_recs + 64, 64
    while True:
        runs = np.empty((max_runs, 2), dtype=np.int64)
        esc = np.empty((max_esc, 5), dtype=np.int64)
        rc = lib.json_list_filter(
            body, len(body), records, offsets.ctypes.data_as(p64), n_recs,
            arr_span.ctypes.data_as(p64), runs.ctypes.data_as(p64), max_runs,
            esc.ctypes.data_as(p64), max_esc, counts.ctypes.data_as(p64))
        if rc != -2:
            break
        max_runs, max_esc = int(counts[1]), int(counts[2])
    if rc < 0:
        return None
    return (arr_span.tolist(), runs[:counts[1]], esc[:counts[2]],
            int(counts[0]))


def json_list_keys(body: bytes, read_namespace: bool, read_name: bool):
    """A kube *List or Table response body read in ONE native call that
    holds no interpreter lock (graphcore.cpp json_list_keys), by the same
    scan as :func:`json_list_filter`, for a caller that decides items by
    their keys only after the scan. An item's key is its namespace if
    ``read_namespace``, its name if ``read_name`` (what is unread or
    missing reads empty). Returns ``(arr_span, spans, ids, keys, esc)``:
    ``arr_span`` as :func:`json_list_filter` gives it; ``spans`` an int64
    ``[n, 2]`` array, every item's byte span in ``body``; ``ids`` int32
    ``[n]``, each item's key, dense in the order keys first occur;
    ``keys`` the distinct keys' raw string content, ``k`` namespaces then
    ``k`` names, each ended by ``b"\\x1e"``; ``esc`` int32, the ids of the
    keys whose bytes hold an escape, for the caller to decode (two escapes
    of one string are two keys). None where :func:`json_list_filter`
    gives None."""
    lib = _load()
    if lib is None or not isinstance(body, bytes) or not body:
        return None
    p64 = ctypes.POINTER(ctypes.c_int64)
    p32 = ctypes.POINTER(ctypes.c_int32)
    arr_span = np.empty(2, dtype=np.int64)
    counts = np.zeros(4, dtype=np.int64)  # items, keys, key bytes, escaped
    # items are rarely under 64 bytes: start there and grow to what the
    # scanner counted on its overflow code
    max_items = len(body) // 64 + 1024
    while True:
        spans = np.empty((max_items, 2), dtype=np.int64)
        ids = np.empty(max_items, dtype=np.int32)
        keys = np.empty(len(body) + 2 * max_items, dtype=np.uint8)
        esc = np.empty(max_items, dtype=np.int32)
        rc = lib.json_list_keys(
            body, len(body), int(read_namespace) | 2 * int(read_name),
            arr_span.ctypes.data_as(p64), spans.ctypes.data_as(p64),
            ids.ctypes.data_as(p32), max_items,
            keys.ctypes.data_as(ctypes.c_char_p), esc.ctypes.data_as(p32),
            counts.ctypes.data_as(p64))
        if rc != -2:
            break
        max_items = int(counts[0])
    if rc < 0:
        return None
    n, _, n_bytes, n_esc = counts.tolist()
    return (arr_span.tolist(), spans[:n], ids[:n], keys[:n_bytes].tobytes(),
            esc[:n_esc])


def proto_list_spans(raw: bytes):
    """One-pass scan of a kube-protobuf *List MESSAGE (the Unknown
    envelope's raw field): returns ``(item_spans, keys)`` — full-chunk
    spans (tag included) of every repeated ``items`` element, and the
    same packed key-record buffer the JSON scanner emits
    (``'0' ns 0x1f name 0x1e``; first-occurrence field semantics like
    kubeproto._field) — or None when the native path does not apply or
    the scanner bailed (truncated wire data, control bytes or invalid
    utf-8 in a name: the Python walker keeps authority)."""
    return _proto_spans(raw, "proto_list_spans")


def proto_table_spans(raw: bytes):
    """Like :func:`proto_list_spans` but for a meta.k8s.io Table MESSAGE:
    spans of repeated ``rows`` (field 3), keys from each row's
    ``object`` RawExtension (nested magic-prefixed Unknown or bare
    PartialObjectMetadata — kubeproto.table_row_meta semantics). Bails
    when any row has no keyable object or an empty name (the Python
    walker raises ProtoError there and keeps authority)."""
    return _proto_spans(raw, "proto_table_spans")


def _proto_spans(raw: bytes, fn_name: str):
    lib = _load()
    if lib is None or not isinstance(raw, bytes) or not raw:
        return None
    # start with a realistic bound (items are tens of bytes) and grow on
    # the scanner's overflow code — a degenerate body of 2-byte items
    # would otherwise force a huge upfront allocation
    max_items = len(raw) // 64 + 1024
    p64 = ctypes.POINTER(ctypes.c_int64)
    fn = getattr(lib, fn_name)
    while True:
        item_spans = np.empty(2 * max_items, dtype=np.int64)
        key_buf = ctypes.create_string_buffer(
            len(raw) + 3 * max_items + 16)
        key_len = ctypes.c_int64(0)
        count = fn(
            raw, len(raw), item_spans.ctypes.data_as(p64), key_buf,
            ctypes.byref(key_len), max_items)
        if count == -2 and max_items < len(raw) // 2 + 2:
            max_items = min(max_items * 4, len(raw) // 2 + 2)
            continue
        if count < 0:
            return None
        return (item_spans[:2 * count].reshape(-1, 2),
                ctypes.string_at(key_buf, key_len.value))


def sort_perm(keys: np.ndarray) -> Optional[np.ndarray]:
    """Stable ascending argsort of non-negative int64 keys (LSD radix).
    Returns None when the native path does not apply."""
    lib = _load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if keys.ndim != 1 or (len(keys) and keys.min() < 0):
        return None
    perm = np.empty(len(keys), dtype=np.int64)
    lib.sort_perm_i64(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(keys),
        perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return perm

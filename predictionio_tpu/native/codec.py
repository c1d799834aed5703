"""ctypes wrapper for the native JSON-lines event codec.

``parse_jsonl`` returns a :class:`ParsedEvents` batch: per-field python
string lists (None where absent), epoch-second time arrays, and
per-row validation facts pre-computed in C++. Rows the native parser
could not express 1:1 with python semantics carry ``FALLBACK`` and are
re-parsed by the caller with ``Event.from_json`` — so the codec is
always behavior-identical to the python path, only faster.
"""

from __future__ import annotations

import ctypes
import dataclasses
from typing import List, Optional

import numpy as np

from predictionio_tpu import native

# column ids — keep in sync with src/jsonl_codec.cpp
COL_EVENT = 0
COL_ENTITY_TYPE = 1
COL_ENTITY_ID = 2
COL_TARGET_ENTITY_TYPE = 3
COL_TARGET_ENTITY_ID = 4
COL_PROPERTIES = 5
COL_TAGS = 6
COL_PR_ID = 7
COL_EVENT_ID = 8
COL_EVENT_TIME_RAW = 9
COL_CREATION_TIME_RAW = 10
COL_BAD_PROP_KEY = 11

FALLBACK = 1
PROPS_EMPTY = 2
BAD_PROP_KEY = 4


@dataclasses.dataclass
class ParsedEvents:
    """One parsed file: aligned per-row columns."""

    event: List[Optional[str]]
    entity_type: List[Optional[str]]
    entity_id: List[Optional[str]]
    target_entity_type: List[Optional[str]]
    target_entity_id: List[Optional[str]]
    properties_json: List[Optional[str]]   # raw JSON object text
    tags_json: List[Optional[str]]         # raw JSON array text
    pr_id: List[Optional[str]]
    event_id: List[Optional[str]]
    event_time_raw: List[Optional[str]]
    creation_time_raw: List[Optional[str]]
    bad_prop_key: List[Optional[str]]
    event_time: np.ndarray       # float64 epoch sec; NaN = absent/unparsed
    creation_time: np.ndarray
    flags: np.ndarray            # uint8 bitmask per row
    lineno: np.ndarray           # int64 1-based source line numbers
    line_start: np.ndarray       # raw-buffer byte spans (fallback re-parse)
    line_end: np.ndarray
    # numeric-property extraction (ingest value column), when requested:
    # status 0 = absent/null, 1 = numeric (value in prop_value),
    # 2 = present but non-numeric
    prop_value: Optional[np.ndarray] = None   # float64
    prop_status: Optional[np.ndarray] = None  # uint8
    # dictionary encodings (ingest fast lane), when requested:
    # col id -> (codes int32 [n], first-seen distinct labels). A code of
    # -1 means the column is absent on that row.
    dict_codes: Optional[dict] = None
    dict_labels: Optional[dict] = None

    def __len__(self) -> int:
        return len(self.lineno)


def _lib():
    lib = native.load("jsonl_codec")
    # signatures must be (re)applied per CDLL instance — a module-level
    # flag would leave a freshly reloaded handle with the default c_int
    # restype and truncate 64-bit pointers
    if lib is not None and not getattr(lib, "_pio_sigs", False):
        lib.pio_jsonl_parse.restype = ctypes.c_void_p
        lib.pio_jsonl_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.pio_jsonl_count.restype = ctypes.c_int64
        lib.pio_jsonl_count.argtypes = [ctypes.c_void_p]
        lib.pio_jsonl_col_bytes.restype = ctypes.c_int64
        lib.pio_jsonl_col_bytes.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.pio_jsonl_col_fill.restype = None
        lib.pio_jsonl_col_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8)]
        lib.pio_jsonl_times.restype = None
        lib.pio_jsonl_times.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double)]
        lib.pio_jsonl_flags.restype = None
        lib.pio_jsonl_flags.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint8)]
        lib.pio_jsonl_lines.restype = None
        lib.pio_jsonl_lines.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        lib.pio_jsonl_free.restype = None
        lib.pio_jsonl_free.argtypes = [ctypes.c_void_p]
        lib.pio_jsonl_extract_numeric.restype = None
        lib.pio_jsonl_extract_numeric.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_uint8)]
        lib.pio_jsonl_dict_encode.restype = ctypes.c_void_p
        lib.pio_jsonl_dict_encode.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int32]
        lib.pio_dict_n_labels.restype = ctypes.c_int64
        lib.pio_dict_n_labels.argtypes = [ctypes.c_void_p]
        lib.pio_dict_blob_bytes.restype = ctypes.c_int64
        lib.pio_dict_blob_bytes.argtypes = [ctypes.c_void_p]
        lib.pio_dict_fill.restype = None
        lib.pio_dict_fill.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
        lib.pio_dict_free.restype = None
        lib.pio_dict_free.argtypes = [ctypes.c_void_p]
        lib._pio_sigs = True
    return lib


def is_available() -> bool:
    return _lib() is not None


def _col(lib, handle, col: int, n: int) -> List[Optional[str]]:
    nbytes = lib.pio_jsonl_col_bytes(handle, col)
    data = ctypes.create_string_buffer(max(1, nbytes))
    offsets = np.empty(n + 1, dtype=np.int64)
    present = np.empty(n, dtype=np.uint8)
    lib.pio_jsonl_col_fill(
        handle, col, data,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        present.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    out: List[Optional[str]] = [None] * n
    idx = np.nonzero(present)[0]
    if len(idx) == 0:
        return out
    blob = data.raw[:nbytes].decode("utf-8")
    # offsets are byte offsets; slice the decoded str directly only when
    # the blob is pure ASCII (byte offsets == char offsets)
    if len(blob) == nbytes:
        off = offsets
        for i in idx:
            out[i] = blob[off[i]:off[i + 1]]
    else:
        raw = data.raw
        for i in idx:
            out[i] = raw[offsets[i]:offsets[i + 1]].decode("utf-8")
    return out


def _dict_encode(lib, handle, col: int, n: int):
    """C++ dictionary encoding of one string column: int32 codes per row
    plus the distinct labels (only DISTINCT values ever become Python
    strings — the 10M-row ingest fast lane)."""
    d = lib.pio_jsonl_dict_encode(handle, col)
    try:
        k = lib.pio_dict_n_labels(d)
        nbytes = lib.pio_dict_blob_bytes(d)
        codes = np.empty(n, dtype=np.int32)
        blob = ctypes.create_string_buffer(max(1, nbytes))
        offsets = np.empty(k + 1, dtype=np.int64)
        lib.pio_dict_fill(
            d, codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        raw = blob.raw[:nbytes]
        labels = np.empty(k, dtype=object)
        for i in range(k):
            labels[i] = raw[offsets[i]:offsets[i + 1]].decode("utf-8")
        return codes, labels
    finally:
        lib.pio_dict_free(d)


def parse_jsonl(data: bytes,
                numeric_property: Optional[str] = None,
                columns: Optional[set] = None,
                dict_encode: Optional[set] = None
                ) -> Optional[ParsedEvents]:
    """Parse a JSON-lines event buffer natively; None if the native lib
    is unavailable (callers use the pure-python path then).

    ``numeric_property`` additionally extracts that top-level properties
    key as a numeric column in C++ (``prop_value``/``prop_status``) — the
    training-ingest value column without per-row Python JSON parsing.

    ``columns`` (COL_* ids) restricts which string columns are
    materialized as Python lists — the per-row str construction is the
    dominant decode cost, so bulk-ingest callers fetch only what they
    read; excluded columns are ``None`` on the result.

    ``dict_encode`` (COL_* ids) returns those columns as int32 codes +
    distinct labels instead of per-row strings (``dict_codes``/
    ``dict_labels``). With ``columns=None`` every NON-encoded column is
    still materialized; an encoded column is additionally materialized
    only if explicitly listed in ``columns``."""
    lib = _lib()
    if lib is None:
        return None
    handle = lib.pio_jsonl_parse(data, len(data))
    try:
        n = lib.pio_jsonl_count(handle)
        enc = dict_encode or set()
        cols = [_col(lib, handle, c, n)
                if (c in columns if columns is not None else c not in enc)
                else None
                for c in range(12)]
        et = np.empty(n, dtype=np.float64)
        ct = np.empty(n, dtype=np.float64)
        lib.pio_jsonl_times(
            handle, et.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ct.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        flags = np.empty(n, dtype=np.uint8)
        lib.pio_jsonl_flags(
            handle, flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        starts = np.empty(n, dtype=np.int64)
        ends = np.empty(n, dtype=np.int64)
        lineno = np.empty(n, dtype=np.int64)
        lib.pio_jsonl_lines(
            handle, starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ends.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            lineno.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        parsed = ParsedEvents(
            event=cols[COL_EVENT],
            entity_type=cols[COL_ENTITY_TYPE],
            entity_id=cols[COL_ENTITY_ID],
            target_entity_type=cols[COL_TARGET_ENTITY_TYPE],
            target_entity_id=cols[COL_TARGET_ENTITY_ID],
            properties_json=cols[COL_PROPERTIES],
            tags_json=cols[COL_TAGS],
            pr_id=cols[COL_PR_ID],
            event_id=cols[COL_EVENT_ID],
            event_time_raw=cols[COL_EVENT_TIME_RAW],
            creation_time_raw=cols[COL_CREATION_TIME_RAW],
            bad_prop_key=cols[COL_BAD_PROP_KEY],
            event_time=et, creation_time=ct, flags=flags, lineno=lineno,
            line_start=starts, line_end=ends)
        if numeric_property is not None:
            pv = np.empty(n, dtype=np.float64)
            ps = np.empty(n, dtype=np.uint8)
            kb = numeric_property.encode("utf-8")
            lib.pio_jsonl_extract_numeric(
                handle, kb, len(kb),
                pv.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                ps.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
            parsed.prop_value = pv
            parsed.prop_status = ps
        if enc:
            parsed.dict_codes, parsed.dict_labels = {}, {}
            for c in enc:
                codes, labels = _dict_encode(lib, handle, c, n)
                parsed.dict_codes[c] = codes
                parsed.dict_labels[c] = labels
        return parsed
    finally:
        lib.pio_jsonl_free(handle)


# ---------------------------------------------------------------------------
# Ingest kernels — vectorized merge/pad/bucketize host passes
# (lib ingest_kernels; the 35s monolithic bucketize pass of the round-4 bench).
# Each wrapper returns None when the native lib is unavailable; callers
# fall back to the byte-identical numpy path.
# ---------------------------------------------------------------------------

def _ingest_lib():
    lib = native.load("ingest_kernels")
    # signatures (re)applied per CDLL instance, as in _lib()
    if lib is not None and not getattr(lib, "_pio_sigs", False):
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.pio_merge_runs_i64.restype = None
        lib.pio_merge_runs_i64.argtypes = [
            i64p, i64p, ctypes.c_int32, ctypes.c_int64, i64p]
        lib.pio_bucket_fill.restype = None
        lib.pio_bucket_fill.argtypes = [
            ctypes.c_int64, i64p, i64p,
            ctypes.POINTER(ctypes.c_float), i64p,
            ctypes.POINTER(ctypes.c_int32), i64p, ctypes.c_int32, i64p,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]
        lib.pio_segment_starts_i64.restype = ctypes.c_int64
        lib.pio_segment_starts_i64.argtypes = [i64p, ctypes.c_int64, i64p]
        lib._pio_sigs = True
    return lib


def ingest_kernels_available() -> bool:
    return _ingest_lib() is not None


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def merge_sorted_runs(keys: np.ndarray,
                      offsets: np.ndarray) -> Optional[np.ndarray]:
    """Stable k-way merge permutation over contiguous sorted int64 runs
    (run r = ``keys[offsets[r]:offsets[r+1]]``, each ascending).
    Bit-identical to ``np.argsort(keys, kind="stable")``; O(N log k)
    instead of a full sort, and the GIL is released for the whole merge.
    None when the native lib is unavailable."""
    lib = _ingest_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = int(keys.shape[0])
    perm = np.empty(n, dtype=np.int64)
    lib.pio_merge_runs_i64(_i64p(keys), _i64p(offsets),
                           len(offsets) - 1, n, _i64p(perm))
    return perm


def segment_starts(sorted_keys: np.ndarray) -> Optional[np.ndarray]:
    """Start index of each equal-key segment in a SORTED int64 array —
    the grouping step of dedup-sum (identical to
    ``np.flatnonzero(np.r_[True, k[1:] != k[:-1]])``). None when the
    native lib is unavailable."""
    lib = _ingest_lib()
    if lib is None:
        return None
    sorted_keys = np.ascontiguousarray(sorted_keys, dtype=np.int64)
    n = int(sorted_keys.shape[0])
    out = np.empty(max(1, n), dtype=np.int64)
    m = lib.pio_segment_starts_i64(_i64p(sorted_keys), n, _i64p(out))
    return out[:m]


def bucket_fill(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                pos: np.ndarray, b_of_row: np.ndarray, rank: np.ndarray,
                tables) -> bool:
    """One-pass scatter of row-sorted deduped triples into per-bucket
    padded tables (``tables`` = list of ``(cols_i32, w_f32, m_f32)``
    C-contiguous zeroed arrays, one per bucket, each ``[Bp, L_b]``).
    Pure data movement — byte-identical to the numpy per-bucket
    mask+scatter, but one pass over N instead of one per bucket.
    False when the native lib is unavailable (caller uses numpy)."""
    lib = _ingest_lib()
    if lib is None:
        return False
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.float32)
    pos = np.ascontiguousarray(pos, dtype=np.int64)
    b_of_row = np.ascontiguousarray(b_of_row, dtype=np.int32)
    rank = np.ascontiguousarray(rank, dtype=np.int64)
    nb = len(tables)
    L = np.asarray([t[0].shape[1] for t in tables], dtype=np.int64)
    c_pp = (ctypes.POINTER(ctypes.c_int32) * nb)(*[
        t[0].ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        for t in tables])
    w_pp = (ctypes.POINTER(ctypes.c_float) * nb)(*[
        t[1].ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        for t in tables])
    m_pp = (ctypes.POINTER(ctypes.c_float) * nb)(*[
        t[2].ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        for t in tables])
    lib.pio_bucket_fill(
        len(rows), _i64p(rows), _i64p(cols),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), _i64p(pos),
        b_of_row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _i64p(rank), nb, _i64p(L), c_pp, w_pp, m_pp)
    return True

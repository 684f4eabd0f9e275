"""Part-key index backed by the C++ posting-list core (reference analog:
PartKeyTantivyIndex.scala:38 + the 6.3k-line Rust tantivy crate — the
drop-in second implementation of the PartKeyIndex API, exercised by the
same shared-behavior test suite as the Python index, mirroring the
reference's PartKeyIndexRawSpec pattern).

Equality-AND + time-overlap queries run in C++; regex/negative matchers and
label introspection use the Python-side tag mirror (the reference keeps
tantivy's term dictionaries for the same purpose).
"""

from __future__ import annotations

import ctypes
import re
from typing import Iterable, Mapping, Sequence

import numpy as np

from ..core.filters import ColumnFilter
from ..native import NativeLib
from .index import _LITERAL_ALT, PartKeyIndex, regex_literal_prefix


def _bind(L) -> None:
    c_charpp = ctypes.POINTER(ctypes.c_char_p)
    c_longp = ctypes.POINTER(ctypes.c_long)
    c_i32p = ctypes.POINTER(ctypes.c_int32)
    L.fdb_idx_new.restype = ctypes.c_void_p
    L.fdb_idx_free.argtypes = [ctypes.c_void_p]
    L.fdb_idx_add.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
        c_charpp, c_longp, c_charpp, c_longp, ctypes.c_int64, ctypes.c_int64,
    ]
    L.fdb_idx_update_end.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64]
    L.fdb_idx_remove.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, c_charpp, c_longp, c_charpp, c_longp,
    ]
    L.fdb_idx_query.restype = ctypes.c_long
    L.fdb_idx_query.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, c_charpp, c_longp, c_charpp, c_longp,
        ctypes.c_int64, ctypes.c_int64, c_i32p, ctypes.c_long,
    ]
    L.fdb_idx_all.restype = ctypes.c_long
    L.fdb_idx_all.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, c_i32p, ctypes.c_long]
    L.fdb_idx_size.restype = ctypes.c_long
    L.fdb_idx_size.argtypes = [ctypes.c_void_p]
    L.fdb_idx_values_prefix.restype = ctypes.c_long
    L.fdb_idx_values_prefix.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
        ctypes.c_char_p, ctypes.c_long,
        ctypes.c_char_p, ctypes.c_long, c_longp,
    ]
    L.fdb_idx_union.restype = ctypes.c_long
    L.fdb_idx_union.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
        ctypes.c_int32, c_charpp, c_longp,
        ctypes.c_int64, ctypes.c_int64, c_i32p, ctypes.c_long,
    ]
    L.fdb_idx_union_prefix.restype = ctypes.c_long
    L.fdb_idx_union_prefix.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
        ctypes.c_char_p, ctypes.c_long,
        ctypes.c_int64, ctypes.c_int64, c_i32p, ctypes.c_long,
    ]


_INDEX = NativeLib("filodbindex", "index.cpp", ("-O3",), _bind)


def _load():
    return _INDEX.load()


# regex_literal_prefix moved to memstore/index.py (the bitmap index's
# dictionary-batched regex path uses the same prefix split); re-exported
# here for backward compatibility.


def native_index_available() -> bool:
    return _load() is not None


def _pack_pairs(tags: Mapping[str, str]):
    keys = [k.encode() for k in tags.keys()]
    vals = [v.encode() for v in tags.values()]
    n = len(keys)
    KeyArr = ctypes.c_char_p * n
    LenArr = ctypes.c_long * n
    return (
        n,
        KeyArr(*keys), LenArr(*[len(k) for k in keys]),
        KeyArr(*vals), LenArr(*[len(v) for v in vals]),
    )


class NativePartKeyIndex(PartKeyIndex):
    """PartKeyIndex with the hot equality path in C++.

    Inherits the Python postings for regex/label APIs (kept in sync) but
    answers pure-equality AND queries from the native core.
    """

    def __init__(self):
        super().__init__()
        L = _load()
        if L is None:
            raise RuntimeError("native index library unavailable")
        self._L = L
        self._h = L.fdb_idx_new()

    def __del__(self):
        try:
            self._L.fdb_idx_free(self._h)
        except Exception:
            pass

    # -- writes kept in both stores ---------------------------------------

    def add_partkey(self, part_id, tags, start_ts, end_ts=2**62):
        super().add_partkey(part_id, tags, start_ts, end_ts)
        n, k, kl, v, vl = _pack_pairs(tags)
        self._L.fdb_idx_add(self._h, part_id, n, k, kl, v, vl, start_ts, min(end_ts, 2**62))

    def update_end_time(self, part_id, end_ts):
        super().update_end_time(part_id, end_ts)
        self._L.fdb_idx_update_end(self._h, part_id, end_ts)

    def remove(self, part_ids: Iterable[int]):
        for pid in list(part_ids):
            tags = self._tags.get(pid)
            if tags is not None:
                n, k, kl, v, vl = _pack_pairs(tags)
                self._L.fdb_idx_remove(self._h, pid, n, k, kl, v, vl)
            super().remove([pid])

    # -- queries ------------------------------------------------------------

    def part_ids_from_filters(self, filters: Sequence[ColumnFilter], start_ts, end_ts, limit=None):
        # equality with "" matches missing tags too (PromQL) — python path
        eq = [f for f in filters if f.op == "=" and f.value != ""]
        # positive anchored regexes that can't match a MISSING tag take the
        # native prefix-range path; everything else stays python
        rex = [
            f for f in filters
            if f.op == "=~" and isinstance(f.value, str) and not f.matches(None)
        ]
        rest = [f for f in filters if not (f.op == "=" and f.value != "") and f not in rex]
        if not eq and not rex:
            return super().part_ids_from_filters(filters, start_ts, end_ts, limit)
        cands = None
        if eq:
            cands = self._query_native(eq, start_ts, end_ts)
        for f in rex:
            ids = self._query_regex_native(f, start_ts, end_ts)
            cands = ids if cands is None else np.intersect1d(
                cands, ids, assume_unique=True
            )
            if not len(cands):
                return np.empty(0, dtype=np.int32)
        if rest:
            keep = [
                p for p in cands.tolist()
                if all(f.matches(self._tags[p].get(f.column)) for f in rest)
            ]
            cands = np.asarray(keep, dtype=np.int32)
        if limit is not None:
            cands = cands[:limit]
        return cands

    def _query_regex_native(self, f: ColumnFilter, start_ts, end_ts) -> np.ndarray:
        """Range-aware anchored regex: narrow the value dictionary to the
        literal-prefix slice in C++, regex-match only that slice, union the
        postings natively (reference tantivy_utils range-aware regex;
        PartKeyTantivyIndex.scala:38)."""
        pattern = f.value
        key = f.column.encode()
        cap = max(len(self._tags), 1)
        out = np.empty(cap, dtype=np.int32)
        optr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        if _LITERAL_ALT.match(pattern):
            # pure literal alternation (a|b|c): native union, no regex
            enc = [v.encode() for v in pattern.split("|")]
            n = len(enc)
            got = self._L.fdb_idx_union(
                self._h, key, len(key), n,
                (ctypes.c_char_p * n)(*enc),
                (ctypes.c_long * n)(*[len(v) for v in enc]),
                start_ts, end_ts, optr, cap,
            )
            return out[: min(got, cap)]
        prefix, remainder = regex_literal_prefix(pattern)
        if remainder == "" or remainder == ".*":
            # pure literal (handled as exact value) or pure prefix match:
            # no per-value regex anywhere
            if remainder == "":
                got = self._L.fdb_idx_union(
                    self._h, key, len(key), 1,
                    (ctypes.c_char_p * 1)(prefix.encode()),
                    (ctypes.c_long * 1)(len(prefix.encode())),
                    start_ts, end_ts, optr, cap,
                )
            else:
                p = prefix.encode()
                got = self._L.fdb_idx_union_prefix(
                    self._h, key, len(key), p, len(p),
                    start_ts, end_ts, optr, cap,
                )
            return out[: min(got, cap)]
        # general anchored regex: fetch the prefix-narrowed candidate
        # values, regex-match them host-side, union the survivors natively
        rx = re.compile(pattern)
        values = self._values_with_prefix(key, prefix.encode())
        matched = [v for v in values if rx.fullmatch(v) is not None]
        if not matched:
            return np.empty(0, dtype=np.int32)
        enc = [v.encode() for v in matched]
        n = len(enc)
        got = self._L.fdb_idx_union(
            self._h, key, len(key), n,
            (ctypes.c_char_p * n)(*enc),
            (ctypes.c_long * n)(*[len(v) for v in enc]),
            start_ts, end_ts, optr, cap,
        )
        return out[: min(got, cap)]

    def _values_with_prefix(self, key: bytes, prefix: bytes) -> list[str]:
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            used = ctypes.c_long(0)
            n = self._L.fdb_idx_values_prefix(
                self._h, key, len(key), prefix, len(prefix),
                buf, cap, ctypes.byref(used),
            )
            if used.value <= cap:
                break
            cap = used.value + 16
        out = []
        raw = buf.raw
        off = 0
        for _ in range(n):
            ln = int.from_bytes(raw[off : off + 4], "little")
            out.append(raw[off + 4 : off + 4 + ln].decode())
            off += 4 + ln
        return out

    def _query_native(self, eq_filters, start_ts, end_ts) -> np.ndarray:
        n = len(eq_filters)
        keys = [f.column.encode() for f in eq_filters]
        vals = [f.value.encode() for f in eq_filters]
        KeyArr = ctypes.c_char_p * n
        LenArr = ctypes.c_long * n
        cap = max(len(self._tags), 1)
        out = np.empty(cap, dtype=np.int32)
        got = self._L.fdb_idx_query(
            self._h, n,
            KeyArr(*keys), LenArr(*[len(k) for k in keys]),
            KeyArr(*vals), LenArr(*[len(v) for v in vals]),
            start_ts, end_ts,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), cap,
        )
        if got < 0:
            return super().part_ids_from_filters(eq_filters, start_ts, end_ts)
        return np.sort(out[: min(got, cap)])

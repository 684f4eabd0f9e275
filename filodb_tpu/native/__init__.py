"""ctypes bindings for the native libraries (reference analog: the Rust
JNI shims, SimdNativeMethods.scala:15 / TantivyNativeMethods).

Each ``lib<stem>.so`` is built with g++ from its committed ``.cpp`` on the
machine that loads it: the build is stamped with the source's hash, the
flags and this machine's boot id, and a library whose stamp does not match
(another host's ``-march=native`` build riding a copied tree, an edited
source) is rebuilt, never reused. Without a working compiler every caller
takes its numpy / pure-Python tier, and ``tiers()`` says so.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("filodb_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))


def _machine_id() -> str:
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return os.uname().nodename


LIBS: dict[str, "NativeLib"] = {}


class NativeLib:
    """One g++-built shared library: build-if-unstamped, load, bind — once
    per process. ``load()`` returns the CDLL or None; ``status`` records
    which tier the process ended on and why."""

    def __init__(self, stem: str, source: str, flags: tuple[str, ...], bind):
        self.so = os.path.join(_HERE, f"lib{stem}.so")
        self.src = os.path.join(_HERE, source)
        self.flags = flags
        self._bind = bind
        self._lock = threading.Lock()
        self._lib = None
        self.status = "not loaded"
        LIBS[stem] = self

    def _stamp(self) -> str:
        with open(self.src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        return f"{digest} {' '.join(self.flags)} {_machine_id()}\n"

    def _build(self, stamp: str) -> str | None:
        """Compile to a temp name and rename (concurrent processes may race
        to build); returns None on success, else the reason."""
        tmp = f"{self.so}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", *self.flags, "-shared", "-fPIC", self.src, "-o", tmp],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp, self.so)
            with open(self.so + ".stamp", "w") as f:
                f.write(stamp)
        except FileNotFoundError:
            return "no compiler (g++ not found)"
        except subprocess.CalledProcessError as e:
            return f"g++ failed: {e.stderr.decode(errors='replace')[-200:]}"
        except (subprocess.TimeoutExpired, OSError) as e:
            return f"build failed: {e}"
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return None

    def load(self):
        if self._lib is not None or self.status != "not loaded":
            return self._lib
        with self._lock:
            if self._lib is not None or self.status != "not loaded":
                return self._lib
            stamp = self._stamp()
            try:
                with open(self.so + ".stamp") as f:
                    fresh = f.read() == stamp and os.path.exists(self.so)
            except OSError:
                fresh = False
            err = None if fresh else self._build(stamp)
            if err is None:
                try:
                    L = ctypes.CDLL(self.so)
                except OSError as e:
                    err = f"load failed: {e}"
            if err is not None:
                self.status = f"fallback: {err}"
                log.warning("native %s unavailable, using the Python tier: %s",
                            os.path.basename(self.so), err)
                return None
            self._bind(L)
            self._lib = L
            self.status = ("native (built here)" if not fresh
                           else "native (this machine's earlier build)")
            return L


def tiers() -> dict[str, str]:
    """{library: tier the process ended on} after attempting every load —
    what chip_smoke.py prints, so "no compiler" is a stated fact."""
    from ..memstore import index_native  # noqa: F401 — registers its lib

    for nl in LIBS.values():
        nl.load()
    return {stem: nl.status for stem, nl in LIBS.items()}


def _bind_codecs(L) -> None:
    L.fdb_nibble_pack.restype = ctypes.c_long
    L.fdb_nibble_pack.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_long,
    ]
    L.fdb_nibble_unpack.restype = ctypes.c_long
    L.fdb_nibble_unpack.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_long,
    ]
    L.fdb_nan_sum.restype = ctypes.c_double
    L.fdb_nan_sum.argtypes = [ctypes.POINTER(ctypes.c_double), ctypes.c_long]
    L.fdb_nan_count.restype = ctypes.c_long
    L.fdb_nan_count.argtypes = [ctypes.POINTER(ctypes.c_double), ctypes.c_long]


_CODECS = NativeLib("filodbcodecs", "codecs.cpp", ("-O3", "-march=native"),
                    _bind_codecs)


def lib():
    """The loaded native codec library, or None when unavailable."""
    return _CODECS.load()


def nibble_pack_native(values: np.ndarray) -> bytes | None:
    L = lib()
    if L is None:
        return None
    v = np.ascontiguousarray(values, dtype=np.uint64)
    n = len(v)
    cap = 2 + n * 9 + (n // 8 + 1) * 2
    out = np.empty(cap, dtype=np.uint8)
    written = L.fdb_nibble_pack(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    if written < 0:
        return None
    return out[:written].tobytes()


def nibble_unpack_native(data: bytes, n: int) -> np.ndarray | None:
    L = lib()
    if L is None:
        return None
    src = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(n, dtype=np.uint64)
    consumed = L.fdb_nibble_unpack(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(src),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
    )
    if consumed < 0:
        return None
    return out


def nan_sum(values: np.ndarray) -> float:
    L = lib()
    v = np.ascontiguousarray(values, dtype=np.float64)
    if L is None:
        return float(np.nansum(v))
    return L.fdb_nan_sum(v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(v))


def nan_count(values: np.ndarray) -> int:
    L = lib()
    v = np.ascontiguousarray(values, dtype=np.float64)
    if L is None:
        return int(np.count_nonzero(~np.isnan(v)))
    return L.fdb_nan_count(v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(v))


# ---------------------------------------------------------------------------
# Cold histogram staging (stage.cpp -> libfilodbstage.so)
# ---------------------------------------------------------------------------

# one int64 row a segment; must mirror struct Seg in stage.cpp. The last two
# columns are stage_measure's, the rest the caller's.
STAGE_SEG_COLS = ("row", "ts", "vals", "n", "clamp", "flags", "lo", "k")
STAGE_INT_VALUES = 1  # the value array is int64 (a decoded chunk), else f64
STAGE_GATED = 2       # a write buffer: skipped unless first/last ts overlap


def _bind_stage(L) -> None:
    L.fdb_stage_measure.restype = ctypes.c_long
    L.fdb_stage_measure.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_long,
    ]
    L.fdb_stage_fill.restype = ctypes.c_long
    L.fdb_stage_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_long, ctypes.c_long,
        ctypes.c_long, ctypes.c_int64, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]


_STAGE = NativeLib("filodbstage", "stage.cpp", ("-O3", "-march=native"),
                   _bind_stage)


def stage_lib():
    """The loaded staging library, or None when unavailable. A
    ``TimeSeriesMemStore`` forces the load where it is made, so a stage
    finds the handle and a query never builds it."""
    return _STAGE.load()


def stage_measure(L, table: np.ndarray, t0: int, t1: int, S: int):
    """Pass 1 over a [segments, 8] int64 table (``STAGE_SEG_COLS``): fills
    its ``lo`` / ``k`` columns and returns (lens int32 [S], longest row)."""
    if (table.dtype != np.int64 or table.ndim != 2 or not table.flags.c_contiguous
            or table.shape[1] != len(STAGE_SEG_COLS)):
        raise ValueError(f"stage table is not int64 [n, {len(STAGE_SEG_COLS)}]")
    lens = np.empty(S, dtype=np.int32)
    longest = L.fdb_stage_measure(table.ctypes.data, len(table), t0, t1,
                                  lens.ctypes.data, S)
    if longest < 0:
        raise ValueError("stage table refused: rows out of order or range")
    return lens, longest


def stage_fill(L, table: np.ndarray, lens: np.ndarray, T: int, B: int,
               base_ms: int, subtract: bool):
    """Pass 2: (ts int32 [S, T], vals f32 [S, T, B], baseline f32 [S, B]),
    every element written once by the call (the interpreter lock is released
    for it), so nothing is pre-filled."""
    S = len(lens)
    out_ts = np.empty((S, T), dtype=np.int32)
    out_vals = np.empty((S, T, B), dtype=np.float32)
    baseline = np.empty((S, B), dtype=np.float32)
    rc = L.fdb_stage_fill(table.ctypes.data, len(table), S, T, B, base_ms,
                          int(subtract), lens.ctypes.data, out_ts.ctypes.data,
                          out_vals.ctypes.data, baseline.ctypes.data)
    if rc < 0:
        raise ValueError("stage table changed between its two passes")
    return out_ts, out_vals, baseline


# ---------------------------------------------------------------------------
# Prometheus text-exposition scanner (promparse.cpp -> libfilodbprom.so)
# ---------------------------------------------------------------------------

# must mirror FdbPromRec in promparse.cpp (x86-64 struct layout, 8-aligned)
PROM_REC_DTYPE = np.dtype(
    {
        "names": ["key_off", "key_len", "value", "ts_ms", "type_code", "flags"],
        "formats": [np.uint32, np.uint32, np.float64, np.int64, np.uint8, np.uint8],
        "offsets": [0, 4, 8, 16, 24, 25],
        "itemsize": 32,
    }
)

TS_ABSENT = np.iinfo(np.int64).min


def _bind_prom(L) -> None:
    for fn in (L.fdb_parse_prom, L.fdb_parse_influx):
        fn.restype = ctypes.c_long
        fn.argtypes = [
            ctypes.c_char_p, ctypes.c_long,
            ctypes.c_void_p, ctypes.c_long,
        ]


_PROM = NativeLib("filodbprom", "promparse.cpp",
                  ("-O3", "-march=native", "-std=c++17"), _bind_prom)


def prom_lib():
    return _PROM.load()


# splitlines() separators the byte scanner cannot see (multi-byte UTF-8):
# payloads containing them take the pure-Python path for exact parity
_UNICODE_SEPS = (b"\xc2\x85", b"\xe2\x80\xa8", b"\xe2\x80\xa9")


def parse_prom_records(payload: bytes):
    """Scan a Prometheus exposition payload natively. Returns a structured
    array (PROM_REC_DTYPE) of records, or None when the native lib is
    unavailable (callers fall back to the Python parser). Never raises on
    content: lines the scanner can't tokenize exactly like Python come back
    flagged (flags=1) for per-line Python parsing."""
    L = prom_lib()
    if L is None:
        return None
    if any(s in payload for s in _UNICODE_SEPS):
        return None
    # every record consumes at least one line; count all separator bytes
    max_out = sum(payload.count(s) for s in b"\n\r\v\f\x1c\x1d\x1e") + 2
    out = np.zeros(max_out, dtype=PROM_REC_DTYPE)
    n = L.fdb_parse_prom(payload, len(payload), out.ctypes.data, max_out)
    if n < 0:  # defensive: max_out is sized from separator count
        return None
    return out[:n]


INFLUX_REC_DTYPE = np.dtype(
    {
        "names": ["key_off", "key_len", "field_off", "field_len", "value",
                  "ts_ms", "flags"],
        "formats": [np.uint32, np.uint32, np.uint32, np.uint32, np.float64,
                    np.int64, np.uint8],
        "offsets": [0, 4, 8, 12, 16, 24, 32],
        "itemsize": 40,
    }
)


def parse_influx_records(payload: bytes):
    """Scan an Influx line-protocol payload natively; None when the lib is
    unavailable. Same defer contract as parse_prom_records."""
    L = prom_lib()
    if L is None:
        return None
    if any(s in payload for s in _UNICODE_SEPS):
        return None
    # a line can hold many fields: size by commas+lines (upper bound)
    max_out = (sum(payload.count(s) for s in b"\n\r\v\f\x1c\x1d\x1e")
               + payload.count(b",") + 2)
    out = np.zeros(max_out, dtype=INFLUX_REC_DTYPE)
    n = L.fdb_parse_influx(payload, len(payload), out.ctypes.data, max_out)
    if n < 0:
        return None
    return out[:n]


# ---------------------------------------------------------------------------
# Prometheus JSON sample renderer (promrender.cpp -> libfilodbrender.so)
# ---------------------------------------------------------------------------

_render_scratch = threading.local()


def _bind_render(L) -> None:
    for name, vt in (("fdb_render_values_f64", ctypes.POINTER(ctypes.c_double)),
                     ("fdb_render_values_f32", ctypes.POINTER(ctypes.c_float))):
        fn = getattr(L, name)
        fn.restype = ctypes.c_long
        fn.argtypes = [ctypes.POINTER(ctypes.c_double), vt,
                       ctypes.c_long, ctypes.c_void_p, ctypes.c_long]
    for name, vt in (("fdb_render_matrix_f64", ctypes.POINTER(ctypes.c_double)),
                     ("fdb_render_matrix_f32", ctypes.POINTER(ctypes.c_float))):
        fn = getattr(L, name)
        fn.restype = ctypes.c_longlong
        fn.argtypes = [ctypes.POINTER(ctypes.c_double), vt,
                       ctypes.c_longlong, ctypes.c_longlong,
                       ctypes.c_void_p, ctypes.c_longlong,
                       ctypes.POINTER(ctypes.c_longlong)]
    L.fdb_format_double.restype = ctypes.c_int
    L.fdb_format_double.argtypes = [ctypes.c_double, ctypes.c_char_p]
    L.fdb_fmt_slow_count.restype = ctypes.c_long
    L.fdb_fmt_slow_count.argtypes = []


_RENDER = NativeLib("filodbrender", "promrender.cpp",
                    ("-O3", "-march=native", "-std=c++17"), _bind_render)


def render_lib():
    return _RENDER.load()


def render_values(ts_s: np.ndarray, vals: np.ndarray):
    """Render [[t,"v"],...] (NaN samples skipped) natively; None when the
    lib is unavailable (callers fall back to the Python renderer)."""
    L = render_lib()
    if L is None:
        return None
    ts = np.ascontiguousarray(ts_s, dtype=np.float64)
    n = len(ts)
    cap = 64 * n + 16
    # thread-local reusable scratch + a copy of only the written bytes: the
    # previous create_string_buffer + .raw[:nw] zero-filled AND copied the
    # full 64*n capacity every call (and freshly-mapped pages fault during
    # the render), capping large renders at ~2-3 Msamples/s by memory traffic
    out = getattr(_render_scratch, "buf", None)
    if out is None or len(out) < cap:
        out = np.empty(max(cap, 1 << 20), dtype=np.uint8)
        _render_scratch.buf = out
    if vals.dtype == np.float32:
        v = np.ascontiguousarray(vals, dtype=np.float32)
        nw = L.fdb_render_values_f32(
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n,
            out.ctypes.data, cap)
    else:
        v = np.ascontiguousarray(vals, dtype=np.float64)
        nw = L.fdb_render_values_f64(
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n,
            out.ctypes.data, cap)
    if nw < 0:
        return None
    return out[:nw].tobytes()


def render_matrix_rows(ts_s: np.ndarray, vals: np.ndarray):
    """Render a [G,J] matrix as G per-series [[t,"v"],...] fragments in ONE
    native call (per-row ctypes dispatch costs ~2us, which dominates small
    rows); returns a list of G bytes objects, or None when the lib is
    unavailable."""
    L = render_lib()
    if L is None or vals.ndim != 2:
        return None
    ts = np.ascontiguousarray(ts_s, dtype=np.float64)
    G, J = vals.shape
    if len(ts) != J:
        return None
    cap = 64 * G * J + 4 * G + 16
    out = getattr(_render_scratch, "buf", None)
    if out is None or len(out) < cap:
        out = np.empty(max(cap, 1 << 20), dtype=np.uint8)
        _render_scratch.buf = out
    offs = np.empty(G + 1, dtype=np.int64)
    offs_p = offs.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
    if vals.dtype == np.float32:
        v = np.ascontiguousarray(vals, dtype=np.float32)
        nw = L.fdb_render_matrix_f32(
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            G, J, out.ctypes.data, cap, offs_p)
    else:
        v = np.ascontiguousarray(vals, dtype=np.float64)
        nw = L.fdb_render_matrix_f64(
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            G, J, out.ctypes.data, cap, offs_p)
    if nw < 0:
        return None
    raw = out[:nw].tobytes()
    return [raw[offs[g]:offs[g + 1]] for g in range(G)]


def format_double(v: float) -> str | None:
    """repr(float(v)) via the native formatter; None when unavailable.
    Exposed for the byte-parity torture test."""
    L = render_lib()
    if L is None:
        return None
    buf = ctypes.create_string_buffer(40)
    n = L.fdb_format_double(float(v), buf)
    return buf.raw[:n].decode()

"""ctypes binding for the native columnar chunk engine (native/engine.cpp).

The C++ engine plays the at-rest role HBase's block encoding + compaction
played for the reference (CompactionQueue.java:40-56 — pack cells so the
per-cell overhead amortizes): per-series sealed chunks hold
delta-of-delta/zig-zag varint timestamps and Gorilla-style XOR'd values,
with an is-int bitmap preserving Java-long exactness.

The Python hot path stays columnar numpy/JAX; the engine serves as the
compressed binary snapshot codec (storage/persist.py) — orders of magnitude
denser than the JSONL/npz round 1 shipped and loaded with one C pass.  The
shared library builds from source on first use (``make -C native``); every
entry point degrades to the pure-Python path when the toolchain is absent.
"""

from __future__ import annotations

import codecs
import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

LOG = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "native")
_LIB_NAME = "libtsdb_engine.so"

_lock = threading.Lock()
_lib = None
_load_attempted = False

_I64 = ctypes.c_int64
_I32 = ctypes.c_int32
_F64 = ctypes.c_double
_U8P = ctypes.POINTER(ctypes.c_uint8)
_I64P = ctypes.POINTER(ctypes.c_int64)
_F64P = ctypes.POINTER(ctypes.c_double)


def _configure(lib) -> None:
    lib.eng_create.restype = ctypes.c_void_p
    lib.eng_destroy.argtypes = [ctypes.c_void_p]
    lib.eng_series.argtypes = [ctypes.c_void_p, ctypes.c_char_p, _I32]
    lib.eng_series.restype = _I64
    lib.eng_num_series.argtypes = [ctypes.c_void_p]
    lib.eng_num_series.restype = _I32
    lib.eng_series_key.argtypes = [ctypes.c_void_p, _I64, _U8P, _I32]
    lib.eng_series_key.restype = _I32
    lib.eng_append_batch.argtypes = [
        ctypes.c_void_p, _I64, _I64P, _F64P, _I64P, _U8P, _I64]
    lib.eng_series_len.argtypes = [ctypes.c_void_p, _I64]
    lib.eng_series_len.restype = _I64
    lib.eng_series_bytes.argtypes = [ctypes.c_void_p, _I64]
    lib.eng_series_bytes.restype = _I64
    lib.eng_window.argtypes = [ctypes.c_void_p, _I64, _I64, _I64,
                               _I64P, _F64P, _I64P, _U8P, _I64]
    lib.eng_window.restype = _I64
    lib.eng_window_raw.argtypes = [ctypes.c_void_p, _I64,
                                   _I64P, _F64P, _I64P, _U8P, _I64]
    lib.eng_window_raw.restype = _I64
    lib.eng_delete_range.argtypes = [ctypes.c_void_p, _I64, _I64, _I64]
    lib.eng_delete_range.restype = _I64
    lib.eng_normalize.argtypes = [ctypes.c_void_p, _I64]
    lib.eng_total_bytes.argtypes = [ctypes.c_void_p]
    lib.eng_total_bytes.restype = _I64
    lib.eng_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.eng_save.restype = _I32
    lib.eng_load.argtypes = [ctypes.c_char_p]
    lib.eng_load.restype = ctypes.c_void_p
    # bulk put parser
    lib.eng_put_parse.argtypes = [ctypes.c_char_p, _I64]
    lib.eng_put_parse.restype = ctypes.c_void_p
    lib.eng_put_free.argtypes = [ctypes.c_void_p]
    lib.eng_put_npoints.argtypes = [ctypes.c_void_p]
    lib.eng_put_npoints.restype = _I64
    lib.eng_put_ngroups.argtypes = [ctypes.c_void_p]
    lib.eng_put_ngroups.restype = _I64
    for name, ptr in (("eng_put_ts", _I64P), ("eng_put_fval", _F64P),
                      ("eng_put_ival", _I64P), ("eng_put_isint", _U8P),
                      ("eng_put_group", ctypes.POINTER(_I32)),
                      ("eng_put_spans", _I64P)):
        fn = getattr(lib, name)
        fn.argtypes = [ctypes.c_void_p]
        fn.restype = ptr
    lib.eng_put_group_key.argtypes = [ctypes.c_void_p, _I64]
    lib.eng_put_group_key.restype = ctypes.c_char_p
    lib.eng_put_nerrors.argtypes = [ctypes.c_void_p]
    lib.eng_put_nerrors.restype = _I64
    lib.eng_put_error.argtypes = [ctypes.c_void_p, _I64, _I64P,
                                  ctypes.POINTER(ctypes.c_char_p)]
    lib.eng_put_error.restype = ctypes.c_char_p
    # telnet put-line batch parser
    lib.eng_telnet_parse.argtypes = [ctypes.c_char_p, _I64]
    lib.eng_telnet_parse.restype = ctypes.c_void_p
    lib.eng_telnet_free.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_batch.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_batch.restype = ctypes.c_void_p
    lib.eng_telnet_nlines.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_nlines.restype = _I64
    lib.eng_telnet_status.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_status.restype = ctypes.POINTER(ctypes.c_int8)
    lib.eng_telnet_spans.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_spans.restype = _I64P
    lib.eng_telnet_point.argtypes = [ctypes.c_void_p]
    lib.eng_telnet_point.restype = ctypes.POINTER(_I32)
    # a grouped answer's points as JSON text
    lib.eng_emit_rows.argtypes = [_F64P, _I64, _I64P, _I64, ctypes.c_char_p,
                                  _I64P, _U8P, _I64, _I64P]
    lib.eng_emit_rows.restype = _I64


def _load_library():
    """Load (building if needed) the shared library; None on failure."""
    global _lib, _load_attempted
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        path = os.environ.get("TSDB_NATIVE_LIB") or os.path.join(
            _NATIVE_DIR, _LIB_NAME)
        if path.startswith(_NATIVE_DIR):
            src = os.path.join(_NATIVE_DIR, "engine.cpp")
            stale = (not os.path.exists(path)
                     or (os.path.exists(src)
                         and os.path.getmtime(src) > os.path.getmtime(path)))
            if stale:
                # build under a name of this process's own and rename it
                # into place: several processes (pytest -n 6 on a fresh
                # checkout) build at once, and a reader must never
                # dlopen a file the linker is still writing
                tmp = "%s.%d.tmp" % (_LIB_NAME, os.getpid())
                tmp_path = os.path.join(_NATIVE_DIR, tmp)
                try:
                    subprocess.run(["make", "-C", _NATIVE_DIR, "-B",
                                    "lib=" + tmp],
                                   capture_output=True, timeout=120,
                                   check=True)
                    os.replace(tmp_path, path)
                except (OSError, subprocess.SubprocessError) as e:
                    LOG.warning("native engine build failed (%s); falling "
                                "back to the pure-Python snapshot codec", e)
                    if os.path.exists(tmp_path):
                        os.remove(tmp_path)
                    if not os.path.exists(path):
                        return None
        try:
            lib = ctypes.CDLL(path)
            _configure(lib)
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: a stale prebuilt .so missing a newer export —
            # degrade to the pure-Python codec rather than crash.
            LOG.warning("native engine unavailable (%s)", e)
        return _lib


def available() -> bool:
    return _load_library() is not None


class NativeEngine:
    """One engine instance: keyed compressed series + binary save/load."""

    def __init__(self, handle=None):
        lib = _load_library()
        if lib is None:
            raise RuntimeError("native engine library unavailable")
        self._lib = lib
        self._handle = handle if handle is not None else lib.eng_create()

    @classmethod
    def load(cls, path: str) -> "NativeEngine":
        lib = _load_library()
        if lib is None:
            raise RuntimeError("native engine library unavailable")
        handle = lib.eng_load(path.encode())
        if not handle:
            raise IOError("cannot load native snapshot: " + path)
        return cls(handle=handle)

    def close(self) -> None:
        if self._handle:
            self._lib.eng_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -------------------------------------------------------------- #

    def series(self, key: bytes) -> int:
        """Stable id for a series key (created on first use)."""
        return self._lib.eng_series(self._handle, key, len(key))

    def num_series(self) -> int:
        return self._lib.eng_num_series(self._handle)

    def series_key(self, sid: int) -> bytes:
        n = self._lib.eng_series_key(
            self._handle, sid, ctypes.cast(ctypes.create_string_buffer(0),
                                           _U8P), 0)
        buf = ctypes.create_string_buffer(n)
        self._lib.eng_series_key(self._handle, sid,
                                 ctypes.cast(buf, _U8P), n)
        return buf.raw[:n]

    def append_batch(self, sid: int, ts: np.ndarray, fval: np.ndarray,
                     ival: np.ndarray, is_int: np.ndarray) -> None:
        n = len(ts)
        if n == 0:
            return
        ts = np.ascontiguousarray(ts, np.int64)
        fval = np.ascontiguousarray(fval, np.float64)
        ival = np.ascontiguousarray(ival, np.int64)
        is_int = np.ascontiguousarray(is_int, np.uint8)
        self._lib.eng_append_batch(
            self._handle, sid,
            ts.ctypes.data_as(_I64P), fval.ctypes.data_as(_F64P),
            ival.ctypes.data_as(_I64P), is_int.ctypes.data_as(_U8P), n)

    def series_len(self, sid: int) -> int:
        return self._lib.eng_series_len(self._handle, sid)

    def series_bytes(self, sid: int) -> int:
        return self._lib.eng_series_bytes(self._handle, sid)

    def total_bytes(self) -> int:
        return self._lib.eng_total_bytes(self._handle)

    def _materialize(self, fn, sid: int, *mid_args):
        """Shared column-buffer marshalling for the window reads.

        The buffers are sized by the series' RESIDENT length (store
        state, bounded by ingest), never by a request field — hence the
        taint suppressions."""
        cap = self.series_len(sid)
        ts = np.empty(cap, np.int64)     # tsdblint: disable=taint-unsanitized-alloc
        fval = np.empty(cap, np.float64)  # tsdblint: disable=taint-unsanitized-alloc
        ival = np.empty(cap, np.int64)   # tsdblint: disable=taint-unsanitized-alloc
        is_int = np.empty(cap, np.uint8)  # tsdblint: disable=taint-unsanitized-alloc
        n = fn(self._handle, sid, *mid_args,
               ts.ctypes.data_as(_I64P), fval.ctypes.data_as(_F64P),
               ival.ctypes.data_as(_I64P), is_int.ctypes.data_as(_U8P), cap)
        return (ts[:n], fval[:n], ival[:n], is_int[:n].astype(bool))

    def window(self, sid: int, start: int = -(1 << 62),
               end: int = 1 << 62):
        """Materialize [start, end] -> (ts, fval, ival, is_int) arrays."""
        return self._materialize(self._lib.eng_window, sid, start, end)

    def window_raw(self, sid: int):
        """Full materialization with duplicate timestamps preserved.

        Snapshot-restore path: a series persisted with unresolved duplicate
        timestamps (tsd.storage.fix_duplicates=false) must restore dirty so
        reads keep raising and fsck can repair it — eng_window's
        last-write-wins dedup would silently heal it.
        """
        return self._materialize(self._lib.eng_window_raw, sid)

    def delete_range(self, sid: int, start: int, end: int) -> int:
        return self._lib.eng_delete_range(self._handle, sid, start, end)

    def normalize(self, sid: int) -> None:
        self._lib.eng_normalize(self._handle, sid)

    def save(self, path: str) -> None:
        if self._lib.eng_save(self._handle, path.encode()) != 0:
            raise IOError("cannot write native snapshot: " + path)


class ParsedPutBatch:
    """Columnar view of one parsed /api/put body (native fast path).

    Wraps the C++ parse result: validated + normalized point columns, a
    distinct-series key table, and per-point error messages mirroring the
    Python path's exception strings.  Columns are COPIED out so the
    native buffer frees eagerly.
    """

    __slots__ = ("n", "ts", "fval", "ival", "isint", "group", "spans",
                 "errors", "group_keys")

    def __init__(self, lib, handle):
        n = lib.eng_put_npoints(handle)
        g = lib.eng_put_ngroups(handle)
        self.n = n

        def col(fn, dtype, count):
            ptr = fn(handle)
            return np.ctypeslib.as_array(ptr, shape=(count,)).copy() \
                if count else np.empty(0, dtype)

        self.ts = col(lib.eng_put_ts, np.int64, n)
        self.fval = col(lib.eng_put_fval, np.float64, n)
        self.ival = col(lib.eng_put_ival, np.int64, n)
        self.isint = col(lib.eng_put_isint, np.uint8, n).astype(bool)
        self.group = col(lib.eng_put_group, np.int32, n)
        self.spans = col(lib.eng_put_spans, np.int64, 2 * n).reshape(n, 2) \
            if n else np.empty((0, 2), np.int64)
        self.errors = []            # [(index, kind, message)]
        kind_p = ctypes.c_char_p()
        idx_p = ctypes.c_int64()
        # error/group counts are bounded by the points in the already-
        # received body — proportional, not amplified
        # tsdblint: disable=taint-unsanitized-alloc
        for j in range(lib.eng_put_nerrors(handle)):
            msg = lib.eng_put_error(handle, j, ctypes.byref(idx_p),
                                    ctypes.byref(kind_p))
            self.errors.append((int(idx_p.value),
                                (kind_p.value or b"").decode(),
                                (msg or b"").decode()))
        self.group_keys = []        # [(metric, {tagk: tagv})]
        # same already-received-body bound as the error loop above
        # tsdblint: disable=taint-unsanitized-alloc
        for gi in range(g):
            raw = lib.eng_put_group_key(handle, gi).decode()
            parts = raw.split("\x1f")
            tags = {}
            for pair in parts[1:]:
                k, _, v = pair.partition("\x1e")
                tags[k] = v
            self.group_keys.append((parts[0], tags))


LINE_OK, LINE_ERROR, LINE_FALLBACK = 0, 1, 2


class ParsedTelnetBatch:
    """Columnar view of one parsed telnet put-line block.

    `points` is the shared ParsedPutBatch column view; per-LINE arrays
    map each non-blank line to its outcome: OK/ERROR lines carry the
    point index they produced, FALLBACK lines (exotic grammar the parser
    refuses to mirror) carry their byte span so the caller can replay
    just those through the per-line Python handler.
    """

    __slots__ = ("points", "n_lines", "status", "spans", "point_index")

    def __init__(self, lib, handle):
        self.points = ParsedPutBatch(lib, lib.eng_telnet_batch(handle))
        n = int(lib.eng_telnet_nlines(handle))
        self.n_lines = n

        def col(fn, count):
            return np.ctypeslib.as_array(fn(handle), shape=(count,)).copy() \
                if count else np.empty(0, np.int64)

        self.status = col(lib.eng_telnet_status, n)
        self.spans = col(lib.eng_telnet_spans, 2 * n).reshape(n, 2) \
            if n else np.empty((0, 2), np.int64)
        self.point_index = col(lib.eng_telnet_point, n)


def parse_telnet_block(block: bytes):
    """Parse a block of telnet put lines natively; None -> Python path."""
    lib = _load_library()
    if lib is None or not hasattr(lib, "eng_telnet_parse"):
        return None
    handle = lib.eng_telnet_parse(block, len(block))
    if not handle:
        return None
    try:
        return ParsedTelnetBatch(lib, handle)
    except UnicodeDecodeError:
        return None
    finally:
        lib.eng_telnet_free(handle)


def parse_put_body(body: bytes):
    """Parse a /api/put JSON body natively; None -> use the Python path.

    None covers: library unavailable, malformed JSON (the Python path
    raises the user-visible parse error), and any construct whose Python
    semantics the native parser refuses to mirror (non-string tags,
    arbitrary-precision timestamps, ...).
    """
    lib = _load_library()
    if lib is None or not hasattr(lib, "eng_put_parse"):
        return None
    handle = lib.eng_put_parse(body, len(body))
    if not handle:
        return None
    try:
        return ParsedPutBatch(lib, handle)
    except UnicodeDecodeError:
        # group keys that aren't valid UTF-8 (the parser guards the
        # known producers of these, e.g. lone surrogates, but a decode
        # failure must degrade to the Python path, never to a 500)
        return None
    finally:
        lib.eng_put_free(handle)


def emit_rows(block: np.ndarray, rows: np.ndarray,
              pieces: list[str]) -> list[str] | None:
    """Rows `rows` of the [G, W] float64 `block`, each as the text
    pieces[0] v0 pieces[1] v1 ... v(W-1) pieces[W], every value written
    as float.__repr__ writes it (eng_emit_rows), in one native pass over
    the block.  Every value of those rows must be finite and every piece
    ASCII.  None where the library is unavailable."""
    lib = _load_library()
    if lib is None:
        return None
    g, w = block.shape
    if (block.dtype != np.float64 or not block.flags.c_contiguous
            or len(pieces) != w + 1):
        raise ValueError("emit_rows: a C-ordered float64 block and one "
                         "piece more than it has columns")
    rows = np.ascontiguousarray(rows, np.int64)
    if len(rows) and (rows.min() < 0 or rows.max() >= g):
        raise ValueError("emit_rows: a row outside the block")
    text = "".join(pieces).encode("ascii")
    piece_off = np.cumsum([0] + [len(p) for p in pieces], dtype=np.int64)
    # a value's repr is at most 24 bytes: -2.2250738585072014e-308
    cap = len(rows) * (len(text) + 24 * w)
    out = np.empty(cap, np.uint8)
    offsets = np.empty(len(rows) + 1, np.int64)
    size = lib.eng_emit_rows(
        block.ctypes.data_as(_F64P), w, rows.ctypes.data_as(_I64P),
        len(rows), text, piece_off.ctypes.data_as(_I64P),
        out.ctypes.data_as(_U8P), cap, offsets.ctypes.data_as(_I64P))
    if size == -2:
        raise ValueError("emit_rows: a NaN or an infinity in a row")
    if size < 0:
        raise RuntimeError("emit_rows: the text outgrew its bound")
    written = codecs.ascii_decode(out[:size])[0]
    ends = offsets.tolist()
    return [written[a:b] for a, b in zip(ends, ends[1:])]

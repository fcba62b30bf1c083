"""``native`` engine: ctypes adapter over the C++ kbstore library.

The embedded single-host engine (the role Badger plays for the reference,
pkg/storage/badger) and the default authoritative host store under the TPU
mirror. Build with ``make -C native``; the adapter builds the library
itself (that target alone) where it is missing or older than its source.

Mapping to the engine contract:
- TSO            → kb_tso (commit counter; badger.go:41-46 uses ReadTs)
- snapshot reads → kb_get / kb_iter_open(snap)
- CAS batches    → kb_batch_* with conflict index + observed value
- TTL            → native (support_ttl=True, entries expire server-side,
                   badger.go:48)
- partitions     → kb_split_keys sampling (the PD-region-map analogue)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from .. import coder
from ..backend.common import KeyValue
from ..backend.scanner import Scanner
from ..trace import TRACER
from . import BatchWrite, Iter, KvStorage, Partition, register_engine
from .errors import CASFailedError, Conflict, KeyNotFoundError, StorageError

_LIB_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "native", "libkbstore.so")
_lib = None
_lib_lock = threading.Lock()


#: the newest entry points: a library without them predates this adapter
_REQUIRED_SYMBOLS = ("kb_mvcc_list_wire", "kb_wire_gather", "kb_wire_read")


def _lib_stale(path: str) -> bool:
    """No library yet, or one older than its source."""
    src = os.path.join(os.path.dirname(path), "kbstore.cc")
    try:
        return os.path.getmtime(path) < os.path.getmtime(src)
    except OSError:
        return not os.path.exists(path)


def _build_lib(path: str) -> None:
    """``make -C native libkbstore.so`` — that target alone: the fronts and
    the store daemon beside it want nghttp2 and ssl, which a process that
    needs the library must not — under a file lock: the test workers and a
    server's children all come here first, and one of them builds (the
    Makefile moves the finished library into place, so a process that has
    the old one mapped is not disturbed)."""
    import fcntl

    native_dir = os.path.dirname(path)
    with open(os.path.join(native_dir, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _lib_stale(path):
            subprocess.run(["make", "-C", native_dir, os.path.basename(path)],
                           check=True, capture_output=True)


def load_lib() -> ctypes.CDLL:
    """The library, built first where it is missing or older than its
    source. Whoever will call into it loads it when constructed
    (``NativeKv``, ``TpuScanner``), so a toolchain that is not there or a
    stale build stops the boot, never a request."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = os.path.abspath(_LIB_PATH)
        if _lib_stale(path):
            # first-use auto-build must be single-flight; every caller
            # needs the lib before it can proceed anyway
            # kblint: disable=KB102,KB112 -- deliberate build-under-lock
            _build_lib(path)
        lib = ctypes.CDLL(path)
        missing = [n for n in _REQUIRED_SYMBOLS if not hasattr(lib, n)]
        if missing:
            # a library older than this adapter (kept by a copy that lost
            # the sources' times, say) must never load: a caller probing
            # for a fast path would quietly take the slow one
            raise StorageError(
                f"{path} lacks {', '.join(missing)}: a stale build; "
                f"run `make -C {os.path.dirname(path)}`")
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.kb_open.restype = ctypes.c_void_p
        lib.kb_open_at.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.kb_open_at.restype = ctypes.c_void_p
        lib.kb_checkpoint.argtypes = [ctypes.c_void_p]
        lib.kb_close.argtypes = [ctypes.c_void_p]
        lib.kb_tso.argtypes = [ctypes.c_void_p]
        lib.kb_tso.restype = ctypes.c_uint64
        lib.kb_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.kb_free.argtypes = [ctypes.c_void_p]
        lib.kb_batch_begin.argtypes = [ctypes.c_void_p]
        lib.kb_batch_begin.restype = ctypes.c_void_p
        for name, extra in [
            ("kb_batch_put", [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int64]),
            ("kb_batch_put_if_absent", [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int64]),
        ]:
            getattr(lib, name).argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, *extra
            ]
        lib.kb_batch_cas.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int64,
        ]
        lib.kb_batch_del.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
        lib.kb_batch_del_current.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.kb_batch_abort.argtypes = [ctypes.c_void_p]
        lib.kb_batch_commit.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(u8p),
            ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
        ]
        lib.kb_iter_open.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_int,
        ]
        lib.kb_iter_open.restype = ctypes.c_void_p
        lib.kb_iter_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.kb_iter_close.argtypes = [ctypes.c_void_p]
        lib.kb_scan_page.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.kb_scan_page.restype = ctypes.c_uint64
        lib.kb_mvcc_list_page.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
        ]
        lib.kb_mvcc_list_page.restype = ctypes.c_uint64
        lib.kb_mvcc_list_wire.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int),
        ]
        lib.kb_mvcc_list_wire.restype = ctypes.c_uint64
        lib.kb_wire_gather.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,  # run descriptors, how many
            ctypes.c_void_p, ctypes.c_size_t,  # out, its capacity
        ]
        lib.kb_wire_gather.restype = ctypes.c_size_t
        lib.kb_wire_read.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,  # partitions (8 words), how many
            ctypes.c_size_t, ctypes.c_void_p,  # key chunks a row, dictionary
            ctypes.c_size_t,                   # overlay entries, then their
            ctypes.c_char_p, ctypes.c_void_p,  # keys: blob, offsets
            ctypes.c_char_p, ctypes.c_void_p,  # values: blob, offsets
            ctypes.c_void_p, ctypes.c_void_p,  # revisions, deletion flags
            ctypes.c_uint64,                   # limit
            ctypes.c_void_p, ctypes.c_size_t,  # out, its capacity
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int),
        ]
        lib.kb_wire_read.restype = ctypes.c_size_t
        lib.kb_split_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.kb_key_count.argtypes = [ctypes.c_void_p]
        lib.kb_key_count.restype = ctypes.c_uint64
        lib.kb_version_count.argtypes = [ctypes.c_void_p]
        lib.kb_version_count.restype = ctypes.c_uint64
        lib.kb_prune.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.kb_prune.restype = ctypes.c_uint64
        lib.kb_bulk_gc.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,  # victims
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_uint64,                                   # rev records
            ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,  # width, magic
        ]
        lib.kb_bulk_gc.restype = ctypes.c_uint64
        lib.kb_mvcc_export_stats.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.kb_mvcc_export_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.kb_mvcc_export_fill.restype = ctypes.c_uint64
        lib.kb_mvcc_delete.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p, ctypes.c_size_t,  # rev_key
            ctypes.c_uint64, ctypes.c_uint64,  # expected, new rev
            ctypes.c_char_p, ctypes.c_size_t,  # new record
            ctypes.c_char_p, ctypes.c_size_t,  # tombstone value
            ctypes.c_char_p, ctypes.c_size_t,  # last_key
            ctypes.c_char_p, ctypes.c_size_t,  # last_val
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.kb_mvcc_write.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p, ctypes.c_size_t,  # rev_key
            ctypes.c_char_p, ctypes.c_size_t,  # rev_val
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,  # expected
            ctypes.c_char_p, ctypes.c_size_t,  # obj_key
            ctypes.c_char_p, ctypes.c_size_t,  # obj_val
            ctypes.c_char_p, ctypes.c_size_t,  # last_key
            ctypes.c_char_p, ctypes.c_size_t,  # last_val
            ctypes.c_int64,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int),
        ]
        _lib = lib
        return lib


#: what ``kb_wire_gather`` reads through raw pointers: a wire source's
#: arrays, in order, each C-contiguous in exactly this dtype
_WIRE_SOURCE_DTYPES = ("uint8", "int32", "uint64", "uint8", "uint64", "int64")


def wire_gather(sources: list[tuple], runs: list[tuple[int, int, int]]) -> bytes:
    """``RangeResponse.kvs`` wire bytes of ``runs`` — ``(source, from, to)``
    row ranges, in the order they go out — through ``kb_wire_gather``. A
    source is six numpy arrays: a decoded key matrix ``uint8[n, W]`` with
    its ``int32`` lengths and ``uint64`` revisions (row-aligned), then a
    ``uint8`` value arena with its ``uint64`` offsets and the ``int64``
    rows that index them (``rows[i]`` is row i's value). Two ``CDLL`` calls
    a reply, whatever its runs (the size, then the bytes): the GIL is
    released while they copy, and given up no more often than that."""
    bases = []
    for src in sources:
        k_u8, k_lens, revs, arena, offsets, rows = src
        if (tuple(str(a.dtype) for a in src) != _WIRE_SOURCE_DTYPES
                or not all(a.flags.c_contiguous for a in src)
                or not len(k_u8) == len(k_lens) == len(revs) == len(rows)):
            # kb_wire_gather reads raw pointers: a wrong dtype or stride
            # would be wrong bytes on the wire, or a read out of bounds
            raise ValueError(
                "wire source: want C-contiguous "
                f"{', '.join(_WIRE_SOURCE_DTYPES)} with row-aligned keys, "
                "lens, revs and rows; got "
                + ", ".join(f"{a.dtype}{list(a.shape)}" for a in src))
        bases.append((k_u8.ctypes.data, k_u8.strides[0], k_lens.ctypes.data,
                      revs.ctypes.data, arena.ctypes.data, offsets.ctypes.data,
                      rows.ctypes.data))
    table = np.array(
        [(k + a * stride, stride, kl + 4 * a, rv + 8 * a, ar, of, rw + 8 * a,
          b - a)
         for (k, stride, kl, rv, ar, of, rw), a, b in (
             (bases[s], a, b) for s, a, b in runs)],
        dtype=np.uint64).reshape(-1, 8)
    lib = _lib or load_lib()  # every read's hot path: no lock once loaded
    need = lib.kb_wire_gather(table.ctypes.data, len(table), None, 0)
    out = np.empty(need, dtype=np.uint8)
    lib.kb_wire_gather(table.ctypes.data, len(table), out.ctypes.data, need)
    return out.tobytes()


#: bytes of one wire row beyond its key and value, at most: five tags, the
#: row's and the value's length (5 each), the key's (2), two revisions (10
#: each) and the version's byte
WIRE_ROW_OVERHEAD = 40
#: ``kb_wire_read``'s answer to an index, a code or an offset it will not read
_SIZE_MAX = ctypes.c_size_t(-1).value
_WIRE_REFUSED = ("wire read: a row index, a key code or a value offset "
                 "outside the mirror's arrays")


def wire_columns(keys: np.ndarray, lens: np.ndarray, revs: np.ndarray,
                 val_arena: list[np.ndarray],
                 val_offsets: list[np.ndarray]) -> np.ndarray:
    """A mirror's host columns as ``kb_wire_read`` reads them: ``uint64[P,
    6]``, a partition a row — where its keys (``uint32[N, C]`` chunks, as
    stored), lengths (``int32[N]``), revisions (``uint64[N]``), value arena
    and value offsets start, and how many rows have a value. Made ONCE a
    mirror (``Mirror.__post_init__``), so a read converts and checks
    nothing: the call reads these through raw pointers, and a column of
    another dtype, stride or length would be wrong bytes on the wire or a
    read out of bounds — refused here. The addresses hold as long as the
    mirror keeps these very arrays, which it does for its life."""
    n_parts, n_rows = lens.shape if lens.ndim == 2 else (-1, -1)
    ok = (keys.dtype == np.uint32 and keys.ndim == 3
          and keys.shape[:2] == (n_parts, n_rows)
          and lens.dtype == np.int32 and revs.dtype == np.uint64
          and revs.shape == lens.shape
          and keys.flags.c_contiguous and lens.flags.c_contiguous
          and revs.flags.c_contiguous
          and len(val_arena) == len(val_offsets) == n_parts
          and all(a.dtype == np.uint8 and a.ndim == 1 and a.flags.c_contiguous
                  for a in val_arena)
          and all(o.dtype == np.uint64 and o.ndim == 1 and len(o) >= 1
                  and o.flags.c_contiguous for o in val_offsets))
    if not ok:
        raise ValueError(
            "wire columns: want C-contiguous uint32[P, N, C] keys, int32[P, N] "
            "lens, uint64[P, N] revs and P uint8 arenas with uint64 offsets; "
            f"got {keys.dtype}{list(keys.shape)}, {lens.dtype}"
            f"{list(lens.shape)}, {revs.dtype}{list(revs.shape)}, "
            + ", ".join(f"{a.dtype}{list(a.shape)}/{o.dtype}{list(o.shape)}"
                        for a, o in zip(val_arena, val_offsets)))
    table = np.empty((n_parts, 6), dtype=np.uint64)
    part = np.arange(n_parts, dtype=np.uint64)
    table[:, 0] = keys.ctypes.data + part * keys.strides[0]
    table[:, 1] = lens.ctypes.data + part * lens.strides[0]
    table[:, 2] = revs.ctypes.data + part * revs.strides[0]
    table[:, 3] = [a.ctypes.data for a in val_arena]
    table[:, 4] = [o.ctypes.data for o in val_offsets]
    table[:, 5] = [min(n_rows, len(o) - 1) for o in val_offsets]
    return table


def _overlay_args(overlay: dict) -> tuple[tuple, int, tuple]:
    """A read's overlay (user key → ``(revision, value)``, None a deletion)
    as ``kb_wire_read`` takes it — how many entries, then in key order their
    keys and values (each one blob with its offsets), revisions and which
    are deletions — with the bytes they can need on the wire and the arrays
    the addresses point into (the caller holds them over the call). A fixed
    number of calls whatever the overlay holds: the comprehensions call
    nothing, and ``map(len, …)`` runs inside ``np.fromiter``."""
    if not overlay:
        return (0, None, None, None, None, None, None), 0, ()
    keys = sorted(overlay)
    entries = [overlay[k] for k in keys]
    values = [b"" if e is None else e[1] for e in entries]
    n = len(keys)
    key_blob, val_blob = b"".join(keys), b"".join(values)
    key_offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter(map(len, keys), np.uint64, n), out=key_offs[1:])
    val_offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(np.fromiter(map(len, values), np.uint64, n), out=val_offs[1:])
    revs = np.array([0 if e is None else e[0] for e in entries], dtype=np.uint64)
    dead = np.array([e is None for e in entries], dtype=np.uint8)
    return ((n, key_blob, key_offs.ctypes.data, val_blob, val_offs.ctypes.data,
             revs.ctypes.data, dead.ctypes.data),
            len(key_blob) + len(val_blob) + n * WIRE_ROW_OVERHEAD,
            (key_offs, val_offs, revs, dead))


def wire_read(columns: np.ndarray, val_offsets: list[np.ndarray],
              key_chunks: int, key_width: int, key_dict: np.ndarray | None,
              counts: np.ndarray, rows: np.ndarray, overlay: dict,
              limit: int = 0) -> tuple[bytes, int, bool]:
    """The host half of a device-path wire read in ONE foreign call
    (``kb_wire_read``: key decode, overlay merge, cut at ``limit``, wire
    encoding), the GIL released for all of it: ``(RangeResponse.kvs bytes,
    rows, more)``. ``columns`` is :func:`wire_columns` of the mirror read
    and ``val_offsets`` its value offsets, ``key_chunks`` the chunks of a
    stored key and ``key_width`` the bytes of a user key at most,
    ``key_dict`` the dictionary's table (``KeyEncoding.wire_table``; None
    for raw keys: the chunks are the key), ``rows[p, :counts[p]]``
    partition p's visible rows as the device handed them back. The reply's
    buffer is sized from what those rows can need at most — a few steps a
    PARTITION, none a row — so the size needs no call of its own; a buffer
    that is short all the same is never written past, and the call is made
    again with the size it answered."""
    if (rows.dtype != np.int32 or rows.ndim != 2 or not rows.flags.c_contiguous
            or counts.shape != (len(rows),) or len(columns) != len(rows)
            or (len(counts) and not 0 <= counts.min() <= counts.max()
                <= rows.shape[1])):
        # kb_wire_read reads raw pointers, counts[p] row indices a partition
        raise ValueError(
            "wire read: want C-contiguous int32[P, size] row indices and P "
            f"counts within them; got {rows.dtype}{list(rows.shape)}, "
            f"{counts.dtype}{list(counts.shape)} for {len(columns)} partitions")
    ps = np.flatnonzero(counts)
    parts = np.empty((len(ps), 8), dtype=np.uint64)
    parts[:, :6] = columns[ps]
    parts[:, 6] = rows.ctypes.data + ps * rows.strides[0]
    parts[:, 7] = counts[ps]
    room = int(parts[:, 7].sum()) * (key_width + WIRE_ROW_OVERHEAD)
    for p in ps.tolist():
        # ascending rows: their values lie between the first's start and
        # the last's end in the partition's arena
        offs, mine = val_offsets[p], rows[p]
        if mine[counts[p] - 1] + 1 >= len(offs):
            raise StorageError(_WIRE_REFUSED)
        room += int(offs[mine[counts[p] - 1] + 1]) - int(offs[mine[0]])
    ov_args, ov_room, _held = _overlay_args(overlay)
    room += ov_room
    n_rows, more = ctypes.c_uint64(), ctypes.c_int()
    lib = _lib or load_lib()  # every read's hot path: no lock once loaded
    while True:
        out = np.empty(room, dtype=np.uint8)
        need = lib.kb_wire_read(
            parts.ctypes.data, len(parts), key_chunks,
            None if key_dict is None else key_dict.ctypes.data, *ov_args,
            limit, out.ctypes.data, room, ctypes.byref(n_rows),
            ctypes.byref(more))
        if need == _SIZE_MAX:
            raise StorageError(_WIRE_REFUSED)
        if need <= room:
            return out[:need].tobytes(), n_rows.value, bool(more.value)
        room = need


class NativeKv(KvStorage):
    def __init__(self, partitions: int = 1, data_dir: str = "", fsync: bool = False):
        self._lib = load_lib()
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            self._store = ctypes.c_void_p(
                self._lib.kb_open_at(data_dir.encode(), 1 if fsync else 0)
            )
            if not self._store:
                raise StorageError(f"failed to open/recover store at {data_dir}")
        else:
            self._store = ctypes.c_void_p(self._lib.kb_open())
        self._n_parts = partitions

    def checkpoint(self) -> None:
        """Write a latest-only snapshot and truncate the WAL."""
        if self._lib.kb_checkpoint(self._store) != 0:
            raise StorageError("checkpoint failed (snapshot write or WAL reopen)")

    def get_timestamp_oracle(self) -> int:
        return int(self._lib.kb_tso(self._store))

    def get_partitions(self, start: bytes, end: bytes) -> list[Partition]:
        n = self._n_parts
        if n <= 1:
            return [Partition(start, end)]
        width = 256
        borders_buf = ctypes.create_string_buffer(width * (n - 1))
        lens = (ctypes.c_size_t * (n - 1))()
        got = self._lib.kb_split_keys(self._store, n, borders_buf, width, lens)
        borders = [start]
        for i in range(got):
            b = borders_buf.raw[i * width : i * width + lens[i]]
            if borders[-1] < b and (not end or b < end):
                borders.append(b)
        borders.append(end)
        return [Partition(borders[i], borders[i + 1]) for i in range(len(borders) - 1)]

    def get(self, key: bytes, snapshot_ts: int | None = None) -> bytes:
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_size_t()
        rc = self._lib.kb_get(
            self._store, key, len(key), snapshot_ts or 0,
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if rc != 0:
            raise KeyNotFoundError(key)
        try:
            return ctypes.string_at(out, out_len.value)
        finally:
            self._lib.kb_free(out)

    def iter(self, start: bytes, end: bytes, snapshot_ts: int | None = None, limit: int = 0) -> Iter:
        reverse = 1 if (end and start > end) else 0
        if not reverse:
            # forward scans page through ONE FFI call per 1024 rows instead
            # of 3 calls + 2 copies per row (the etcd list hot path)
            snap = snapshot_ts or self.get_timestamp_oracle()
            return _PagedNativeIter(self._lib, self._store, start, end, snap, limit)
        handle = self._lib.kb_iter_open(
            self._store, start, len(start), end, len(end),
            snapshot_ts or 0, limit, reverse,
        )
        return _NativeIter(self._lib, handle)

    def begin_batch_write(self) -> BatchWrite:
        return _NativeBatch(self._lib, self._lib.kb_batch_begin(self._store))

    def support_ttl(self) -> bool:
        return True

    def key_count(self) -> int:
        return int(self._lib.kb_key_count(self._store))

    def version_count(self) -> int:
        return int(self._lib.kb_version_count(self._store))

    def prune_versions(self, keep_after_ts: int) -> int:
        """Physically free version history invisible to snapshots >=
        keep_after_ts; returns versions freed."""
        return int(self._lib.kb_prune(self._store, keep_after_ts))

    def write_batch(self, ops: list) -> list:
        """Group-commit executor (docs/writes.md): the shared loop over the
        one-FFI-call MVCC fast paths below — each op is already a single C
        round trip; the group's wins live above the engine (one scheduler
        dispatch, one revision block, one ring pass). A native C grouped op
        (one FFI call for the whole group) is the documented next step."""
        from .groupwrite import mvcc_write_batch

        return mvcc_write_batch(self, ops)

    def mvcc_write(
        self,
        rev_key: bytes,
        rev_val: bytes,
        expected: bytes | None,
        obj_key: bytes,
        obj_val: bytes,
        last_key: bytes,
        last_val: bytes,
        ttl_seconds: int = 0,
    ) -> None:
        """One-FFI-call MVCC write: conditional revision record + object row
        + last-revision watermark, atomic. Raises CASFailedError with the
        observed record on conflict."""
        cv = ctypes.POINTER(ctypes.c_uint8)()
        cl = ctypes.c_size_t()
        ch = ctypes.c_int(0)
        rc = self._lib.kb_mvcc_write(
            self._store,
            rev_key, len(rev_key), rev_val, len(rev_val),
            expected or b"", len(expected or b""), 1 if expected is not None else 0,
            obj_key, len(obj_key), obj_val, len(obj_val),
            last_key, len(last_key), last_val, len(last_val),
            ttl_seconds,
            ctypes.byref(cv), ctypes.byref(cl), ctypes.byref(ch),
        )
        if rc == 2:
            raise StorageError("WAL append failed; commit aborted")
        if rc == 1:
            observed = None
            if ch.value:
                observed = ctypes.string_at(cv, cl.value)
                self._lib.kb_free(cv)
            raise CASFailedError(Conflict(0, rev_key, observed))

    def mvcc_delete(
        self,
        rev_key: bytes,
        expected_rev: int,
        new_rev: int,
        new_record: bytes,
        tombstone: bytes,
        last_key: bytes,
        last_val: bytes,
    ) -> tuple[str, bytes | None, int]:
        """One-call read-validate-tombstone delete. Returns
        (outcome, prev_value, latest_rev) with outcome in
        {"ok", "not_found", "mismatch"}; raises on WAL failure/drift."""
        pv = ctypes.POINTER(ctypes.c_uint8)()
        pl = ctypes.c_size_t(0)
        latest = ctypes.c_uint64(0)
        rc = self._lib.kb_mvcc_delete(
            self._store, rev_key, len(rev_key),
            expected_rev, new_rev, new_record, len(new_record),
            tombstone, len(tombstone), last_key, len(last_key),
            last_val, len(last_val),
            ctypes.byref(pv), ctypes.byref(pl), ctypes.byref(latest),
        )
        # free whenever the C side filled the buffer, regardless of rc —
        # rc 4 (revision drift) also mallocs prev_val before its check
        prev = None
        if pl.value:
            prev = ctypes.string_at(pv, pl.value)
            self._lib.kb_free(pv)
        if rc == 0:
            return "ok", prev, int(latest.value)
        if rc == 1:
            # latest = the tombstone's revision (0 when truly absent) — the
            # backend fences its read floor on it (_await_revealed)
            return "not_found", None, int(latest.value)
        if rc == 2:
            return "mismatch", prev, int(latest.value)
        if rc == 3:
            raise StorageError("WAL append failed; delete aborted")
        from .errors import RevisionDriftBackError

        raise RevisionDriftBackError(
            f"revision drift on delete (latest {latest.value})",
            latest=int(latest.value))

    def export_mvcc(
        self,
        start: bytes,
        end: bytes,
        snapshot_ts: int,
        key_width: int,
        magic: bytes,
        tombstone: bytes,
    ):
        """Bulk-export version rows as numpy arrays (the TPU-mirror rebuild
        fast path): (keys uint8[N, W], lens int32[N], revs uint64[N],
        tomb bool[N], value_arena bytes, offsets uint64[N+1])."""
        import numpy as np

        n_rows = ctypes.c_uint64()
        val_bytes = ctypes.c_uint64()
        self._lib.kb_mvcc_export_stats(
            self._store, start, len(start), end, len(end), snapshot_ts,
            magic, len(magic), ctypes.byref(n_rows), ctypes.byref(val_bytes),
        )
        n = int(n_rows.value)
        keys = np.zeros((n, key_width), dtype=np.uint8)
        lens = np.zeros(n, dtype=np.int32)
        revs = np.zeros(n, dtype=np.uint64)
        tomb = np.zeros(n, dtype=np.uint8)
        arena = np.zeros(int(val_bytes.value), dtype=np.uint8)
        offsets = np.zeros(n + 1, dtype=np.uint64)
        if n:
            got = self._lib.kb_mvcc_export_fill(
                self._store, start, len(start), end, len(end), snapshot_ts,
                magic, len(magic), tombstone, len(tombstone),
                key_width, n,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                revs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                tomb.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                arena.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            )
            if got == 2**64 - 1:
                raise StorageError("export overflow (key wider than key_width?)")
            if got < n:  # rows vanished between the two passes: trim
                keys, lens, revs, tomb = keys[:got], lens[:got], revs[:got], tomb[:got]
                offsets = offsets[: got + 1]
        return keys, lens, revs, tomb.astype(bool), arena, offsets

    def bulk_gc(self, vkeys, vlens, vrevs, rkeys, rlens, rrevs, rtomb) -> int:
        """Compaction fast path: delete all victim object rows and
        CAS-guarded revision records in ONE engine call (one lock, one WAL
        record) — no per-victim Python (reference hot loop
        scanner.go:465-491, vectorized). Arrays: fixed-width uint8[N, W]
        user keys + int32 lens + uint64 revs; rtomb uint8[M] marks records
        whose expected value carries the deletion flag. Returns the number
        of revision records deleted."""
        import numpy as np

        from .. import coder

        vkeys = np.ascontiguousarray(vkeys, dtype=np.uint8)
        rkeys = np.ascontiguousarray(rkeys, dtype=np.uint8)
        vlens = np.ascontiguousarray(vlens, dtype=np.int32)
        rlens = np.ascontiguousarray(rlens, dtype=np.int32)
        vrevs = np.ascontiguousarray(vrevs, dtype=np.uint64)
        rrevs = np.ascontiguousarray(rrevs, dtype=np.uint64)
        rtomb = np.ascontiguousarray(rtomb, dtype=np.uint8)
        width = vkeys.shape[1] if len(vkeys) else (rkeys.shape[1] if len(rkeys) else 1)
        u8 = ctypes.POINTER(ctypes.c_uint8)
        i32 = ctypes.POINTER(ctypes.c_int32)
        u64 = ctypes.POINTER(ctypes.c_uint64)
        got = self._lib.kb_bulk_gc(
            self._store,
            vkeys.ctypes.data_as(u8), vlens.ctypes.data_as(i32),
            vrevs.ctypes.data_as(u64), len(vlens),
            rkeys.ctypes.data_as(u8), rlens.ctypes.data_as(i32),
            rrevs.ctypes.data_as(u64), rtomb.ctypes.data_as(u8), len(rlens),
            width, coder.MAGIC, len(coder.MAGIC),
        )
        if got == 2**64 - 1:
            raise StorageError("WAL append failed; bulk GC aborted")
        return int(got)

    def mvcc_list_page(self, start: bytes, end: bytes, snapshot_ts: int,
                       read_rev: int, max_rows: int = 4096,
                       val_cap: int = 4 << 20):
        """One page of MVCC-visible (user_key, value, revision) rows — the
        whole visibility rule runs in C (kb_mvcc_list_page). Returns
        (rows, more, next_start)."""
        import numpy as np

        from .. import coder
        from ..backend.common import TOMBSTONE

        u8 = ctypes.POINTER(ctypes.c_uint8)
        u64 = ctypes.POINTER(ctypes.c_uint64)
        key_cap = 1 << 18
        next_cap = 4096
        while True:
            if key_cap > (1 << 30) or val_cap > (1 << 30):
                raise StorageError("mvcc list row exceeds 1GB arena cap")
            karena = np.empty(key_cap, dtype=np.uint8)
            varena = np.empty(val_cap, dtype=np.uint8)
            koffs = np.empty(max_rows + 1, dtype=np.uint64)
            voffs = np.empty(max_rows + 1, dtype=np.uint64)
            revs = np.empty(max_rows, dtype=np.uint64)
            nxt = np.empty(next_cap, dtype=np.uint8)
            nxt_len = ctypes.c_size_t()
            more = ctypes.c_int()
            n = int(self._lib.kb_mvcc_list_page(
                self._store, start, len(start), end, len(end),
                snapshot_ts, read_rev,
                coder.MAGIC, len(coder.MAGIC), TOMBSTONE, len(TOMBSTONE),
                max_rows,
                karena.ctypes.data_as(u8), key_cap, koffs.ctypes.data_as(u64),
                varena.ctypes.data_as(u8), val_cap, voffs.ctypes.data_as(u64),
                revs.ctypes.data_as(u64),
                nxt.ctypes.data_as(u8), next_cap, ctypes.byref(nxt_len),
                ctypes.byref(more),
            ))
            if more.value == 2:
                next_cap = int(nxt_len.value) + 64
                continue
            if n == 0 and more.value:
                # a single row larger than an arena; C can't say which, so
                # grow both (bounded above)
                val_cap *= 4
                key_cap *= 4
                continue
            break
        ko = koffs[: n + 1].astype(np.int64)
        vo = voffs[: n + 1].astype(np.int64)
        kb = karena[: int(ko[-1]) if n else 0].tobytes()
        vb = varena[: int(vo[-1]) if n else 0].tobytes()
        rows = [
            (kb[ko[i]:ko[i + 1]], vb[vo[i]:vo[i + 1]], int(revs[i]))
            for i in range(n)
        ]
        return rows, bool(more.value), bytes(nxt[: nxt_len.value])

    def mvcc_list_wire(self, start: bytes, end: bytes, snapshot_ts: int,
                       read_rev: int, max_rows: int = 65536,
                       byte_cap: int = 32 << 20):
        """One MVCC list page as ready RangeResponse.kvs protobuf bytes —
        the entire list hot path (visibility + wire encoding) in one C call.
        Returns (blob, rows, more, next_start)."""
        from .. import coder
        from ..backend.common import TOMBSTONE

        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_size_t()
        nxt_len = ctypes.c_size_t()
        more = ctypes.c_int()
        next_cap = 4096
        while True:
            nxt = (ctypes.c_uint8 * next_cap)()
            rows = int(self._lib.kb_mvcc_list_wire(
                self._store, start, len(start), end, len(end),
                snapshot_ts, read_rev,
                coder.MAGIC, len(coder.MAGIC), TOMBSTONE, len(TOMBSTONE),
                max_rows, byte_cap,
                ctypes.byref(out), ctypes.byref(out_len),
                nxt, next_cap, ctypes.byref(nxt_len), ctypes.byref(more),
            ))
            blob = ctypes.string_at(out, out_len.value)
            self._lib.kb_free(out)
            if more.value == 2:
                next_cap = int(nxt_len.value) + 64
                continue
            return blob, rows, bool(more.value), bytes(nxt[: nxt_len.value])

    def make_scanner(self, **kwargs):
        return NativeScanner(self, **kwargs)

    def close(self) -> None:
        if self._store:
            self._lib.kb_close(self._store)
            self._store = None


def list_wire_pages(store, snapshot: int, start: bytes, end: bytes,
                    read_revision: int, limit: int = 0,
                    page_rows: int = 4096) -> tuple[bytes, int, bool]:
    """The visible range of a store that has ``mvcc_list_wire``, page by
    page, as ``(kvs_blob, n_rows, more)``: scan and wire encoding in C,
    the engine's iteration stage (``host_scan``) and nothing else."""
    lo, hi = coder.internal_range(start, end)
    blobs: list[bytes] = []
    total = 0
    cursor = lo
    with TRACER.stage("host_scan"):
        while True:
            want = min(limit - total, page_rows) if limit else page_rows
            blob, n, more, nxt = store.mvcc_list_wire(
                cursor, hi, snapshot, read_revision, want
            )
            blobs.append(blob)
            total += n
            if limit and total >= limit:
                # the C more flag is exact: set only when a further visible
                # non-tombstone row exists — etcd's More semantics directly
                return b"".join(blobs), total, more
            if not more or not nxt:
                return b"".join(blobs), total, False
            cursor = nxt


class NativeScanner(Scanner):
    """Generic scanner with the list hot paths served by the engine's C
    MVCC pass (kb_mvcc_list_page) — one FFI call per page instead of a
    per-row Python loop. Compact keeps the generic (partition-parallel)
    implementation. Reference analogue: the scan worker loop
    (scanner.go:389-516) running inside the Badger-role engine."""

    PAGE_ROWS = 4096

    def _list_pages(self, lo: bytes, hi: bytes, snapshot: int, read_rev: int,
                    max_rows: int):
        cursor = lo
        while True:
            rows, more, nxt = self._store.mvcc_list_page(
                cursor, hi, snapshot, read_rev, max_rows
            )
            yield rows
            if not more or not nxt:
                return
            cursor = nxt

    def range_(self, start: bytes, end: bytes, read_revision: int, limit: int = 0):
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)
        kvs: list[KeyValue] = []
        want = min(limit + 1, self.PAGE_ROWS) if limit else self.PAGE_ROWS
        for rows in self._list_pages(lo, hi, snapshot, read_revision, want):
            kvs.extend(KeyValue(k, v, r) for k, v, r in rows)
            if limit and len(kvs) > limit:
                break
        if limit:
            return kvs[:limit], len(kvs) > limit
        return kvs, False

    def count(self, start: bytes, end: bytes, read_revision: int) -> int:
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)
        total = 0
        for rows in self._list_pages(lo, hi, snapshot, read_revision, self.PAGE_ROWS):
            total += len(rows)
        return total

    def list_wire(self, start: bytes, end: bytes, read_revision: int,
                  limit: int = 0) -> tuple[bytes, int, bool]:
        """Visible range as ready RangeResponse.kvs wire bytes (C encoder).
        Returns (kvs_blob, n_rows, more)."""
        return list_wire_pages(self._store, self._snapshot_checked(read_revision),
                               start, end, read_revision, limit, self.PAGE_ROWS)

    def range_stream(self, start: bytes, end: bytes, read_revision: int,
                     batch_size: int = 300):
        lo, hi = coder.internal_range(start, end)
        snapshot = self._snapshot_checked(read_revision)

        def generate():
            batch: list[KeyValue] = []
            for rows in self._list_pages(lo, hi, snapshot, read_revision,
                                         self.PAGE_ROWS):
                for k, v, r in rows:
                    batch.append(KeyValue(k, v, r))
                    if len(batch) >= batch_size:
                        out, b2 = batch[:], []
                        batch = b2
                        yield out
            if batch:
                yield batch

        return generate()


class _PagedNativeIter(Iter):
    """Forward scan over kb_scan_page: bulk pages, zero per-row FFI."""

    PAGE_ROWS = 1024
    KEY_CAP = 1 << 18
    VAL_CAP = 4 << 20

    def __init__(self, lib, store, start, end, snap, limit):
        self._lib = lib
        self._store = store
        self._cursor = start
        self._end = end
        self._snap = snap
        self._limit = limit
        self._served = 0
        self._rows: list[tuple[bytes, bytes]] = []
        self._pos = 0
        self._more = True
        self._val_cap = self.VAL_CAP

    def _fetch(self) -> None:
        import numpy as np

        want = self.PAGE_ROWS
        if self._limit:
            want = min(want, self._limit - self._served)
        while True:
            if getattr(self, "_karena", None) is None or len(self._varena) < self._val_cap:
                self._karena = np.empty(self.KEY_CAP, dtype=np.uint8)
                self._varena = np.empty(self._val_cap, dtype=np.uint8)
                self._koffs = np.empty(self.PAGE_ROWS + 1, dtype=np.uint64)
                self._voffs = np.empty(self.PAGE_ROWS + 1, dtype=np.uint64)
            karena, varena = self._karena, self._varena
            koffs, voffs = self._koffs, self._voffs
            more = ctypes.c_int()
            u8 = ctypes.POINTER(ctypes.c_uint8)
            u64 = ctypes.POINTER(ctypes.c_uint64)
            n = int(self._lib.kb_scan_page(
                self._store, self._cursor, len(self._cursor),
                self._end, len(self._end), self._snap, want,
                karena.ctypes.data_as(u8), self.KEY_CAP,
                koffs.ctypes.data_as(u64),
                varena.ctypes.data_as(u8), self._val_cap,
                voffs.ctypes.data_as(u64),
                ctypes.byref(more),
            ))
            if n == 0 and more.value:
                # single row larger than the value arena: grow and retry
                self._val_cap *= 4
                continue
            break
        ko = koffs[: n + 1].astype(np.int64)
        vo = voffs[: n + 1].astype(np.int64)
        kb = karena[: int(ko[-1]) if n else 0].tobytes()
        vb = varena[: int(vo[-1]) if n else 0].tobytes()
        self._rows = [
            (kb[ko[i]:ko[i + 1]], vb[vo[i]:vo[i + 1]]) for i in range(n)
        ]
        self._pos = 0
        self._more = bool(more.value)
        if n:
            self._cursor = self._rows[-1][0] + b"\x00"

    def next(self) -> tuple[bytes, bytes]:
        if self._limit and self._served >= self._limit:
            raise StopIteration
        if self._pos >= len(self._rows):
            if not self._more:
                raise StopIteration
            self._fetch()
            if not self._rows:
                raise StopIteration
        kv = self._rows[self._pos]
        self._pos += 1
        self._served += 1
        return kv

    def close(self) -> None:
        self._rows = []
        self._more = False


class _NativeIter(Iter):
    def __init__(self, lib, handle):
        self._lib = lib
        self._h = handle

    def next(self) -> tuple[bytes, bytes]:
        if self._h is None:
            raise StopIteration
        k = ctypes.POINTER(ctypes.c_uint8)()
        kl = ctypes.c_size_t()
        v = ctypes.POINTER(ctypes.c_uint8)()
        vl = ctypes.c_size_t()
        rc = self._lib.kb_iter_next(
            self._h, ctypes.byref(k), ctypes.byref(kl), ctypes.byref(v), ctypes.byref(vl)
        )
        if rc != 0:
            self.close()
            raise StopIteration
        return ctypes.string_at(k, kl.value), ctypes.string_at(v, vl.value)

    def close(self) -> None:
        if self._h is not None:
            self._lib.kb_iter_close(self._h)
            self._h = None

    def __del__(self):
        self.close()


class _NativeBatch(BatchWrite):
    def __init__(self, lib, handle):
        self._lib = lib
        self._h = handle
        self._keys: list[bytes] = []

    def put_if_not_exist(self, key, value, ttl_seconds=0):
        self._keys.append(key)
        self._lib.kb_batch_put_if_absent(self._h, key, len(key), value, len(value), ttl_seconds)

    def cas(self, key, new_value, old_value, ttl_seconds=0):
        self._keys.append(key)
        self._lib.kb_batch_cas(
            self._h, key, len(key), new_value, len(new_value),
            old_value, len(old_value), ttl_seconds,
        )

    def put(self, key, value, ttl_seconds=0):
        self._keys.append(key)
        self._lib.kb_batch_put(self._h, key, len(key), value, len(value), ttl_seconds)

    def delete(self, key):
        self._keys.append(key)
        self._lib.kb_batch_del(self._h, key, len(key))

    def del_current(self, key, expected_value):
        self._keys.append(key)
        self._lib.kb_batch_del_current(self._h, key, len(key), expected_value, len(expected_value))

    def commit(self):
        idx = ctypes.c_int64(-1)
        val = ctypes.POINTER(ctypes.c_uint8)()
        vlen = ctypes.c_size_t()
        has_val = ctypes.c_int(0)
        rc = self._lib.kb_batch_commit(
            self._h, ctypes.byref(idx), ctypes.byref(val),
            ctypes.byref(vlen), ctypes.byref(has_val),
        )
        self._h = None  # commit consumes the batch
        if rc == 2:
            raise StorageError("WAL append failed; commit aborted")
        if rc != 0:
            observed = None
            if has_val.value:
                observed = ctypes.string_at(val, vlen.value)
                self._lib.kb_free(val)
            i = int(idx.value)
            key = self._keys[i] if 0 <= i < len(self._keys) else b""
            raise CASFailedError(Conflict(i, key, observed))

    def __del__(self):
        if self._h is not None:
            self._lib.kb_batch_abort(self._h)
            self._h = None


register_engine("native", NativeKv)

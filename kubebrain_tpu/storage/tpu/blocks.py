"""HBM-resident sorted block mirror of the MVCC keyspace.

The TiKV-role engine re-imagined for TPU (SURVEY §2.8): the authoritative
store stays on host (writes are pointwise and CAS-heavy — wrong for TPU);
the *scan-hot columns* (packed user key, revision, tombstone flag) are
mirrored into device HBM as P sorted partitions, padded to a common row
count and sharded over the mesh's ``part`` axis. Values never leave the
host — kernels decide *which* rows are visible; the host materializes bytes
by row index from per-partition byte arenas (no per-row Python objects, so
a million-row mirror rebuild is numpy memcpy, not object churn).

Partition borders are always user-key-aligned (adjustPartitionBorders,
scanner.go:202-225) so no version chain straddles devices and shard-local
kernels need no cross-device carry.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...ops import keys as keyops
from ..native import wire_columns
from .encode import EncodeOverflow, KeyEncoding, build_encoding

TTL_PREFIX = b"/events/"


@dataclass
class Mirror:
    # device (sharded over "part" on axis 0). With a live ``encoding`` the
    # key columns hold ENCODED rows (storage/tpu/encode.py: code chunk +
    # stripped suffix, C' << C chunks) whose lexicographic order equals
    # raw byte order — the kernels compare them unchanged; ``lens_host``
    # then holds encoded-suffix byte lengths.
    keys_dev: jax.Array     # uint32[P, N, C]
    rh_dev: jax.Array       # uint32[P, N]
    rl_dev: jax.Array       # uint32[P, N]
    tomb_dev: jax.Array     # bool[P, N]
    ttl_dev: jax.Array      # bool[P, N]
    n_valid_dev: jax.Array  # int32[P]
    # host copies (row-aligned with device arrays)
    keys_host: np.ndarray   # uint32[P, N, C]
    lens_host: np.ndarray   # int32[P, N]
    revs_host: np.ndarray   # uint64[P, N]
    tomb_host: np.ndarray   # bool[P, N]
    n_valid: np.ndarray     # int32[P]
    # values: one byte arena + offsets per partition
    val_arena: list[np.ndarray]    # uint8[...]
    val_offsets: list[np.ndarray]  # uint64[nv+1]
    snapshot_ts: int
    max_rev: int
    key_width: int = 0              # RAW packed key width (bytes)
    encoding: KeyEncoding | None = None
    # host TTL flag column (row-aligned with ttl_dev): lets the incremental
    # stored-domain merge and the pallas TTL layout run without a device
    # pull, and lets merged TTL flags ride the delta instead of being
    # recomputed from (undecodable) encoded keys
    ttl_host: np.ndarray | None = None  # bool[P, N]
    #: where the wire read finds each partition's columns
    #: (``native.wire_columns``), made once for the mirror's life
    wire_cols: np.ndarray = field(init=False, repr=False)  # uint64[P, 6]

    def __post_init__(self):
        # the wire read reads the key, length, revision and value columns
        # through raw pointers on every Range: whatever built this mirror,
        # they are held in the dtype and layout declared above from here on
        # (no copy where they already are, which is every build and merge
        # path today), and never replaced
        self.keys_host = np.ascontiguousarray(self.keys_host, dtype=np.uint32)
        self.lens_host = np.ascontiguousarray(self.lens_host, dtype=np.int32)
        self.revs_host = np.ascontiguousarray(self.revs_host, dtype=np.uint64)
        self.val_arena = [np.ascontiguousarray(a, dtype=np.uint8)
                          for a in self.val_arena]
        self.val_offsets = [np.ascontiguousarray(o, dtype=np.uint64)
                            for o in self.val_offsets]
        self.wire_cols = wire_columns(
            self.keys_host, self.lens_host, self.revs_host, self.val_arena,
            self.val_offsets)

    @property
    def partitions(self) -> int:
        return self.keys_host.shape[0]

    @property
    def rows(self) -> int:
        return int(self.n_valid.sum())

    @property
    def raw_key_width(self) -> int:
        """RAW packed key width in bytes (the width decoded keys pad to);
        falls back to the stored chunk width for pre-encoding mirrors."""
        return self.key_width or self.keys_host.shape[2] * 4

    def user_key(self, p: int, i: int) -> bytes:
        if self.encoding is not None:
            return self.encoding.decode_one(
                self.keys_host[p, i], int(self.lens_host[p, i]))
        row = keyops.chunks_to_u8(self.keys_host[p, i : i + 1])[0]
        return row[: int(self.lens_host[p, i])].tobytes()

    def value(self, p: int, i: int) -> bytes:
        o = self.val_offsets[p]
        return self.val_arena[p][int(o[i]) : int(o[i + 1])].tobytes()

    def decoded_keys(self, p: int, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(raw_u8, raw_lens) for row indices of one partition — the ONE
        decode funnel in Python (kblint KB116): encoded key bytes only turn
        back into raw bytes here, sized by the caller's visible-row set. The
        wire read alone decodes elsewhere — in C, inside its one call
        (``wire_key`` in native/kbstore.cc, over :attr:`wire_cols` and
        ``KeyEncoding.wire_table``), row by row as it writes the reply; it
        is this function's twin and tests/test_wire_read.py holds the two
        together on random dictionaries and the edge shapes."""
        if self.encoding is not None:
            return self.encoding.decode_rows(
                self.keys_host[p][rows], self.lens_host[p][rows])
        return (keyops.chunks_to_u8(self.keys_host[p][rows]),
                self.lens_host[p][rows])

    def materialize(self, p: int, rows: np.ndarray):
        """Bulk (keys, values, revisions) for sorted row indices of one
        partition — one vectorized unpack instead of per-row slicing.
        Decoding (when the mirror is encoded) happens here, for exactly the
        visible rows — never for the whole mirror."""
        k_u8, k_lens = self.decoded_keys(p, rows)
        keys = [k_u8[i, : int(k_lens[i])].tobytes() for i in range(len(k_u8))]
        o = self.val_offsets[p].astype(np.int64)
        arena = self.val_arena[p]
        values = [arena[o[i] : o[i + 1]].tobytes() for i in map(int, rows)]
        revs = self.revs_host[p][rows]
        return keys, values, revs

    def partition_first_keys(self) -> list[bytes]:
        return [
            self.user_key(p, 0) if self.n_valid[p] > 0 else b""
            for p in range(self.partitions)
        ]

    def flat_arrays(self):
        """Valid rows of every partition, concatenated in order:
        (keys_u8[N, W], lens, revs, tomb, arena, offsets). Always RAW-domain
        keys — an encoded mirror decodes every valid row here, which is why
        this path only backs full-rebuild maintenance, never serving."""
        parts_u8, parts_lens, parts_revs, parts_tomb = [], [], [], []
        arenas, lens_list = [], []
        for p in range(self.partitions):
            nv = int(self.n_valid[p])
            k_u8, k_lens = self.decoded_keys(p, np.arange(nv))
            parts_u8.append(k_u8)
            parts_lens.append(np.asarray(k_lens, np.int32))
            parts_revs.append(self.revs_host[p, :nv])
            parts_tomb.append(self.tomb_host[p, :nv])
            arenas.append(self.val_arena[p][: int(self.val_offsets[p][nv])])
            o = self.val_offsets[p].astype(np.int64)
            lens_list.append(o[1 : nv + 1] - o[:nv])
        # empty-mirror fallback: the RAW key width the caller will merge
        # against, never a hardcoded 4 (a non-default --key-width mirror
        # used to come back as uint8[0, 4] and poison the rebuild concat)
        keys_u8 = (np.concatenate(parts_u8) if parts_u8
                   else np.zeros((0, self.raw_key_width), np.uint8))
        arena = np.concatenate(arenas) if arenas else np.zeros(0, np.uint8)
        row_lens = np.concatenate(lens_list) if lens_list else np.zeros(0, np.int64)
        offsets = np.zeros(len(row_lens) + 1, dtype=np.uint64)
        offsets[1:] = np.cumsum(row_lens).astype(np.uint64)
        return (
            keys_u8,
            np.concatenate(parts_lens) if parts_lens else np.zeros(0, np.int32),
            np.concatenate(parts_revs) if parts_revs else np.zeros(0, np.uint64),
            np.concatenate(parts_tomb) if parts_tomb else np.zeros(0, bool),
            arena,
            offsets,
        )


def rows_to_arrays(rows: list[tuple[bytes, int, bytes]], width: int):
    """Python (user_key, rev, value) rows → the array quintuple."""
    n = len(rows)
    keys_u8 = np.zeros((n, width), dtype=np.uint8)
    lens = np.zeros(n, dtype=np.int32)
    revs = np.zeros(n, dtype=np.uint64)
    from ...backend.common import TOMBSTONE

    tomb = np.zeros(n, dtype=bool)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    chunks_vals = []
    off = 0
    for i, (k, rev, v) in enumerate(rows):
        keys_u8[i, : len(k)] = np.frombuffer(k, dtype=np.uint8)
        lens[i] = len(k)
        revs[i] = rev
        tomb[i] = v == TOMBSTONE
        chunks_vals.append(v)
        off += len(v)
        offsets[i + 1] = off
    arena = np.frombuffer(b"".join(chunks_vals), dtype=np.uint8).copy() if rows else np.zeros(0, np.uint8)
    return keys_u8, lens, revs, tomb, arena, offsets


def rows_wire_source(rows: list[tuple[bytes, bytes, int]]) -> tuple:
    """Python ``(key, value, revision)`` rows as the six arrays
    ``native.wire_gather`` reads — for the rows that exist as objects (a
    host-path page of an inner engine with no wire scan of its own), so
    they reach the wire through the same encoder as the mirror's."""
    n = len(rows)
    keys_u8 = np.zeros((n, max((len(r[0]) for r in rows), default=0) or 1),
                       dtype=np.uint8)
    lens = np.zeros(n, dtype=np.int32)
    revs = np.zeros(n, dtype=np.uint64)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    off = 0
    for i, (k, v, rev) in enumerate(rows):
        keys_u8[i, : len(k)] = np.frombuffer(k, dtype=np.uint8)
        lens[i] = len(k)
        revs[i] = rev
        off += len(v)
        offsets[i + 1] = off
    arena = np.frombuffer(b"".join(r[1] for r in rows), dtype=np.uint8)
    return keys_u8, lens, revs, arena, offsets, np.arange(n, dtype=np.int64)


def _order_void(keys_u8: np.ndarray, revs: np.ndarray) -> np.ndarray:
    """Rows' sort key — key bytes + big-endian revision — as a void scalar
    per row, so numpy orders and searches rows by memcmp."""
    n = len(keys_u8)
    rev_be = revs[:, None].astype(">u8").view(np.uint8).reshape(n, 8)
    return keyops.u8_void(np.concatenate([keys_u8, rev_be], axis=1))


def _take_rows(block: tuple, perm: np.ndarray) -> tuple:
    """Rows ``perm`` of a row-array tuple, values carried along."""
    *cols, arena, offsets = block
    return (*(c[perm] for c in cols),
            *keyops.gather_arena(arena, offsets, perm))


def sort_arrays(block: tuple) -> tuple:
    """Sort one row-array tuple (commit order, as the delta records it) by
    (key, revision): the stable argsort the merges below never need."""
    return _take_rows(
        block, np.argsort(_order_void(block[0], block[2]), kind="stable"))


def _merge_sorted_blocks(blocks: list[tuple]) -> tuple:
    """k-way merge of row-array tuples, each sorted by (key, revision).

    Each block is ``(keys_u8[n, W], *columns, arena, offsets)`` — any
    number of row-aligned 1-D columns between the key matrix and the
    value arena. Sort key = key bytes + big-endian revision (the column
    right after the lens), compared as a void scalar (memcmp). Sorted
    inputs are never re-sorted: block after block is PLACED into the
    merged order by one binary search per row of the incoming block
    (equal rows keep the earlier block's first, as a stable sort over the
    concatenation would), so merging a small delta into a large partition
    costs the delta's searches plus memcpy-class takes, and the values
    move as the few runs :func:`keyops.gather_arena` finds in the merged
    order. Shared by the raw-domain :func:`merge_sorted_arrays` and the
    stored-domain :func:`merge_sorted_stored` so the two merge paths
    cannot diverge."""
    ncols = len(blocks[0]) - 2  # keys + columns, before the arena
    cols = [np.concatenate([b[c] for b in blocks]) for c in range(ncols)]
    void = _order_void(cols[0], cols[2])  # (keys, lens, revs, ...) everywhere
    ends = np.cumsum([len(b[0]) for b in blocks]).tolist()
    perm = np.arange(ends[0])
    for lo, hi in zip(ends[:-1], ends[1:]):
        # until a second block has rows, perm is the identity over the first
        merged = void[:lo] if lo == ends[0] else void[perm]
        at = np.searchsorted(merged, void[lo:hi], side="right")
        perm = np.insert(perm, at, np.arange(lo, hi))
    # one arena (each block's offsets rebased), then the rows by perm
    arena = np.concatenate([b[-2] for b in blocks])
    bases = np.cumsum([0] + [len(b[-2]) for b in blocks[:-1]]).astype(np.int64)
    offsets = np.concatenate(
        [b[-1].astype(np.int64)[:-1] + base
         for b, base in zip(blocks, bases)]
        + [np.array([len(arena)], dtype=np.int64)]
    ).astype(np.uint64)
    return _take_rows((*cols, arena, offsets), perm)


def merge_sorted_arrays(a, b):
    """Merge two RAW row-array sextuples ``(keys, lens, revs, tomb,
    arena, offsets)`` into one, sorted by (key, revision)."""
    return _merge_sorted_blocks([a, b])


def padded_capacity(count: int) -> int:
    """Row capacity for a partition holding ``count`` rows: the next power
    of two past 1.25x headroom. Headroom lets incremental delta merges land
    in place without reshaping every shard; the power-of-two bucket keeps
    kernel shapes stable across rebuilds (bounded recompiles)."""
    want = max(256, int(count * 1.25) + 1)
    cap = 256
    while cap < want:
        cap *= 2
    return cap


def compute_ttl_flags(keys_u8: np.ndarray, lens: np.ndarray) -> np.ndarray:
    ttl_pref = np.frombuffer(TTL_PREFIX, dtype=np.uint8)
    if len(keys_u8) == 0:
        return np.zeros(0, dtype=bool)
    pref = keys_u8[:, : len(ttl_pref)]
    return (pref == ttl_pref).all(axis=1) & (lens >= len(ttl_pref))


def build_mirror_from_arrays(
    keys_u8: np.ndarray,
    lens: np.ndarray,
    revs: np.ndarray,
    tomb: np.ndarray,
    arena: np.ndarray,
    offsets: np.ndarray,
    mesh,
    key_width: int,
    snapshot_ts: int,
    n_parts: int | None = None,
    encode: bool = False,
) -> Mirror:
    """Sorted RAW row arrays → partitioned, padded, device-resident Mirror.

    ``n_parts`` decouples the partition count from the mesh size
    (--scan-partitions): P must be a multiple of the mesh's ``part`` axis so
    ``PartitionSpec("part")`` places P//N contiguous partitions per device.
    Default: one partition per mesh device.

    ``encode=True`` builds an order-preserving prefix dictionary from the
    snapshot keys (storage/tpu/encode.py) and stores ENCODED rows — the
    device key column shrinks from ``key_width`` to ``encoding.width``
    bytes per row while every kernel compare stays byte-order-exact.
    Partition borders, TTL flags, and the user-key-aligned split are
    computed from the RAW keys (encoded order equals raw order, so the
    split is identical either way)."""
    if n_parts is None:
        n_parts = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    n = len(keys_u8)
    if keys_u8.shape[1] != key_width:
        padded = np.zeros((n, key_width), dtype=np.uint8)
        padded[:, : keys_u8.shape[1]] = keys_u8[:, :key_width]
        keys_u8 = padded

    encoding = build_encoding(keys_u8, lens, raw_width=key_width) \
        if (encode and n) else None
    if encoding is not None:
        # cannot overflow: the dictionary was built from these very keys
        store_u8, store_lens = encoding.encode_keys(keys_u8, lens)
        store_width = encoding.width
    else:
        store_u8, store_lens, store_width = keys_u8, lens, key_width

    # user-key-aligned balanced split offsets (vectorized boundary detect)
    if n:
        same_prev = np.zeros(n, dtype=bool)
        same_prev[1:] = (keys_u8[1:] == keys_u8[:-1]).all(axis=1)
    splits = [0]
    target = max(1, (n + n_parts - 1) // n_parts)
    for p in range(1, n_parts):
        pos = min(p * target, n)
        while 0 < pos < n and same_prev[pos]:
            pos += 1
        splits.append(max(pos, splits[-1]))
    splits.append(n)
    counts = [splits[i + 1] - splits[i] for i in range(n_parts)]
    n_max = padded_capacity(max(counts) if counts else 0)

    c = store_width // 4
    keys_h = np.zeros((n_parts, n_max, c), dtype=np.uint32)
    lens_h = np.zeros((n_parts, n_max), dtype=np.int32)
    revs_h = np.zeros((n_parts, n_max), dtype=np.uint64)
    tomb_h = np.zeros((n_parts, n_max), dtype=bool)
    ttl_h = np.zeros((n_parts, n_max), dtype=bool)
    arenas, offs = [], []
    ttl_pref = np.frombuffer(TTL_PREFIX, dtype=np.uint8)

    off64 = offsets.astype(np.int64)
    for p in range(n_parts):
        lo, hi = splits[p], splits[p + 1]
        nv = hi - lo
        if nv:
            keys_h[p, :nv] = keyops.bytes_to_chunks(store_u8[lo:hi])
            lens_h[p, :nv] = store_lens[lo:hi]
            revs_h[p, :nv] = revs[lo:hi]
            tomb_h[p, :nv] = tomb[lo:hi]
            pref = keys_u8[lo:hi, : len(ttl_pref)]  # TTL flag: RAW prefix
            ttl_h[p, :nv] = (pref == ttl_pref).all(axis=1) & (lens[lo:hi] >= len(ttl_pref))
        arenas.append(arena[off64[lo] : off64[hi]].copy())
        offs.append((off64[lo : hi + 1] - off64[lo]).astype(np.uint64))

    rh, rl = keyops.split_revs(revs_h.reshape(-1))
    rh = rh.reshape(n_parts, n_max)
    rl = rl.reshape(n_parts, n_max)
    n_valid = np.array(counts, dtype=np.int32)

    def put(arr):
        if mesh is None:
            return jax.device_put(arr)
        spec = PartitionSpec("part", *(None,) * (arr.ndim - 1))
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return Mirror(
        keys_dev=put(keys_h), rh_dev=put(rh), rl_dev=put(rl),
        tomb_dev=put(tomb_h), ttl_dev=put(ttl_h), n_valid_dev=put(n_valid),
        keys_host=keys_h, lens_host=lens_h, revs_host=revs_h, tomb_host=tomb_h,
        n_valid=n_valid, val_arena=arenas, val_offsets=offs,
        snapshot_ts=snapshot_ts,
        max_rev=int(revs.max()) if n else 0,
        key_width=key_width, encoding=encoding, ttl_host=ttl_h,
    )


def build_mirror(
    rows: list[tuple[bytes, int, bytes]],
    mesh,
    key_width: int,
    snapshot_ts: int,
    n_parts: int | None = None,
    encode: bool = False,
) -> Mirror:
    """Python-row convenience path (tests / generic engines)."""
    return build_mirror_from_arrays(
        *rows_to_arrays(rows, key_width), mesh, key_width, snapshot_ts,
        n_parts=n_parts, encode=encode,
    )


def _assemble_sharded(mesh, host_arr: np.ndarray, old_dev, dirty: set[int]):
    """Rebuild a [P, ...]-sharded device array, re-uploading ONLY the device
    shards holding dirty partitions when the layout places P//N contiguous
    partitions per device (any single-axis mesh with P a multiple of the
    device count — one-per-device is the k=1 case); clean shards reuse the
    existing device buffers. Falls back to a full device_put for
    replicated / multi-axis layouts."""
    if mesh is None:
        return jax.device_put(host_arr)
    spec = PartitionSpec("part", *(None,) * (host_arr.ndim - 1))
    sharding = NamedSharding(mesh, spec)
    P = host_arr.shape[0]
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dev = axis_sizes.get("part", 0)
    blocked = (
        old_dev is not None
        and len(mesh.axis_names) == 1
        and n_dev > 0
        and P % n_dev == 0
        and tuple(old_dev.shape) == tuple(host_arr.shape)
    )
    if not blocked:
        return jax.device_put(host_arr, sharding)
    k = P // n_dev  # contiguous partitions per device shard
    by_dev = {s.device: s.data for s in old_dev.addressable_shards}
    shards = []
    for i, d in enumerate(mesh.devices.flat):
        lo = i * k
        if d not in by_dev or any(p in dirty for p in range(lo, lo + k)):
            shards.append(jax.device_put(host_arr[lo : lo + k], d))
        else:
            shards.append(by_dev[d])
    return jax.make_array_from_single_device_arrays(host_arr.shape, sharding, shards)


def merge_sorted_stored(blocks: list[tuple]) -> tuple:
    """Merge k sorted STORED-domain row blocks into one.

    A stored block is a septuple ``(keys_u8[n, W], lens, revs, tomb, ttl,
    arena, offsets)`` whose key bytes live in the mirror's compare domain —
    raw packed bytes for a raw mirror, dictionary-encoded rows for an
    encoded one. Encoded lexicographic order equals raw byte order
    (storage/tpu/encode.py order preservation) and the encoding is
    injective, so the memcmp order of ``key || rev_be`` merges encoded
    blocks as exactly as raw ones — the k-way merge of the write-path
    delta blocks (docs/writes.md). Shares :func:`_merge_sorted_blocks`
    with the raw-domain :func:`merge_sorted_arrays` so the two merge
    paths cannot diverge."""
    if len(blocks) == 1:
        return blocks[0]
    return _merge_sorted_blocks(blocks)


def merge_partitions_stored(
    mirror: Mirror,
    delta: tuple,  # sorted stored-domain septuple (see merge_sorted_stored)
    mesh,
    snapshot_ts: int,
) -> Mirror | None:
    """Incremental merge of a STORED-domain delta into the mirror — the
    write-path successor to :func:`merge_partitions_incremental`.

    The delta rows arrive already encoded against the published dictionary
    (sealed at write time, PR 9's incremental re-encode moved off the merge
    path), so a dirty partition merges by pure byte interleave: no
    partition decode, no raw-domain merge, no re-encode, no re-sort — the
    delta's rows are placed by binary search and the partition's columns
    and value arena move around them in runs, so per-merge host work is
    O(delta x log partition + dirty-partition memcpy), values included.
    TTL flags ride the delta column and the mirror's host TTL column, so
    the merge never touches the device except for the dirty-shard-only
    republish (:func:`_assemble_sharded`, PR 7 machinery).

    A partition outgrowing its padded capacity does NOT force the full
    decode → re-dictionary → re-partition host rebuild: the stored-domain
    arrays grow to the next padded capacity by pure memcpy (every shard
    republishes — the device pays, the host never re-sorts or re-encodes),
    which is what keeps a sustained write storm on the incremental path
    between compactions (compaction re-partitions and re-fits capacity).
    Returns None only when the mirror predates the host TTL column or the
    delta's stored width no longer matches (a re-dictionaried mirror) —
    the true full-rebuild cases."""
    d_keys, d_lens, d_revs, d_tomb, d_ttl, d_arena, d_offsets = delta
    dn = len(d_keys)
    if dn == 0:
        return mirror
    if mirror.ttl_host is None:
        return None  # pre-ttl_host mirror: full rebuild re-derives everything
    P = mirror.partitions
    cap = mirror.keys_host.shape[1]
    W = mirror.keys_host.shape[2] * 4
    if d_keys.shape[1] != W:
        return None  # stored-width drift (re-dictionaried mirror): rebuild

    # route delta rows to non-empty partitions by the partitions' FIRST
    # STORED rows — stored order == raw order, so the stored compare routes
    # identically to the raw-domain routing of merge_partitions_incremental
    nonempty = [p for p in range(P) if mirror.n_valid[p] > 0]
    if not nonempty:
        return None  # nothing to merge into; full rebuild re-partitions
    firsts = np.stack([mirror.keys_host[p, 0] for p in nonempty])
    firsts_u8 = keyops.chunks_to_u8(firsts)
    firsts_void = keyops.u8_void(np.ascontiguousarray(firsts_u8))
    d_void = keyops.u8_void(np.ascontiguousarray(d_keys))
    # last non-empty partition whose first key <= row key (rows below the
    # first partition's floor route to it)
    pos = np.maximum(np.searchsorted(firsts_void, d_void, side="right") - 1, 0)
    row_part = np.asarray(nonempty, dtype=np.int64)[pos]
    # row_part is non-decreasing (sorted delta routed through sorted
    # firsts), so each dirty partition owns ONE contiguous delta slice —
    # locate every slice with two binary searches instead of a full-delta
    # boolean scan per partition (the build phase: off _mlock, but a merge
    # is not published until it ends)
    dirty = np.unique(row_part).tolist()
    part_lo = np.searchsorted(row_part, np.asarray(dirty), side="left")
    part_hi = np.searchsorted(row_part, np.asarray(dirty), side="right")

    # capacity check up front: if any dirty partition outgrows the padded
    # cap, grow EVERY partition's stored arrays to the next padded
    # capacity (memcpy, no decode/re-encode/re-sort) and republish all
    # shards — the write-storm path that must never fall back to the full
    # host rebuild between compactions
    need = int(max(
        int(mirror.n_valid[p]) + int(hi - lo)
        for p, lo, hi in zip(dirty, part_lo, part_hi)))
    grew = need > cap
    if grew:
        new_cap = padded_capacity(need)
        keys_h = np.zeros((P, new_cap, mirror.keys_host.shape[2]),
                          dtype=mirror.keys_host.dtype)
        lens_h = np.zeros((P, new_cap), dtype=mirror.lens_host.dtype)
        revs_h = np.zeros((P, new_cap), dtype=mirror.revs_host.dtype)
        tomb_h = np.zeros((P, new_cap), dtype=mirror.tomb_host.dtype)
        ttl_h = np.zeros((P, new_cap), dtype=mirror.ttl_host.dtype)
        for p in range(P):
            nv = int(mirror.n_valid[p])
            keys_h[p, :nv] = mirror.keys_host[p, :nv]
            lens_h[p, :nv] = mirror.lens_host[p, :nv]
            revs_h[p, :nv] = mirror.revs_host[p, :nv]
            tomb_h[p, :nv] = mirror.tomb_host[p, :nv]
            ttl_h[p, :nv] = mirror.ttl_host[p, :nv]
        cap = new_cap
    else:
        # copy-on-write: readers hold the old Mirror object
        keys_h = mirror.keys_host.copy()
        lens_h = mirror.lens_host.copy()
        revs_h = mirror.revs_host.copy()
        tomb_h = mirror.tomb_host.copy()
        ttl_h = mirror.ttl_host.copy()
    n_valid = mirror.n_valid.copy()
    arenas = list(mirror.val_arena)
    offs = list(mirror.val_offsets)

    d_off64 = d_offsets.astype(np.int64)
    for p, lo, hi in zip(dirty, part_lo, part_hi):
        lo, hi = int(lo), int(hi)
        nv = int(n_valid[p])
        mn = nv + (hi - lo)
        part = (
            keyops.chunks_to_u8(mirror.keys_host[p, :nv]),
            mirror.lens_host[p, :nv], mirror.revs_host[p, :nv],
            mirror.tomb_host[p, :nv], mirror.ttl_host[p, :nv],
            mirror.val_arena[p][: int(mirror.val_offsets[p][nv])],
            mirror.val_offsets[p][: nv + 1],
        )
        dslice = (
            d_keys[lo:hi], d_lens[lo:hi], d_revs[lo:hi], d_tomb[lo:hi],
            d_ttl[lo:hi],
            d_arena[d_off64[lo] : d_off64[hi]],
            (d_off64[lo : hi + 1] - d_off64[lo]).astype(np.uint64),
        )
        mk, ml, mr, mt, mttl, ma, mo = merge_sorted_stored([part, dslice])
        keys_h[p, :mn] = keyops.bytes_to_chunks(np.ascontiguousarray(mk))
        lens_h[p, :mn] = ml
        revs_h[p, :mn] = mr
        tomb_h[p, :mn] = mt
        ttl_h[p, :mn] = mttl
        ttl_h[p, mn:] = False
        n_valid[p] = mn
        arenas[p] = ma
        offs[p] = mo

    rh_all, rl_all = keyops.split_revs(revs_h.reshape(-1))
    rh_all = rh_all.reshape(P, cap)
    rl_all = rl_all.reshape(P, cap)

    ds = set(dirty)
    return Mirror(
        keys_dev=_assemble_sharded(mesh, keys_h, mirror.keys_dev, ds),
        rh_dev=_assemble_sharded(mesh, rh_all, mirror.rh_dev, ds),
        rl_dev=_assemble_sharded(mesh, rl_all, mirror.rl_dev, ds),
        tomb_dev=_assemble_sharded(mesh, tomb_h, mirror.tomb_dev, ds),
        ttl_dev=_assemble_sharded(mesh, ttl_h, mirror.ttl_dev, ds),
        n_valid_dev=(
            jax.device_put(n_valid) if mesh is None
            else jax.device_put(
                n_valid, NamedSharding(mesh, PartitionSpec("part")))
        ),
        keys_host=keys_h, lens_host=lens_h, revs_host=revs_h, tomb_host=tomb_h,
        n_valid=n_valid, val_arena=arenas, val_offsets=offs,
        snapshot_ts=snapshot_ts,
        max_rev=max(mirror.max_rev, int(d_revs.max())),
        key_width=mirror.key_width, encoding=mirror.encoding, ttl_host=ttl_h,
    )


def compact_partitions_stored(
    mirror: Mirror,
    keep_idx: dict[int, np.ndarray],  # dirty partition -> sorted survivor rows
    mesh,
    snapshot_ts: int,
) -> Mirror | None:
    """Shrink the mirror to the compaction survivors WITHOUT leaving the
    stored domain — the mirror half of the device-side compaction pipeline
    (docs/compaction.md).

    ``keep_idx`` names only the DIRTY partitions (those with >= 1 victim);
    each maps to the ascending row indices that survive. Survivors are
    gathered as stored rows — ``(code, suffix)`` key bytes, host TTL
    column, value-arena gather — so the steady compaction path performs no
    key decode, no re-encode, and no re-dictionary: partition borders and
    the published :class:`~.encode.KeyEncoding` are carried over unchanged,
    and only dirty shards republish (:func:`_assemble_sharded`). A pending
    write delta then lands through the ordinary
    :func:`merge_partitions_stored` against the compacted mirror.

    Returns None only for a pre-``ttl_host`` mirror (nothing to gather the
    TTL flags from) — the caller falls back to the full host rebuild.
    Shrinking can never overflow a partition's padded capacity."""
    if not keep_idx:
        return mirror
    if mirror.ttl_host is None:
        return None
    P = mirror.partitions
    cap = mirror.keys_host.shape[1]

    # copy-on-write: readers hold the old Mirror object
    keys_h = mirror.keys_host.copy()
    lens_h = mirror.lens_host.copy()
    revs_h = mirror.revs_host.copy()
    tomb_h = mirror.tomb_host.copy()
    ttl_h = mirror.ttl_host.copy()
    n_valid = mirror.n_valid.copy()
    arenas = list(mirror.val_arena)
    offs = list(mirror.val_offsets)

    for p, keep in keep_idx.items():
        nv = int(n_valid[p])
        keep = np.asarray(keep, dtype=np.int64)
        mn = len(keep)
        keys_h[p, :mn] = mirror.keys_host[p][keep]
        lens_h[p, :mn] = mirror.lens_host[p][keep]
        revs_h[p, :mn] = mirror.revs_host[p][keep]
        tomb_h[p, :mn] = mirror.tomb_host[p][keep]
        ttl_h[p, :mn] = mirror.ttl_host[p][keep]
        # zero the vacated tail: stale rows beyond n_valid are kernel-masked
        # but must not survive as garbage into later capacity-grow memcpys
        keys_h[p, mn:nv] = 0
        lens_h[p, mn:nv] = 0
        revs_h[p, mn:nv] = 0
        tomb_h[p, mn:nv] = False
        ttl_h[p, mn:nv] = False
        n_valid[p] = mn
        arenas[p], offs[p] = keyops.gather_arena(
            mirror.val_arena[p], mirror.val_offsets[p][: nv + 1], keep)

    rh_all, rl_all = keyops.split_revs(revs_h.reshape(-1))
    rh_all = rh_all.reshape(P, cap)
    rl_all = rl_all.reshape(P, cap)

    ds = set(keep_idx)
    return Mirror(
        keys_dev=_assemble_sharded(mesh, keys_h, mirror.keys_dev, ds),
        rh_dev=_assemble_sharded(mesh, rh_all, mirror.rh_dev, ds),
        rl_dev=_assemble_sharded(mesh, rl_all, mirror.rl_dev, ds),
        tomb_dev=_assemble_sharded(mesh, tomb_h, mirror.tomb_dev, ds),
        ttl_dev=_assemble_sharded(mesh, ttl_h, mirror.ttl_dev, ds),
        n_valid_dev=(
            jax.device_put(n_valid) if mesh is None
            else jax.device_put(
                n_valid, NamedSharding(mesh, PartitionSpec("part")))
        ),
        keys_host=keys_h, lens_host=lens_h, revs_host=revs_h, tomb_host=tomb_h,
        n_valid=n_valid, val_arena=arenas, val_offsets=offs,
        snapshot_ts=snapshot_ts,
        max_rev=mirror.max_rev,
        key_width=mirror.key_width, encoding=mirror.encoding, ttl_host=ttl_h,
    )


def merge_partitions_incremental(
    mirror: Mirror,
    delta,  # sorted row-array sextuple (keys_u8, lens, revs, tomb, arena, offsets)
    mesh,
    key_width: int,
    snapshot_ts: int,
) -> Mirror | None:
    """Merge a (small, sorted) delta into the mirror touching ONLY the
    partitions the delta lands in: per-partition two-way merge on host,
    dirty-shard-only re-upload on device. Returns None when any partition
    overflows its padded capacity — the caller falls back to the full
    rebuild (which re-balances and re-pads).

    This is the incremental answer to VERDICT r1 weak #4 (all-or-nothing
    mirror maintenance): merge cost scales with delta size + dirty-partition
    size, not dataset size."""
    d_keys, d_lens, d_revs, d_tomb, d_arena, d_offsets = delta
    dn = len(d_keys)
    if dn == 0:
        return mirror
    P = mirror.partitions
    cap = mirror.keys_host.shape[1]

    # route delta rows to partitions by the partition lower bounds. Only
    # NON-EMPTY partitions are routing targets — routing into an empty
    # partition sandwiched between populated ones would break the global
    # cross-partition sort order that range_stream/compact rely on.
    firsts = mirror.partition_first_keys()
    nonempty = [p for p in range(P) if mirror.n_valid[p] > 0]
    if not nonempty:
        return None  # nothing to merge into; full rebuild re-partitions
    ne_bounds = [firsts[p] for p in nonempty]
    import bisect as _bisect

    d_key_bytes = [d_keys[i, : d_lens[i]].tobytes() for i in range(dn)]
    row_part = np.empty(dn, dtype=np.int64)
    for i, kb in enumerate(d_key_bytes):
        # last non-empty partition whose first key <= kb (earlier keys go to
        # the first non-empty partition — everything left of it is empty)
        row_part[i] = nonempty[max(0, _bisect.bisect_right(ne_bounds, kb) - 1)]
    dirty = sorted(set(int(p) for p in row_part))

    # copy-on-write: readers hold the old Mirror object; stacked-array copies
    # are memcpy (fast), the expensive work below is per-dirty-partition only
    keys_h = mirror.keys_host.copy()
    lens_h = mirror.lens_host.copy()
    revs_h = mirror.revs_host.copy()
    tomb_h = mirror.tomb_host.copy()
    n_valid = mirror.n_valid.copy()
    arenas = list(mirror.val_arena)
    offs = list(mirror.val_offsets)

    ttl_dirty: dict[int, np.ndarray] = {}
    d_off64 = d_offsets.astype(np.int64)
    for p in dirty:
        rows_p = np.nonzero(row_part == p)[0]
        lo, hi = rows_p[0], rows_p[-1] + 1  # contiguous: delta is sorted
        nv = int(n_valid[p])
        # the merge runs in the RAW domain: decode the dirty partition (it
        # is the only one paying the cost), merge with the raw delta, then
        # re-encode against the PUBLISHED dictionary — a delta key that no
        # longer fits (wrong bucket strip / suffix past the width budget)
        # falls back to the full re-dictionary rebuild
        part_u8, part_lens = mirror.decoded_keys(p, np.arange(nv))
        o = mirror.val_offsets[p].astype(np.int64)
        part = (
            part_u8, np.asarray(part_lens, np.int32), mirror.revs_host[p, :nv],
            mirror.tomb_host[p, :nv],
            mirror.val_arena[p][: o[nv]], mirror.val_offsets[p][: nv + 1],
        )
        dslice = (
            d_keys[lo:hi], d_lens[lo:hi], d_revs[lo:hi], d_tomb[lo:hi],
            d_arena[d_off64[lo] : d_off64[hi]],
            (d_off64[lo : hi + 1] - d_off64[lo]).astype(np.uint64),
        )
        mk, ml, mr, mt, ma, mo = merge_sorted_arrays(part, dslice)
        mn = len(mk)
        if mn > cap:
            return None  # overflow: rebalance via full rebuild
        if mirror.encoding is not None:
            try:
                enc_u8, enc_lens = mirror.encoding.encode_keys(mk, ml)
            except EncodeOverflow:
                return None  # suffix-width budget overflow: re-dictionary
            keys_h[p, :mn] = keyops.bytes_to_chunks(enc_u8)
            lens_h[p, :mn] = enc_lens
        else:
            keys_h[p, :mn] = keyops.bytes_to_chunks(
                np.ascontiguousarray(mk[:, :key_width])
            )
            lens_h[p, :mn] = ml
        revs_h[p, :mn] = mr
        tomb_h[p, :mn] = mt
        n_valid[p] = mn
        arenas[p] = ma
        offs[p] = mo
        ttl_row = np.zeros(cap, dtype=bool)
        ttl_row[:mn] = compute_ttl_flags(mk, ml)
        ttl_dirty[p] = ttl_row

    rh_all, rl_all = keyops.split_revs(revs_h.reshape(-1))
    rh_all = rh_all.reshape(P, cap)
    rl_all = rl_all.reshape(P, cap)
    ttl_h = None
    if ttl_dirty:
        ttl_h = (mirror.ttl_host.copy() if mirror.ttl_host is not None
                 else np.array(jax.device_get(mirror.ttl_dev)))
        for p, row in ttl_dirty.items():
            ttl_h[p] = row

    ds = set(dirty)
    return Mirror(
        keys_dev=_assemble_sharded(mesh, keys_h, mirror.keys_dev, ds),
        rh_dev=_assemble_sharded(mesh, rh_all, mirror.rh_dev, ds),
        rl_dev=_assemble_sharded(mesh, rl_all, mirror.rl_dev, ds),
        tomb_dev=_assemble_sharded(mesh, tomb_h, mirror.tomb_dev, ds),
        ttl_dev=_assemble_sharded(mesh, ttl_h, mirror.ttl_dev, ds)
        if ttl_h is not None else mirror.ttl_dev,
        n_valid_dev=(
            jax.device_put(n_valid) if mesh is None
            else jax.device_put(
                n_valid, NamedSharding(mesh, PartitionSpec("part")))
        ),
        keys_host=keys_h, lens_host=lens_h, revs_host=revs_h, tomb_host=tomb_h,
        n_valid=n_valid, val_arena=arenas, val_offsets=offs,
        snapshot_ts=snapshot_ts,
        max_rev=max(mirror.max_rev, int(d_revs.max())),
        key_width=mirror.key_width, encoding=mirror.encoding,
        ttl_host=ttl_h if ttl_h is not None else mirror.ttl_host,
    )

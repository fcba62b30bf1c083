"""The ``tpu`` storage engine: host-authoritative store + HBM scan mirror.

Division of labor (SURVEY §7 build plan, step 4):

- **writes / point reads / CAS**: delegated to a host engine (memkv for
  tests, the C++ native store in production) — pointwise, latency-bound,
  wrong shape for TPU;
- **range scans / counts / compaction decisions**: the device mirror
  (blocks.Mirror) + the kernels in kubebrain_tpu.ops, vmapped over the
  partition axis and sharded across the mesh;
- **freshness**: committed version rows are appended to a host-side delta
  log by the batch decorator; queries overlay the delta (all delta revisions
  exceed every published revision, so overlay-wins resolution is exact);
  the delta is merged into the mirror once it crosses a threshold.
  Uncertain commits poison the mirror (force rebuild from the store) —
  the store is the only source of truth for maybe-applied writes.

This mirrors the reference's TiKV adapter role (pkg/storage/tikv) with the
region map replaced by mesh partitions (SURVEY §2.10: mesh sharding mirrors
storage sharding through GetPartitions).
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ... import coder
from ...backend.common import TOMBSTONE
from ...backend.scanner import CompactHistory, CompactStats, Scanner
from ...ops import keys as keyops
from ...ops.compact import victim_mask
from ...ops.scan import lex_geq, lex_less, visibility_mask, visibility_mask_queries
from ...parallel.mesh import make_mesh
from ...trace import TRACER
from ...util import fieldcheck, lockcheck
from .. import BatchWrite, CASFailedError, KvStorage, Partition, register_engine
from ..errors import UncertainResultError
from ..native import (
    list_wire_pages,
    load_lib,
    wire_gather,
    wire_read,
)
from .blocks import (
    Mirror,
    build_mirror,
    build_mirror_from_arrays,
    compact_partitions_stored,
    compute_ttl_flags,
    merge_partitions_incremental,  # noqa: F401  (raw-domain path, tests/compat)
    merge_partitions_stored,
    merge_sorted_arrays,
    merge_sorted_stored,
    rows_to_arrays,
    rows_wire_source,
    sort_arrays,
)
from .encode import EncodeOverflow


class _DeltaIndex:
    """Commit-order delta rows PLUS a sorted key index, so read overlays
    cost O(log d + matches) instead of a full O(d) Python scan per query
    (VERDICT r1 weak #5). Writers append; per-key revision lists only grow.

    The index ALSO accumulates the rows into sealed, sorted, STORED-domain
    blocks (``seal_rows`` rows each; encoded against the published
    dictionary when the mirror is encoded) so the incremental merge
    (:func:`blocks.merge_partitions_stored`) consumes ready-made sorted
    encoded runs instead of re-sorting and re-encoding the whole delta
    under the engine lock — the write-path half of PR 9's incremental
    re-encode. A key the dictionary cannot express marks the index
    ``overflowed`` (the merge then falls back to the full re-dictionary
    rebuild, which reads the raw rows kept alongside)."""

    __slots__ = ("_rows", "_keys", "_by_key", "_width", "_encoding",
                 "_seal_rows", "_blocks", "_sealed_upto", "_overflow")

    def __init__(self, width: int = keyops.KEY_WIDTH, encoding=None,
                 seal_rows: int = 512):
        self._rows: list[tuple[bytes, int, bytes]] = []
        self._keys: list[bytes] = []  # sorted, unique
        self._by_key: dict[bytes, list[tuple[int, bytes]]] = {}
        self._width = width
        self._encoding = encoding
        self._seal_rows = max(1, seal_rows)
        self._blocks: list[tuple] = []  # sealed stored-domain septuples
        self._sealed_upto = 0
        self._overflow = False

    def extend(self, rows) -> None:
        import bisect

        for ukey, rev, value in rows:
            self._rows.append((ukey, rev, value))
            lst = self._by_key.get(ukey)
            if lst is None:
                self._by_key[ukey] = [(rev, value)]
                bisect.insort(self._keys, ukey)
            else:
                lst.append((rev, value))
        while len(self._rows) - self._sealed_upto >= self._seal_rows:
            hi = self._sealed_upto + self._seal_rows
            self._seal(self._rows[self._sealed_upto:hi])
            self._sealed_upto = hi

    def _seal(self, rows: list[tuple[bytes, int, bytes]]) -> None:
        """Sort one run and move it into the mirror's stored domain. Sealing
        amortizes over writes (one small argsort + encode per ``seal_rows``
        rows) so merge time pays only the k-way interleave."""
        k, l, r, t, arena, off = sort_arrays(
            rows_to_arrays(rows, self._width))
        ttl = compute_ttl_flags(k, l)
        if self._encoding is not None and not self._overflow:
            try:
                k, l = self._encoding.encode_keys(k, l)
            except EncodeOverflow:
                # inexpressible key: the whole delta merges via the full
                # re-dictionary rebuild (raw rows kept in self._rows)
                self._overflow = True
        self._blocks.append((k, np.asarray(l, np.int32), r, t, ttl,
                             arena, off))

    def snapshot_blocks(self) -> tuple[list[tuple], list, bool]:
        """Seal the open tail and return ``(sealed blocks, raw-row prefix,
        overflowed)`` — the merge's input snapshot. Rows appended after
        this call stay in the index (the caller re-indexes the tail after
        the swap)."""
        if self._sealed_upto < len(self._rows):
            self._seal(self._rows[self._sealed_upto:])
            self._sealed_upto = len(self._rows)
        return list(self._blocks), self._rows[: self._sealed_upto], self._overflow

    def tail_rows(self, n: int) -> list[tuple[bytes, int, bytes]]:
        """Rows appended after a ``snapshot_blocks`` that covered ``n``."""
        return self._rows[n:]

    def force_overflow(self) -> None:
        """Mark the index overflowed (chaos hook: forced EncodeOverflow) —
        the next merge takes the full re-dictionary rebuild path, exactly
        as if a sealed key had been inexpressible."""
        self._overflow = True

    def __len__(self) -> int:
        return len(self._rows)

    def rows(self) -> list[tuple[bytes, int, bytes]]:
        return self._rows

    def overlay(
        self, start: bytes, end: bytes, read_rev: int
    ) -> dict[bytes, tuple[int, bytes] | None]:
        """Per user key in [start, end): latest delta version <= read_rev.
        None value => tombstoned. Delta revisions all exceed published
        revisions, so any entry here overrides the device result."""
        lo = bisect.bisect_left(self._keys, start)
        hi = bisect.bisect_left(self._keys, end) if end else len(self._keys)
        out: dict[bytes, tuple[int, bytes] | None] = {}
        for ukey in self._keys[lo:hi]:
            versions = self._by_key[ukey]
            # revisions grow append-only; the common case (read at head)
            # matches the last entry immediately
            for rev, value in reversed(versions):
                if rev <= read_rev:
                    out[ukey] = None if value == TOMBSTONE else (rev, value)
                    break
        return out


@jax.jit
def _vis_batch(keys, rh, rl, tomb, nv, start, end, unb, qhi, qlo):
    """jnp visibility masks for all partitions: [P, N] bool + [P] counts.
    Plain elementwise ops — GSPMD partitions them natively over the mesh."""
    f = lambda k, a, b, t, n: visibility_mask(k, a, b, t, n, start, end, unb, qhi, qlo)
    mask = jax.vmap(f)(keys, rh, rl, tomb, nv)
    return mask, jnp.sum(mask, axis=1, dtype=jnp.int32)


@jax.jit
def _vis_batch_q(keys, rh, rl, tomb, nv, starts, ends, unbs, qhis, qlos):
    """jnp visibility masks for Q distinct queries × all partitions in ONE
    traced program: [Q, P, N] bool + [Q, P] counts. Elementwise over both
    axes, so GSPMD partitions the ``part`` axis natively like _vis_batch."""
    per_part = lambda k, a, b, t, n: visibility_mask_queries(
        k, a, b, t, n, starts, ends, unbs, qhis, qlos)
    mask = jax.vmap(per_part, out_axes=1)(keys, rh, rl, tomb, nv)  # [Q, P, N]
    return mask, jnp.sum(mask, axis=2, dtype=jnp.int32)


def _maybe_shard_map(f, mesh, n_part_args: int = 0, n_rep_args: int = 0,
                     out_part_axis: int = 0, in_specs=None, out_specs=None):
    """shard_map ``f`` along ``part`` when the mesh is multi-device:
    pallas_call has no GSPMD partitioning rule, so without this XLA would
    replicate the whole mirror layout to every device per call. First
    ``n_part_args`` args shard on axis 0; the rest replicate. The output
    shards on ``out_part_axis`` (the query-batched kernels put the query
    axis ahead of ``part``). Explicit ``in_specs``/``out_specs`` override
    the counts for layouts the counts can't express (the index-compaction
    helpers shard the middle axis)."""
    if mesh is None or mesh.devices.size <= 1:
        return f
    from jax.sharding import PartitionSpec as PS

    if in_specs is None:
        in_specs = (PS("part"),) * n_part_args + (PS(),) * n_rep_args
    if out_specs is None:
        out_specs = PS(*(None,) * out_part_axis, "part")
    # check_vma off: pallas_call's out_shape carries no vma annotation
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "mesh"))
def _vis_batch_pallas(keys_t, rh31, rl31, tomb8, nv, start, end, unb, qhi, qlo,
                      n, interpret=False, mesh=None):
    """Pallas visibility masks over the `prepare_mirror`-cached layout,
    shard_map'd along ``part`` on a multi-device ``mesh`` (static)."""
    from ...ops.scan_pallas import visibility_mask_batch_cached

    f = _maybe_shard_map(
        functools.partial(visibility_mask_batch_cached, n=n, interpret=interpret),
        mesh, n_part_args=5, n_rep_args=5,
    )
    mask = f(keys_t, rh31, rl31, tomb8, nv, start, end, unb, qhi, qlo)
    return mask, jnp.sum(mask, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "mesh"))
def _vis_batch_pallas_q(keys_t, rh31, rl31, tomb8, nv, starts, ends, unbs,
                        qhis, qlos, n, interpret=False, mesh=None):
    """Query-batched Pallas masks over the `prepare_mirror`-cached layout,
    shard_map'd along ``part`` on a multi-device ``mesh`` (static):
    [Q, P, n] bool + [Q, P] counts from ONE dispatch."""
    from ...ops.scan_pallas import visibility_mask_batch_cached_q

    f = _maybe_shard_map(
        functools.partial(visibility_mask_batch_cached_q, n=n,
                          interpret=interpret),
        mesh, n_part_args=5, n_rep_args=5, out_part_axis=1,
    )
    mask = f(keys_t, rh31, rl31, tomb8, nv, starts, ends, unbs, qhis, qlos)
    return mask, jnp.sum(mask, axis=2, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("size", "mesh"))
def _part_indices_of_mask(mask, size, mesh=None):
    """Per-partition compacted row indices [P, size] (fill = N) of a
    mask [P, N] — the SHARD-LOCAL index extraction of the Compact's
    victim pull. Each device compacts only its own partitions' rows
    (shard_map along ``part``), so a multi-device mesh never all-gathers
    the [P, N] mask, and the host pull that follows is O(rows picked per
    shard), not O(dataset)."""
    def local(m):
        per_row = lambda row: jnp.nonzero(
            row, size=size, fill_value=row.shape[0])[0]
        return jax.vmap(per_row)(m)

    f = _maybe_shard_map(local, mesh, n_part_args=1)
    return f(mask)


@functools.partial(jax.jit, static_argnames=("size", "mesh"))
def _part_indices_of_mask_sel(mask, sel, size, mesh=None):
    """Per-(query, partition) compacted row indices [Q, P, size] (fill =
    N) of a mask [Q, P, N], restricted to the SELECTED queries — the
    read path's shard-local index extraction (`_vis_rows`; a single read
    is a batch of one). Count queries (and pow2 padding copies) are
    deselected so their rows never cross the wire; the ``part`` axis
    (axis 1) stays sharded end to end, and the host pull is O(visible
    rows per shard), never the mask."""
    from jax.sharding import PartitionSpec as PS

    def local(m, s):
        msel = m & s[:, None, None]
        per_row = lambda row: jnp.nonzero(
            row, size=size, fill_value=row.shape[0])[0]
        return jax.vmap(jax.vmap(per_row))(msel)

    f = _maybe_shard_map(
        local, mesh,
        in_specs=(PS(None, "part", None), PS()),
        out_specs=PS(None, "part", None),
    )
    return f(mask, sel)


@functools.partial(jax.jit, static_argnames=("kernel", "n", "size", "mesh"))
def _vis_rows(cols, query, kernel, n, size, mesh=None):
    """One read's whole device half in ONE program: the packed query in,
    ``[P, 1 + size]`` int32 out — column 0 partition p's visible count,
    then its first ``size`` visible row indices (fill = N), ascending. A
    query-batched ``query`` [Qpad, 2C + 3] gives ``[Qpad, P, 1 + size]``
    with the rows of queries that want none (Counts, pow2 padding) left
    all fill. ``size`` 0 returns the counts alone. The [P, N] mask never
    leaves the program.

    ``cols`` are the five mirror columns the ``kernel`` reads (the
    ``prepare_mirror`` layout for Pallas, whose row count is ``n``); the
    query row is ``TpuScanner._pack_query``'s: start chunks, end chunks,
    flags (1 = unbounded end, 2 = rows wanted), read revision hi, lo."""
    c = (query.shape[-1] - 3) // 2
    start, end = query[..., :c], query[..., c:2 * c]
    flags, qhi, qlo = (query[..., 2 * c + i] for i in range(3))
    unb, wanted = (flags & 1) != 0, (flags & 2) != 0
    interpret = kernel == "pallas_interpret"
    if query.ndim == 1:
        if kernel == "jnp":
            mask, counts = _vis_batch(*cols, start, end, unb, qhi, qlo)
        else:
            mask, counts = _vis_batch_pallas(
                *cols, start, end, unb, qhi, qlo, n=n, interpret=interpret,
                mesh=mesh)
        mask, counts, wanted = mask[None], counts[None], wanted[None]
    elif kernel == "jnp":
        mask, counts = _vis_batch_q(*cols, start, end, unb, qhi, qlo)
    else:
        mask, counts = _vis_batch_pallas_q(
            *cols, start, end, unb, qhi, qlo, n=n, interpret=interpret,
            mesh=mesh)
    block = counts[..., None]
    if size:
        block = jnp.concatenate([block, _part_indices_of_mask_sel(
            mask, wanted, size=size, mesh=mesh)], axis=-1)
    return block[0] if query.ndim == 1 else block


#: (start, end) ranges whose last visible count a scanner remembers to size
#: the next read's index block (`TpuScanner._pull_visible`): the oldest is
#: forgotten first
_BUCKET_MEMO = 4096


def _pow2_bucket(want: int, n_flat: int) -> int:
    """Index-transfer size bucketed to a power of two (bounds jit
    recompiles), clamped to the flat row count."""
    bucket = 1
    while bucket < max(want, 1):
        bucket *= 2
    return min(bucket, n_flat)


class TransferMeter:
    """Device→host byte accounting for the scan path. Every device pull in
    this module funnels through :func:`_host_pull` (kblint KB111 statically
    pins device→host transfers to the named materialization points), so
    ``bytes`` IS the per-process host-transfer cost of serving — the
    transfer-budget tests assert it scales with visible rows, never with
    dataset size."""

    __slots__ = ("_lock", "bytes", "pulls")

    def __init__(self):
        self._lock = threading.Lock()
        self.bytes = 0
        self.pulls = 0

    def add(self, nbytes: int) -> None:
        with self._lock:
            self.bytes += int(nbytes)
            self.pulls += 1

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.bytes, self.pulls


TRANSFER_METER = TransferMeter()


def _host_pull(x) -> np.ndarray:
    """THE device→host materialization funnel for the scan path (kblint
    KB111): blocks on the producing kernel and copies to host, with the
    bytes metered. Pulling a device array anywhere else risks an
    accidental full-mirror gather sneaking back onto the sharded path."""
    arr = np.asarray(x)
    TRANSFER_METER.add(arr.nbytes)
    return arr


@jax.jit
def _victim_part_counts(mask, nv):
    """Per-partition (victims [P], valid [P]) as two small device vectors —
    the host reads 8·P bytes to size the index pull and to decide which
    index set (victims or survivors) is cheaper to transfer. Elementwise +
    per-partition reduction: GSPMD keeps the ``part`` axis sharded."""
    valid = jnp.arange(mask.shape[-1], dtype=jnp.int32)[None, :] < nv[:, None]
    return (jnp.sum(mask, axis=1, dtype=jnp.int32),
            jnp.sum(valid, axis=1, dtype=jnp.int32))


@jax.jit
def _survivor_mask(mask, nv):
    """The complement of a victim mask [P, N] over the valid rows: the
    SURVIVORS, whose indices `_part_indices_of_mask` then pulls (the victim
    kernels gate validity themselves; only the complement needs the
    explicit ``valid`` conjunction). Elementwise: GSPMD keeps the ``part``
    axis sharded."""
    valid = jnp.arange(mask.shape[-1], dtype=jnp.int32)[None, :] < nv[:, None]
    return valid & ~mask


def _victim_pull_size(n_rows: int) -> int:
    """The one index block `_pull_victim_indices` pulls, [P, N/8]: where
    the victims or the survivors of every partition fit in it, their
    indices cross as at most half the byte mask's bytes; where neither
    does, the byte mask is the cheaper pull. One shape per mirror width,
    which :meth:`TpuScanner._compact_warm` compiles before the first
    Compact."""
    return max(1, n_rows // 8)


def _resolve_key_encoding(encode_keys: bool | None) -> bool:
    """Flag/env resolution for the order-preserving key encoding
    (storage/tpu/encode.py). Default ON: the encoded mirror is
    byte-identical to the raw one by construction (shared materialization
    funnel) and the key column is the HBM bound on dataset size;
    KB_ENCODE_KEYS=0 / --key-encoding=raw opts back into the raw layout."""
    if encode_keys is not None:
        return encode_keys
    import os

    return os.environ.get("KB_ENCODE_KEYS", "1").lower() not in ("0", "false", "no")


def _resolve_scan_kernel(use_pallas: bool | None) -> str:
    """Flag/env resolution for the scan kernel choice. Mosaic lowering needs
    a real TPU backend; everywhere else the Pallas path runs interpreted
    (slow — differential/testing only, like the reference's mock engines)."""
    import os

    if use_pallas is None:
        use_pallas = os.environ.get("KB_USE_PALLAS", "").lower() in ("1", "true", "yes")
    if not use_pallas:
        return "jnp"
    interp_env = os.environ.get("KB_PALLAS_INTERPRET", "").lower()
    if interp_env in ("1", "true", "yes"):
        kernel = "pallas_interpret"  # explicitly requested — no warning
    elif interp_env in ("0", "false", "no"):
        kernel = "pallas"
    elif jax.default_backend() == "tpu":
        kernel = "pallas"
    else:
        kernel = "pallas_interpret"
        import logging

        logging.getLogger("kubebrain").warning(
            "--use-pallas without a TPU backend: running the Pallas kernel "
            "under the interpreter (slow; differential/testing only)"
        )
    return kernel


@functools.partial(jax.jit, static_argnames=("with_ttl",))
def _victim_batch(keys, rh, rl, tomb, ttl, nv, start, end, unb, chi, clo, thi, tlo,
                  with_ttl=True):
    """Compaction victim masks for all partitions, range-restricted."""
    f = lambda k, a, b, t, x, n: victim_mask(
        k, a, b, t, x, n, chi, clo, thi, tlo, with_ttl=with_ttl
    )
    mask = jax.vmap(f)(keys, rh, rl, tomb, ttl, nv)
    rng = jax.vmap(lambda k: lex_geq(k, start) & (unb | lex_less(k, end)))(keys)
    return mask & rng


@functools.partial(jax.jit, static_argnames=("with_ttl", "interpret", "mesh"))
def _victim_batch_pallas(keys_t, rh31, rl31, tomb8, ttl8, nv, start, end, unb,
                         chi, clo, thi, tlo, with_ttl=True, interpret=False,
                         mesh=None):
    """Pallas victim masks over the cached chunk-major layout, shard_map'd
    along ``part`` on a multi-device ``mesh`` (static)."""
    from ...ops.compact_pallas import victim_mask_batch_cached

    f = _maybe_shard_map(
        functools.partial(victim_mask_batch_cached, with_ttl=with_ttl,
                          interpret=interpret),
        mesh, n_part_args=6, n_rep_args=7,
    )
    return f(keys_t, rh31, rl31, tomb8, ttl8, nv, start, end, unb, chi, clo, thi, tlo)


@fieldcheck.track
class TpuScanner(Scanner):
    """Scanner contract over the device mirror; host fallback for small
    limit queries (one engine iter beats a kernel launch for a 500-row page).
    """

    def __init__(
        self,
        store: KvStorage,
        get_compact_revision,
        retry_min_revision=lambda: 0,
        compact_history: CompactHistory | None = None,
        max_workers: int = 8,
        mesh=None,
        key_width: int = keyops.KEY_WIDTH,
        merge_threshold: int = 4096,
        host_limit_threshold: int = 1024,
        use_pallas: bool | None = None,
        partitions: int = 0,
        encode_keys: bool | None = None,
    ):
        # the wire path's gather is in libkbstore.so whatever the inner
        # engine: a missing or stale library fails here, not on a Range
        load_lib()
        super().__init__(store, get_compact_revision, retry_min_revision, compact_history, max_workers)
        self._mesh = mesh if mesh is not None else make_mesh()
        # --scan-partitions: mirror partition count decoupled from the mesh
        # size (0 = one per device). P must be a multiple of the ``part``
        # axis so PartitionSpec("part") places P//N partitions per device.
        n_dev = int(self._mesh.devices.size) if self._mesh is not None else 1
        if partitions and partitions % n_dev:
            raise ValueError(
                f"partitions={partitions} must be a multiple of the mesh "
                f"part-axis size {n_dev}")
        self._partitions = int(partitions)
        self._kw = key_width
        self._merge_threshold = merge_threshold
        self._host_limit_threshold = host_limit_threshold
        self._scan_kernel = _resolve_scan_kernel(use_pallas)
        self._encode = _resolve_key_encoding(encode_keys)
        # static mesh arg for the kernel dispatch: only the Pallas path needs
        # it (shard_map); None keeps the jnp path's jit cache key mesh-free
        self._kernel_mesh = self._mesh if self._scan_kernel != "jnp" else None
        self._pallas_cache: tuple[Mirror, tuple] | None = None
        self._pallas_ttl_cache: tuple[Mirror, object] | None = None
        self._probe_cache: tuple[Mirror, list] | None = None
        # the most rows a partition showed at the last read of a (start,
        # end) range: the next read's index block is sized from it, so a
        # read is one device call and one pull (`_pull_visible`)
        self._buckets: dict[tuple[bytes, bytes], int] = {}
        self._bucket_lock = threading.Lock()
        self._mlock = threading.RLock()
        # mergers serialize on their own lock and do the heavy interleave
        # OFF _mlock — readers keep serving mirror+overlay while a merge
        # runs (lock order: _merge_lock before _mlock, never the reverse)
        self._merge_lock = threading.Lock()
        # single-flight admission for write-kicked background merges
        self._merge_kick = threading.Lock()
        self._mirror: Mirror | None = None
        self._delta = _DeltaIndex(self._kw)
        self._force_rebuild = True
        self._metrics = None
        self._gauge_regs: list[tuple[str, dict]] = []
        # merge accounting (also exported as kb_mirror_merge_* metrics):
        # steady state must show merge_rows_total accounting every delta row
        # with full_rebuild_total flat (tests/test_write_batch.py asserts it)
        self.merge_count = 0
        self.merge_rows_total = 0
        self.full_rebuild_total = 0
        # background (write-kicked) merge failures: counted + last error
        # kept so a deterministic merge defect is never silent. Written
        # from background workers AND the foreground read path, so the
        # increment needs its own lock (a bare += loses updates).
        self._merr_lock = threading.Lock()
        self.merge_bg_errors = 0
        self._merge_bg_last_error: Exception | None = None
        # bounded-retry accounting for the background merge (docs/faults.md:
        # a failing merge retries with jittered backoff, then escalates to
        # ONE full rebuild from the authoritative store after K consecutive
        # failures — one exception must never leave the delta growing
        # forever while readers pay unbounded overlay cost)
        self.merge_retries_total = 0
        self.merge_escalations_total = 0
        self._merge_max_retries = 4
        # compaction accounting (docs/compaction.md; also exported through
        # encoding_stats() and the kb_compact_* metrics): full_rebuild_total
        # stays flat while compact_count advances — the steady path never
        # decodes/re-encodes the keyspace (tests/test_compact_device.py)
        self.compact_count = 0
        self.compact_victims_total = 0
        self.compact_survivor_rows_total = 0
        self.compact_retries_total = 0
        self.compact_escalations_total = 0
        self.compact_errors = 0
        self._compact_last_error: Exception | None = None
        # True while a compaction holds _merge_lock across its whole pass
        # (mark → gc → mirror apply): read-path threshold merges SKIP
        # (as beside a merge in flight: _ensure_published) instead of
        # blocking on the lock for the compact's duration —
        # mirror+overlay stays exact, and the post-compact kick sweeps
        # the delta. Guarded by _mlock.
        self._compact_active = False
        # mirror degradation state machine (docs/faults.md): a poisoned
        # (uncertain) mirror QUARANTINES — reads serve from the host store,
        # byte-identical by construction, while a single-flight background
        # rebuild runs — instead of the old poison-until-next-reader
        # stop-the-world rebuild on the read path. States:
        # serving | quarantined | rebuilding (kb_mirror_state gauge).
        self._mirror_state = "serving"
        self._poison_epoch = 0
        self._degraded_since = 0.0
        self.degraded_seconds_total = 0.0
        self.rebuild_bg_count = 0
        self._rebuild_kick = threading.Lock()  # single-flight rebuilds
        self._fault_plane = None  # optional chaos-mode injection hooks
        # seconds the FIRST mirror build took (export from the store,
        # encode, place on the device): boot's mirror_build phase, paid by
        # the first read (kb_boot_seconds{phase="mirror_build"})
        self.boot_mirror_build_s: float | None = None
        # the compaction's warm-up (_compact_warm): the shape it last ran
        # for and its seconds (kb_boot_seconds{phase="compact_warm"})
        self._warm_key: tuple | None = None
        self.compact_warm_s: float | None = None
        # the tracer's profiler sink: trace/ imports no JAX, so the engine
        # that does hands it the annotation factory
        TRACER.set_annotator(jax.profiler.TraceAnnotation)

    def describe(self) -> dict:
        """Resolved placement for the server's boot line: which scan kernel
        serves (``pallas`` on a TPU, ``pallas_interpret`` off it, ``jnp``)
        and the mesh the mirror shards over."""
        return {"scan_kernel": self._scan_kernel,
                "mesh": {k: int(v) for k, v in self._mesh.shape.items()}}

    # -------------------------------------------------------------- metrics
    def register_metrics(self, metrics) -> None:
        """Per-shard HBM accounting: a ``kb_mirror_bytes{device=}`` callback
        gauge per mesh device, sampled at scrape time from the live mirror's
        addressable shards — makes the "per-chip HBM bounds the dataset, not
        the whole mirror" claim observable on /metrics. The companion
        ``kb_mirror_raw_bytes{device=}`` gauge reports what the SAME shard
        would cost with raw (un-encoded) keys, so the prefix-encoding HBM
        saving is scrape-visible as a ratio of the two series."""
        if metrics is None:
            return
        self._metrics = metrics  # also feeds kb_mirror_merge_* emissions
        # degradation state machine: kb_mirror_state{state=} is a 0/1 gauge
        # per state (exactly one is 1 at any scrape) so dashboards can plot
        # quarantine/rebuild windows without string-valued series
        for state in ("serving", "quarantined", "rebuilding"):
            metrics.register_gauge_fn(
                "kb.mirror.state",
                functools.partial(self._state_gauge, state),
                state=state,
            )
            self._gauge_regs.append(("kb.mirror.state", {"state": state}))
        # the delta's fill: what the merge-phase rule of the benchmark rests
        # on (r rows as the window opens) and what every overlay costs
        metrics.register_gauge_fn("kb.mirror.delta.rows",
                                  lambda: len(self._delta))
        self._gauge_regs.append(("kb.mirror.delta.rows", {}))
        if self._mesh is None:
            return
        for d in self._mesh.devices.flat:
            metrics.register_gauge_fn(
                "kb.mirror.bytes",
                functools.partial(self._mirror_device_bytes, str(d)),
                device=str(d),
            )
            metrics.register_gauge_fn(
                "kb.mirror.raw.bytes",
                functools.partial(self._mirror_device_bytes, str(d), True),
                device=str(d),
            )
            self._gauge_regs.append(("kb.mirror.bytes", {"device": str(d)}))
            self._gauge_regs.append(
                ("kb.mirror.raw.bytes", {"device": str(d)}))

    def close(self) -> None:
        # drop the callback gauges registered by register_metrics: they
        # close over the live mirror, so a dangling registration keeps a
        # closed scanner's shards reachable and scrapes garbage
        if self._metrics is not None:
            for name, tags in self._gauge_regs:
                self._metrics.unregister_gauge_fn(name, **tags)
            self._gauge_regs = []
        super().close()

    def _mirror_device_bytes(self, device: str,
                             raw_equivalent: bool = False) -> float:
        """Bytes of mirror columns resident on ``device`` (shard metadata
        only — sampling never copies device data). ``raw_equivalent``
        rescales the key column to the raw packed width, i.e. the bytes an
        un-encoded mirror of the same rows would hold."""
        mirror = self._mirror
        if mirror is None:
            return 0.0
        total = 0
        for arr in (mirror.keys_dev, mirror.rh_dev, mirror.rl_dev,
                    mirror.tomb_dev, mirror.ttl_dev, mirror.n_valid_dev):
            for s in getattr(arr, "addressable_shards", ()):
                if str(s.device) == device:
                    nbytes = int(s.data.size) * s.data.dtype.itemsize
                    if (raw_equivalent and arr is mirror.keys_dev
                            and mirror.encoding is not None):
                        nbytes = (nbytes // mirror.encoding.chunks
                                  * (mirror.raw_key_width // 4))
                    total += nbytes
        return float(total)

    def encoding_stats(self) -> dict:
        """Mirror footprint of the PUBLISHED mirror, for the tests:
        per-row device bytes and the key-compression ratio (raw packed key
        bytes / stored key bytes; 1.0 when the mirror is raw)."""
        mirror = self._mirror
        if mirror is None:
            return {}
        rows = mirror.rows
        stored_w = mirror.keys_host.shape[2] * 4
        per_row = stored_w + 8 + 2  # key chunks + rev hi/lo + tomb/ttl flags
        cap = mirror.keys_host.shape[0] * mirror.keys_host.shape[1]
        return {
            "rows": rows,
            # exact per-valid-row bytes; the padded variant (includes
            # pow2 partition-capacity rounding) is what the device
            # actually holds
            "mirror_bytes_per_row": float(per_row),
            "mirror_bytes_per_row_padded": round(per_row * cap / rows, 2)
            if rows else 0.0,
            "key_bytes_per_row": stored_w,
            "raw_key_bytes_per_row": mirror.raw_key_width,
            "key_compression_ratio": round(mirror.raw_key_width / stored_w, 3),
            "encoded": mirror.encoding is not None,
            "dict_entries": (len(mirror.encoding.boundaries)
                             if mirror.encoding is not None else 0),
            "suffix_width": (mirror.encoding.suffix_width
                             if mirror.encoding is not None else 0),
            # compaction accounting (docs/compaction.md): steady-state
            # compaction must advance compact_count with full_rebuild_total
            # flat — every pass stayed in the stored domain
            "compact_count": self.compact_count,
            "compact_victims_total": self.compact_victims_total,
            "compact_survivor_rows_total": self.compact_survivor_rows_total,
            "compact_retries_total": self.compact_retries_total,
            "compact_escalations_total": self.compact_escalations_total,
            "full_rebuild_total": self.full_rebuild_total,
        }

    # ---------------------------------------------------------- degradation
    def set_fault_plane(self, plane) -> None:
        """Arm chaos-mode injection hooks (kubebrain_tpu.faults): forced
        merge failures, merge suppression (delta growth past threshold),
        and forced EncodeOverflow — the TPU-engine fault classes."""
        self._fault_plane = plane

    def _state_gauge(self, state: str) -> float:
        return 1.0 if self._mirror_state == state else 0.0

    def _enter_degraded_locked(self, state: str) -> None:
        """Under ``_mlock``: transition into quarantined/rebuilding. The
        degraded clock starts on the first non-serving transition."""
        if self._mirror_state == "serving":
            self._degraded_since = time.monotonic()
        self._mirror_state = state

    def _exit_degraded_locked(self) -> None:
        """Under ``_mlock``: back to serving; account the degraded window
        (kb_degraded_seconds — the SLO report's degraded-window source)."""
        if self._mirror_state != "serving":
            dt = time.monotonic() - self._degraded_since
            self.degraded_seconds_total += dt
            if self._metrics is not None:
                self._metrics.emit_counter("kb.degraded.seconds", dt)
        self._mirror_state = "serving"

    def _degraded(self) -> bool:
        """True while the mirror is quarantined/rebuilding — the query
        paths then serve from the authoritative host store (byte-identical
        by construction: the host scanner is the oracle the device path is
        differentially tested against) and re-kick the background rebuild
        in case a previous attempt gave up."""
        with self._mlock:
            degraded = self._mirror_state != "serving"
        if degraded:
            self._kick_rebuild()
        return degraded

    def _kick_rebuild(self) -> None:
        """Single-flight background mirror rebuild from the authoritative
        store, with bounded jittered-backoff retries — quarantine recovery
        never runs on a reader's thread and never stops the world."""
        if not self._rebuild_kick.acquire(blocking=False):
            return
        # sanitizer annotation (no-op in production): the kick's ownership
        # moves to the worker we are about to spawn
        lockcheck.handoff(self._rebuild_kick)

        def run() -> None:
            import random as _random

            lockcheck.adopt(self._rebuild_kick)
            try:
                backoff = 0.05
                for _attempt in range(16):
                    try:
                        if self._rebuild_offline():
                            return
                    except Exception:
                        with self._merr_lock:
                            self.merge_bg_errors += 1
                        if self._metrics is not None:
                            self._metrics.emit_counter(
                                "kb.mirror.merge.errors", 1)
                    time.sleep(backoff * _random.uniform(0.5, 1.5))
                    backoff = min(backoff * 2.0, 1.0)
                # gave up: stay quarantined (host store keeps serving);
                # the next degraded read re-kicks this loop
            finally:
                self._rebuild_kick.release()

        try:
            threading.Thread(target=run, name="kb-mirror-rebuild",
                             daemon=True).start()
        except BaseException:
            # a failed spawn must give the single-flight token back, or no
            # rebuild can EVER run again and the mirror stays quarantined
            self._rebuild_kick.release()
            raise

    def _rebuild_offline(self) -> bool:
        """One rebuild attempt OFF the engine lock: snapshot the store,
        build a fresh mirror, then swap under ``_mlock`` — readers (all on
        the host-store path while quarantined) are never blocked on the
        store scan. Returns False when superseded by a newer poisoning
        (the caller retries against the fresher store state)."""
        with self._merge_lock:
            with self._mlock:
                if not self._force_rebuild and self._mirror is not None:
                    self._exit_degraded_locked()
                    return True  # something else already recovered
                epoch = self._poison_epoch
                delta0 = self._delta
                n0 = len(delta0)
                self._enter_degraded_locked("rebuilding")
            m, _ts = self._build_mirror_from_store()
            with self._mlock:
                if self._poison_epoch != epoch or self._delta is not delta0:
                    # superseded mid-build: poisoned again, or a foreground
                    # rebuild/compact already swapped state under us — never
                    # overwrite fresher state (and never discard its delta)
                    return (not self._force_rebuild
                            and self._mirror is not None)
                self._mirror = m
                tail = self._delta.tail_rows(n0)
                self._force_rebuild = False
                self._delta = self._fresh_delta()
                if tail:
                    self._delta.extend(tail)
                self._pallas_cache = None
                self._pallas_ttl_cache = None
                self._probe_cache = None
                self.rebuild_bg_count += 1
                self._exit_degraded_locked()
            self._kick_compact_warm(m)
        return True

    # ------------------------------------------------------------ write feed
    def record_version_rows(self, rows: list[tuple[bytes, int, bytes]]) -> None:
        plane = self._fault_plane
        t0 = time.monotonic()
        with self._mlock:
            waited = time.monotonic() - t0
            self._delta.extend(rows)  # O(log d) per row via the key index
            if plane is not None and plane.encode_overflow():
                # chaos: an inexpressible key landed — the next merge must
                # take the full re-dictionary rebuild path
                self._delta.force_overflow()
            healthy = self._mirror is not None and not self._force_rebuild
            kick = healthy and (
                len(self._delta) >= self._merge_threshold
                # an open merge-fail window kicks eagerly: the failing-
                # merge retry/escalation machinery must actually run
                or (plane is not None and len(self._delta) > 0
                    and plane.merge_fail_active()))
            pending = len(self._delta) > 0
        if self._metrics is not None:
            # a counter, not a histogram: its delta over a window is exact,
            # where a mean over thousands of unblocked writes says nothing
            self._metrics.emit_counter("kb.mirror.lock.wait.seconds", waited,
                                       who="write")
        if plane is not None and plane.merges_suppressed():
            # chaos: merges suppressed — the delta grows (past the
            # threshold, since kicks are denied) and readers pay the
            # still-exact overlay; each write landing on a pending delta
            # counts one denied merge opportunity
            if pending:
                plane.note_suppressed_merge()
            return
        if kick:
            self._kick_merge()

    def _kick_merge(self) -> None:
        """Single-flight BACKGROUND incremental merge: a write burst that
        crosses the merge threshold starts the merge itself instead of
        leaving the whole accumulated delta for the next reader to pay
        (docs/writes.md). If a merge is already in flight the kick is
        dropped — the next threshold crossing re-kicks, and the final
        ``publish()`` sweeps any tail.

        Failure policy (docs/faults.md): a failing merge retries with
        jittered exponential backoff up to ``_merge_max_retries``
        consecutive failures, then ESCALATES to one full rebuild from the
        authoritative store — readers keep serving mirror+overlay (exact)
        throughout; the old behavior (one exception, delta grows until the
        next kick) left a deterministic merge defect unrecovered forever."""
        if not self._merge_kick.acquire(blocking=False):
            return
        # sanitizer annotation (no-op in production): the kick's ownership
        # moves to the worker we are about to spawn
        lockcheck.handoff(self._merge_kick)

        def run() -> None:
            import random as _random

            lockcheck.adopt(self._merge_kick)
            try:
                backoff = 0.05
                for attempt in range(self._merge_max_retries):
                    try:
                        self._merge_delta()
                        return
                    except Exception as e:
                        # NOT silent: counted scrape-visibly, last error
                        # kept for the foreground path to surface
                        with self._merr_lock:
                            self.merge_bg_errors += 1
                            self._merge_bg_last_error = e
                        if self._metrics is not None:
                            self._metrics.emit_counter(
                                "kb.mirror.merge.errors", 1)
                        if attempt + 1 >= self._merge_max_retries:
                            break
                        self.merge_retries_total += 1
                        if self._metrics is not None:
                            self._metrics.emit_counter(
                                "kb.mirror.merge.retries", 1)
                        time.sleep(backoff * _random.uniform(0.5, 1.5))
                        backoff = min(backoff * 2.0, 1.0)
                # K consecutive failures: the merge path itself is broken
                # (not a transient race) — escalate to one full rebuild
                # from the store, which both absorbs the delta and resets
                # the merge machinery. Readers stay on mirror+overlay.
                self.merge_escalations_total += 1
                if self._metrics is not None:
                    self._metrics.emit_counter("kb.mirror.merge.escalations", 1)
                try:
                    with self._mlock:
                        self._force_rebuild = True
                        self._poison_epoch += 1
                        # quarantine in the SAME lock block (exactly like
                        # mark_uncertain): with _force_rebuild set but the
                        # state still "serving", a racing reader would
                        # take the synchronous stop-the-world rebuild in
                        # _ensure_published — the very thing the
                        # degradation machinery exists to avoid
                        self._enter_degraded_locked("quarantined")
                        # counter bump INSIDE the hold: the unguarded +=
                        # raced the merge path's locked increment (lost
                        # updates on the rebuild ledger, kblint KB120)
                        if self._mirror is not None:
                            self.full_rebuild_total += 1
                    self._rebuild_offline()
                except Exception as e:  # keep the thread from dying silently
                    with self._merr_lock:
                        self._merge_bg_last_error = e
                    if self._metrics is not None:
                        self._metrics.emit_counter("kb.mirror.merge.errors", 1)
            finally:
                self._merge_kick.release()

        try:
            threading.Thread(target=run, name="kb-mirror-merge",
                             daemon=True).start()
        except BaseException:
            # a failed spawn must give the single-flight token back, or no
            # merge can EVER run again and the delta grows unbounded
            self._merge_kick.release()
            raise

    def mark_uncertain(self) -> None:
        """A commit with unknowable outcome may or may not have produced
        rows; only the store knows. The mirror QUARANTINES: reads fall
        back to the host store (authoritative, byte-identical) while a
        single-flight background rebuild runs — degraded-mode serving
        instead of poison-until-the-next-reader-pays-a-stop-the-world-
        rebuild (docs/faults.md)."""
        with self._mlock:
            self._force_rebuild = True
            self._poison_epoch += 1
            self._enter_degraded_locked("quarantined")
        self._kick_rebuild()

    # -------------------------------------------------------------- publish
    def _ensure_published(self, full: bool = False) -> None:
        plane = self._fault_plane
        with self._mlock:
            if self._force_rebuild or self._mirror is None:
                self._rebuild_from_store()
                return
            want_merge = (self._delta
                          and (full or len(self._delta) >= self._merge_threshold))
            if not want_merge:
                return
            if not full and (self._compact_active or self._merge_lock.locked()
                             or self._merge_kick.locked()):
                # a compaction or a merge holds _merge_lock (or a kicked
                # merge is about to take it): serve mirror+overlay (exact)
                # instead of parking this reader on the lock and then
                # merging the tail that gathered behind it; the pass in
                # flight merges the sealed delta prefix anyway
                return
        if not full and plane is not None and plane.merges_suppressed():
            # chaos: serve mirror+overlay (the overlay stays exact); each
            # read that would have merged counts one suppressed merge
            plane.note_suppressed_merge()
            return
        # threshold crossed: merge OFF the engine lock — concurrent readers
        # keep serving mirror+overlay (overlay-wins is exact either way)
        if full:
            self._merge_delta()
            return
        try:
            self._merge_delta(threshold=self._merge_threshold)
        except Exception as e:
            # read-path merge failure must not fail the READ: mirror +
            # overlay is still exact, only bigger. Counted like the
            # background kick; the retry/escalation machinery recovers.
            with self._merr_lock:
                self.merge_bg_errors += 1
                self._merge_bg_last_error = e
            if self._metrics is not None:
                self._metrics.emit_counter("kb.mirror.merge.errors", 1)

    def _build_mirror_from_store(self) -> tuple[Mirror, int]:
        """Build a fresh Mirror from the authoritative store — shared by
        the synchronous rebuild (under ``_mlock``) and the quarantine
        recovery path's offline rebuild (no locks held). Pure read: no
        scanner state is mutated."""
        snapshot = self._store.get_timestamp_oracle()
        lo, hi = coder.internal_range(b"", b"")
        exporter = getattr(self._store, "untracked", lambda: self._store)()
        arrays = None
        if hasattr(exporter, "export_mvcc"):
            # C++ host-shim bulk export: numpy arrays straight from the
            # engine, no per-row Python (SURVEY §2.8 fast path)
            from ...backend.common import TOMBSTONE
            from ..errors import StorageError

            try:
                arrays = exporter.export_mvcc(
                    lo, hi, snapshot, self._kw, coder.MAGIC, TOMBSTONE
                )
            except StorageError as exc:
                # e.g. a kbstored daemon predating OP_EXPORT: degrade to the
                # per-row path instead of failing every rebuild
                import logging

                logging.getLogger("kubebrain").warning(
                    "bulk export unavailable (%s); mirror rebuild falling "
                    "back to per-row iteration", exc,
                )
        if arrays is not None:
            return build_mirror_from_arrays(
                *arrays, self._mesh, self._kw, snapshot,
                n_parts=self._partitions or None, encode=self._encode,
            ), snapshot
        rows: list[tuple[bytes, int, bytes]] = []
        for ikey, value in self._store.iter(lo, hi, snapshot_ts=snapshot):
            ukey, rev = coder.decode(ikey)
            if rev != 0:
                rows.append((ukey, rev, value))
        return build_mirror(rows, self._mesh, self._kw, snapshot,
                            n_parts=self._partitions or None,
                            encode=self._encode), snapshot

    def _rebuild_from_store(self) -> None:
        """Synchronous rebuild, caller holds ``_mlock`` (boot path and the
        forced ``publish()``); also the foreground recovery from a
        quarantined mirror — exiting the degraded window on success."""
        t0 = time.monotonic()
        with TRACER.annotate("mirror_build"):
            self._mirror, _snapshot = self._build_mirror_from_store()
        self._delta = self._fresh_delta()
        self._force_rebuild = False
        self._pallas_cache = None  # old mirror's device copies must not pin
        self._pallas_ttl_cache = None
        self._probe_cache = None
        self._exit_degraded_locked()
        self._kick_compact_warm(self._mirror)
        if self.boot_mirror_build_s is None:
            self.boot_mirror_build_s = time.monotonic() - t0
            if self._metrics is not None:
                self._metrics.emit_gauge("kb.boot.seconds",
                                         self.boot_mirror_build_s,
                                         phase="mirror_build")

    def _fresh_delta(self) -> _DeltaIndex:
        """A delta index bound to the CURRENT mirror's stored domain, so
        write-time sealing encodes against the published dictionary."""
        enc = self._mirror.encoding if self._mirror is not None else None
        seal = max(64, min(512, self._merge_threshold // 4 or 64))
        return _DeltaIndex(self._kw, encoding=enc, seal_rows=seal)

    def _merge_delta(self, threshold: int = 0) -> None:
        """Incremental delta merge, OFF the engine lock (docs/writes.md).
        ``threshold``: merge only if the delta still holds that many rows
        once ``_merge_lock`` is ours (the read path's; a merge that took
        the lock first may have absorbed them).

        The delta accumulated into sorted stored-domain blocks at write
        time; here they k-way interleave (:func:`merge_sorted_stored`) and
        land in only the dirty partitions with a dirty-shard-only device
        republish (:func:`merge_partitions_stored`) — no partition decode,
        no re-encode, no stop-the-world host rebuild. Readers keep serving
        the published mirror + overlay throughout; the swap happens under
        ``_mlock`` and keeps every row appended after the snapshot in the
        successor overlay. Falls back to the full re-partitioning (and,
        when a delta key no longer fits the dictionary, re-dictionary)
        rebuild — counted separately (``full_rebuild_total``), because
        the steady state must never take it."""
        plane = self._fault_plane
        if plane is not None and plane.merge_fault():
            # chaos: the merge fails here, BEFORE any state mutation —
            # readers keep serving mirror+overlay; the kick loop's
            # retry/backoff/escalation machinery must recover
            raise RuntimeError("injected merge failure (fault plane)")
        with self._merge_lock:
            # three phases that tile [t0, dt] (kb_mirror_merge_phase_seconds,
            # kb.merge.<phase> on the profiler's clock): snapshot and swap
            # hold _mlock, which every write needs; build runs off it. Each
            # locked phase starts with the merger's OWN wait for _mlock,
            # counted apart (who="merge") so that hold = phase - wait.
            t0 = time.monotonic()
            with TRACER.annotate("merge.snapshot"), self._mlock:
                lock_wait = time.monotonic() - t0
                if self._force_rebuild or self._mirror is None:
                    self._rebuild_from_store()
                    return
                if len(self._delta) < threshold:
                    return
                mirror = self._mirror
                blocks, rows_prefix, overflow = self._delta.snapshot_blocks()
            n_rows = len(rows_prefix)
            if n_rows == 0:
                return
            t_build = time.monotonic()
            with TRACER.annotate("merge.build"):
                m, full = self._build_merged(mirror, blocks, rows_prefix,
                                             overflow)
            t_swap = time.monotonic()
            with TRACER.annotate("merge.swap"), self._mlock:
                lock_wait += time.monotonic() - t_swap
                if self._mirror is not mirror:
                    # superseded mid-merge (uncertainty rebuild / compact):
                    # the fresher mirror came straight from the store —
                    # discard this merge, its rows are already covered
                    return
                self._mirror = m
                tail = self._delta.tail_rows(n_rows)
                self._delta = self._fresh_delta()
                if tail:
                    self._delta.extend(tail)
                self._pallas_cache = None  # re-layout on the next pallas query
                self._pallas_ttl_cache = None
                self._probe_cache = None
                # accounting lands in the SAME critical section as the swap:
                # publish()'s empty-delta fast path returns under _mlock
                # without touching _merge_lock, so anyone who observed the
                # merged (empty) delta must also observe these counters
                t_end = time.monotonic()
                dt = t_end - t0
                self.merge_count += 1
                if full:
                    self.full_rebuild_total += 1
                else:
                    self.merge_rows_total += n_rows
            if self._metrics is not None:
                self._metrics.emit_histogram(
                    "kb.mirror.merge.seconds", dt,
                    kind="full_rebuild" if full else "incremental")
                for phase, seconds in (("snapshot", t_build - t0),
                                       ("build", t_swap - t_build),
                                       ("swap", t_end - t_swap)):
                    self._metrics.emit_histogram(
                        "kb.mirror.merge.phase.seconds", seconds, phase=phase)
                self._metrics.emit_counter("kb.mirror.lock.wait.seconds",
                                           lock_wait, who="merge")
                if not full:
                    self._metrics.emit_counter(
                        "kb.mirror.merge.rows.total", n_rows)
            self._kick_compact_warm(m)

    def _build_merged(self, mirror: Mirror, blocks, rows_prefix,
                      overflow: bool) -> tuple[Mirror, bool]:
        """The merge's build phase, off ``_mlock``: ``(successor mirror,
        whether it took the full rebuild)``."""
        ts = self._store.get_timestamp_oracle()
        if not overflow:
            delta7 = merge_sorted_stored(blocks)
            m = merge_partitions_stored(mirror, delta7, self._mesh, ts)
            if m is not None:
                return m, False
        # full rebuild: re-partition (capacity overflow) or re-dictionary
        # (EncodeOverflow at seal time) — flat_arrays decodes to RAW rows,
        # merge there, fresh dictionary sized to the merged keyspace
        sorted_delta = sort_arrays(rows_to_arrays(rows_prefix, self._kw))
        merged = merge_sorted_arrays(mirror.flat_arrays(), sorted_delta)
        return build_mirror_from_arrays(*merged, self._mesh, self._kw, ts,
                                        n_parts=self._partitions or None,
                                        encode=self._encode), True

    def publish(self) -> None:
        """Force the mirror fully up to date (the tests' hook)."""
        self._ensure_published(full=True)

    # -------------------------------------------------------------- queries
    def _bound_rows(self, mirror: Mirror, start: bytes, end: bytes):
        """Packed numpy bound rows in the MIRROR'S compare domain — raw
        chunks for a raw mirror, dictionary-encoded bounds for an encoded
        one (encode.KeyEncoding.encode_*_bound: exact by the bound-mapping
        proof, so kernels compare them against encoded rows unchanged).
        The one packing point the single and query-batched paths share."""
        encoding = mirror.encoding if mirror is not None else None
        if encoding is not None:
            enc_s = encoding.encode_start_bound(keyops.canonicalize_bound(start))
            enc_e = (encoding.encode_end_bound(keyops.canonicalize_bound(end))
                     if end else np.zeros(encoding.width, np.uint8))
            return (keyops.bytes_to_chunks(enc_s[None])[0],
                    keyops.bytes_to_chunks(enc_e[None])[0], not end)
        s_row = keyops.pack_one(keyops.canonicalize_bound(start), self._kw)
        e_row = keyops.pack_one(
            keyops.canonicalize_bound(end) if end else b"", self._kw)
        return s_row, e_row, not end

    def _query_bounds(self, mirror: Mirror, start: bytes, end: bytes):
        s_row, e_row, unbounded = self._bound_rows(mirror, start, end)
        return jnp.asarray(s_row), jnp.asarray(e_row), jnp.asarray(unbounded)

    def _shard_put(self, arr):
        if self._mesh is None:
            return jax.device_put(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec("part", *(None,) * (arr.ndim - 1))
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def _pallas_layout(self, mirror: Mirror):
        """Chunk-major sign-flipped device copies for the Pallas kernel,
        computed once per mirror publish (identity-cached) — per-query work
        is then O(C) bound conversion, not an O(P·N·C) re-layout."""
        # identity check + install under _mlock (an RLock): the memo is
        # cleared under it by every rebuild/merge/compact swap, and the
        # lock-free install raced those clears (kblint KB120); the
        # expensive re-layout stays OUTSIDE the hold
        with self._mlock:
            cached = self._pallas_cache
            if cached is not None and cached[0] is mirror:
                return cached[1]
        from ...ops.scan_pallas import prepare_mirror

        kt, rh31, rl31, t8, n = prepare_mirror(
            mirror.keys_host,
            np.asarray(mirror.revs_host, dtype=np.uint64),
            mirror.tomb_host,
        )
        out = (
            self._shard_put(kt), self._shard_put(rh31),
            self._shard_put(rl31), self._shard_put(t8), n,
        )
        with self._mlock:
            cur = self._pallas_cache
            if cur is not None and cur[0] is mirror:
                return cur[1]  # another thread won the install race
            self._pallas_cache = (mirror, out)
        return out

    def _pallas_ttl8(self, mirror: Mirror, npad: int):
        """TTL flag column in the pallas layout, built lazily on first
        compact() use (scan-only workloads never pay the ttl_dev round trip);
        identity-cached per mirror like `_pallas_layout`."""
        # the memo is cleared under _mlock by rebuild/merge/compact swaps
        # but was read+installed here under _merge_lock only (no common
        # guard, kblint KB120): take _mlock (an RLock — compact callers
        # already inside it just re-enter) for the identity check and the
        # install; the device pull stays OUTSIDE the hold
        with self._mlock:
            cached = self._pallas_ttl_cache
            if cached is not None and cached[0] is mirror:
                return cached[1]
        ttl_h = np.asarray(jax.device_get(mirror.ttl_dev)).astype(np.int8)
        pad = npad - ttl_h.shape[1]
        if pad:
            ttl_h = np.pad(ttl_h, ((0, 0), (0, pad)))
        ttl8 = self._shard_put(ttl_h)
        with self._mlock:
            cur = self._pallas_ttl_cache
            if cur is not None and cur[0] is mirror:
                return cur[1]  # another thread won the install race
            self._pallas_ttl_cache = (mirror, ttl8)
        return ttl8

    def _pack_query(self, mirror: Mirror, start: bytes, end: bytes,
                    read_rev: int, rows: bool = True) -> np.ndarray:
        """One query as the one host array `_vis_rows` unpacks, uint32[2C +
        3]: the bounds of `_bound_rows` (the packing point the single and
        query-batched paths share), the flags (1 = unbounded end, 2 = rows
        wanted) and the read revision's high and low words."""
        s_row, e_row, unbounded = self._bound_rows(mirror, start, end)
        c = len(s_row)
        query = np.empty(2 * c + 3, np.uint32)
        query[:c], query[c:2 * c] = s_row, e_row
        read_rev = int(read_rev)
        query[2 * c:] = (int(unbounded) | 2 * rows, read_rev >> 32,
                         read_rev & 0xFFFFFFFF)
        return query

    def _scan_cols(self, mirror: Mirror):
        """``(the five mirror columns the selected kernel reads, the Pallas
        layout's row count)``."""
        if self._scan_kernel == "jnp":
            return (mirror.keys_dev, mirror.rh_dev, mirror.rl_dev,
                    mirror.tomb_dev, mirror.n_valid_dev), 0
        kt, rh31, rl31, t8, n = self._pallas_layout(mirror)
        return (kt, rh31, rl31, t8, mirror.n_valid_dev), n

    def _dev_mask(self, mirror: Mirror, start: bytes, end: bytes,
                  read_rev: int, size: int):
        """One query's device block ``[P, 1 + size]`` (`_vis_rows`: the
        visible counts, then ``size`` row indices a partition) through the
        selected kernel, packed and launched in ONE call — with
        :meth:`_dev_mask_batch` the only assembly points allowed to launch
        the scan kernels (kblint KB109), so count/range/stream can't
        diverge and can't silently miss the kernel dispatch."""
        cols, n = self._scan_cols(mirror)
        return _vis_rows(cols, self._pack_query(mirror, start, end, read_rev),
                         kernel=self._scan_kernel, n=n, size=size,
                         mesh=self._mesh)

    def _dev_mask_batch(self, mirror: Mirror, specs, size: int):
        """Q distinct ``(start, end, read_rev, rows wanted)`` queries in ONE
        call: the block ``[Qpad, P, 1 + size]``, the rows of queries that
        want none left all fill.

        Q is a program *shape* (the query array is [Q, 2C + 3]), so every
        distinct Q would jit-compile a fresh program; Q is therefore padded
        to the next power of two with copies of query 0 that want no rows,
        and the block covers the padded axis — callers read
        ``[:len(specs)]``."""
        qpad = 1
        while qpad < len(specs):
            qpad *= 2
        s0, e0, r0, _rows = specs[0]
        query = np.stack(
            [self._pack_query(mirror, *spec) for spec in specs]
            + [self._pack_query(mirror, s0, e0, r0, False)] * (qpad - len(specs)))
        cols, n = self._scan_cols(mirror)
        return _vis_rows(cols, query, kernel=self._scan_kernel, n=n,
                         size=size, mesh=self._mesh)

    def _pull_visible(self, launch, ranges, picked, n_rows: int, path: str,
                      stage=TRACER.stage):
        """One read's device round trip → host ``(counts [..., P], rows
        [..., P, size])``: partition p's visible rows are ``rows[..., p,
        :counts[..., p]]``, ascending. ``launch(size)`` is the read's
        :meth:`_dev_mask` / :meth:`_dev_mask_batch` call; ``ranges`` the
        ``(start, end)`` of the queries that want rows, at ``picked`` on the
        block's query axis (``[0]`` for a single read).

        The index block's ``size`` is pow2 of the most rows a partition
        showed at the last read of each of ``ranges`` (the largest of a
        batch), so a read is ONE call and ONE pull of O(visible rows),
        never the mask. Where a range was never read (or was forgotten),
        the read takes two steps: the counts alone, then the block at the
        exact bucket. Where the counts overflow a remembered bucket, it
        calls once more at the exact bucket and counts it in
        ``kb_scan_index_refetch_total{path=}``. The answer is exact either
        way. ``device_dispatch`` is the packing and the call,
        ``device_compute`` the pull and any second call."""
        with self._bucket_lock:
            known = [self._buckets.get(r) for r in ranges]
        remembered = None not in known
        most_known = max(known) if ranges and remembered else 0
        size = _pow2_bucket(most_known, n_rows) if most_known else 0
        with stage("device_dispatch"):
            out = launch(size)
        with stage("device_compute"):
            block = _host_pull(out)  # blocks on the program
            most = block[..., 0].max(axis=-1).reshape(-1)[picked]
            need = int(most.max()) if len(most) else 0
            if need > size:
                if remembered and self._metrics is not None:
                    self._metrics.emit_counter("kb.scan.index.refetch.total",
                                               path=path)
                out = launch(_pow2_bucket(need, n_rows))
                block = _host_pull(out)
            # the read's device array goes here, inside a stage: dropping
            # it gives up the GIL, and getting it back must not fall
            # between two stages (kb_rpc_unaccounted_seconds)
            del out
        with self._bucket_lock:
            for r, m in zip(ranges, most.tolist()):
                if r not in self._buckets and len(self._buckets) >= _BUCKET_MEMO:
                    del self._buckets[next(iter(self._buckets))]
                self._buckets[r] = m
        return block[..., 0], block[..., 1:]

    def _materialize_visible(self, mirror: Mirror, vis, overlay):
        """Visible rows (``(counts, rows)`` of :meth:`_pull_visible`)
        → sorted KeyValue list with the delta overlay merged — the ONE host
        materialization the single and query-batched range paths share, so
        batched responses cannot drift from sequential ones by
        construction."""
        from ...backend.common import KeyValue

        counts, rows = vis
        kvs: list[KeyValue] = []
        for p in np.flatnonzero(counts):
            keys, values, revs = mirror.materialize(
                int(p), rows[p, : counts[p]])
            for uk, val, rv in zip(keys, values, revs):
                if uk in overlay:
                    continue  # delta supersedes
                kvs.append(KeyValue(uk, val, int(rv)))
        for uk, entry in overlay.items():
            if entry is not None:
                kvs.append(KeyValue(uk, entry[1], entry[0]))
        kvs.sort(key=lambda kv: kv.key)
        return kvs

    def _materialize_wire(self, mirror: Mirror, vis, overlay,
                          limit: int = 0) -> tuple[bytes, int, bool]:
        """Visible rows (``(counts, rows)`` of :meth:`_pull_visible`)
        → ``(RangeResponse.kvs wire bytes, rows, more)`` with the delta
        overlay merged: what :meth:`_materialize_visible` + ``kvs[:limit]``
        + the front's per-row protobuf produce, byte for byte — the ONE
        wire materialization the single and query-batched wire reads share.
        All of it is one foreign call with the GIL released
        (``native.wire_read`` → ``kb_wire_read``): each visible row's key
        decoded as it is written (through the mirror's dictionary, or as it
        stands where the mirror has none — the C twin of
        :meth:`Mirror.decoded_keys`, held to it by tests/test_wire_read.py),
        the overlay's entries merged in by one walk down both sorted
        sequences, the cut at ``limit``, values copied arena → wire. What
        Python keeps is handing over where the mirror's columns are and
        sizing the reply's buffer, a few steps a PARTITION: nothing runs
        per row or per overlay entry."""
        counts, rows = vis
        encoding = mirror.encoding
        # as the device handed them back, int32: contiguous already on one
        # partition, so this copies nothing there (on several, the block's
        # counts column is cut away); the call reads them through a raw
        # pointer
        rows = np.ascontiguousarray(rows, dtype=np.int32)
        return wire_read(
            mirror.wire_cols, mirror.val_offsets, mirror.keys_host.shape[2],
            mirror.raw_key_width,
            None if encoding is None else encoding.wire_table,
            counts, rows, overlay, limit)

    def _device_range(self, start: bytes, end: bytes, read_revision: int,
                      materialize):
        """One device-path Range, ``materialize(mirror, vis, overlay)`` its
        host half — the stages ``range_`` and ``list_wire`` share."""
        # attribution: delta_overlay = the delta on the read path (publish
        # check, the wait for the writers' lock, the overlay under it);
        # dispatch = query packing + the one async call; compute = the
        # one blocking pull of counts and indices, which waits out the
        # program (and a second call where the bucket was short:
        # `_pull_visible`); host_copy = row materialization + overlay merge
        # on the host. Only this engine's kernel path records the device_*
        # stages, so their EWMAs are the auto-depth dispatch RTT.
        with TRACER.stage("delta_overlay"):
            self._snapshot_checked(read_revision)
            self._ensure_published()
            with self._mlock:
                mirror = self._mirror
                overlay = self._delta.overlay(start, end, read_revision)
        vis = self._pull_visible(
            lambda size: self._dev_mask(mirror, start, end, read_revision,
                                        size),
            [(start, end)], [0], mirror.keys_host.shape[1], "single")
        with TRACER.stage("host_copy"):
            return materialize(mirror, vis, overlay)

    def _on_host(self, limit: int) -> bool:
        """A page small enough that one engine iter beats a kernel launch,
        or a quarantined/rebuilding mirror: the authoritative host store
        answers (the differential oracle — byte-identical)."""
        return bool(limit and limit <= self._host_limit_threshold) \
            or self._degraded()

    def range_(self, start: bytes, end: bytes, read_revision: int, limit: int = 0):
        if self._on_host(limit):
            return Scanner.range_(self, start, end, read_revision, limit)
        kvs = self._device_range(start, end, read_revision,
                                 self._materialize_visible)
        if limit:
            return kvs[:limit], len(kvs) > limit
        return kvs, False

    def list_wire(self, start: bytes, end: bytes, read_revision: int,
                  limit: int = 0) -> tuple[bytes, int, bool]:
        """``range_`` answered as ``(RangeResponse.kvs wire bytes, rows,
        more)``: the same snapshot, stages and rows, written from the
        mirror's host arrays straight into wire bytes by one native call
        (:meth:`_materialize_wire`) instead of built row by row."""
        if self._on_host(limit):
            return self._host_list_wire(start, end, read_revision, limit)
        return self._device_range(
            start, end, read_revision,
            lambda mirror, vis, overlay: self._materialize_wire(
                mirror, vis, overlay, limit))

    def _host_list_wire(self, start: bytes, end: bytes, read_revision: int,
                        limit: int) -> tuple[bytes, int, bool]:
        """The host path of a wire read, in the same queue round: the inner
        engine's own wire scan where it has one, else the host scanner's
        rows through the shared encoder."""
        if hasattr(self._store, "mvcc_list_wire"):
            return list_wire_pages(
                self._store, self._snapshot_checked(read_revision),
                start, end, read_revision, limit)
        kvs, more = Scanner.range_(self, start, end, read_revision, limit)
        with TRACER.stage("host_copy"):
            src = rows_wire_source([(kv.key, kv.value, kv.revision) for kv in kvs])
            return wire_gather([src], [(0, 0, len(kvs))]), len(kvs), more

    def scan_batch(self, queries):
        """B concurrent distinct Range/Count queries against ONE mirror
        snapshot = ONE device dispatch (the ROADMAP query-batched
        ``_dev_mask`` lever). ``queries`` is a list of
        ``("range", start, end, read_rev, limit)`` /
        ``("wire", start, end, read_rev, limit)`` /
        ``("count", start, end, read_rev)`` tuples. Returns a list aligned
        with ``queries`` whose elements are ``(kvs, more)`` for range,
        ``(kvs_blob, rows, more)`` for wire, ``int`` for count, or an
        Exception instance — per-query demux, so e.g. one compacted read
        revision fails its own query, never the batch. Results are
        byte-identical to sequential ``range_``/``list_wire``/``count``
        calls: bounds/revision packing, index extraction, and host
        materialization all reuse the single-query code paths."""
        out: list = [None] * len(queries)
        host = {"range": functools.partial(Scanner.range_, self),
                "wire": self._host_list_wire}
        if self._degraded():
            # degraded-mode serving: per-query host-store scans with the
            # same per-query error demux (the engine-generic shape)
            for i, spec in enumerate(queries):
                try:
                    if spec[0] == "count":
                        out[i] = Scanner.count(self, spec[1], spec[2], spec[3])
                    else:
                        out[i] = host[spec[0]](spec[1], spec[2], spec[3],
                                               spec[4])
                except Exception as e:
                    out[i] = e
            return out
        device: list[tuple[int, tuple]] = []
        for i, spec in enumerate(queries):
            kind, start, end, read_rev = spec[0], spec[1], spec[2], spec[3]
            try:
                if (kind != "count" and spec[4]
                        and spec[4] <= self._host_limit_threshold):
                    # same small-page host fallback as range_: one engine
                    # iter beats a kernel launch for a 500-row page
                    out[i] = host[kind](start, end, read_rev, spec[4])
                    continue
                self._snapshot_checked(read_rev)
            except Exception as e:  # demuxed to this query's waiter
                out[i] = e
                continue
            device.append((i, spec))
        if not device:
            return out
        if len(device) == 1:
            # a batch of one gains nothing over the proven single path
            i, spec = device[0]
            try:
                if spec[0] == "count":
                    out[i] = self.count(spec[1], spec[2], spec[3])
                elif spec[0] == "wire":
                    out[i] = self.list_wire(spec[1], spec[2], spec[3], spec[4])
                else:
                    out[i] = self.range_(spec[1], spec[2], spec[3], spec[4])
            except Exception as e:
                out[i] = e
            return out
        with TRACER.stage("delta_overlay"):
            self._ensure_published()
            with self._mlock:
                mirror = self._mirror
                overlays = [
                    self._delta.overlay(s[1], s[2], s[3]) for _, s in device
                ]
        # counts pull no rows (nor does the pow2 padding)
        specs = [(s[1], s[2], s[3], s[0] != "count") for _, s in device]
        picked = [k for k, spec in enumerate(specs) if spec[3]]
        counts_h, idx_parts = self._pull_visible(
            lambda size: self._dev_mask_batch(mirror, specs, size),
            [specs[k][:2] for k in picked], picked, mirror.keys_host.shape[1],
            "batch")
        with TRACER.stage("host_copy"):
            for k, (qi, spec) in enumerate(device):
                if spec[0] == "count":
                    out[qi] = self._overlay_corrected_count(
                        mirror, int(counts_h[k].sum()), overlays[k], spec[3])
                    continue
                # query k's pieces, as the single read's: one
                # materialization, both callers
                vis, limit = (counts_h[k], idx_parts[k]), spec[4]
                if spec[0] == "wire":
                    out[qi] = self._materialize_wire(
                        mirror, vis, overlays[k], limit)
                    continue
                kvs = self._materialize_visible(mirror, vis, overlays[k])
                out[qi] = (kvs[:limit], len(kvs) > limit) if limit else (kvs, False)
        return out

    def range_stream(self, start: bytes, end: bytes, read_revision: int, batch_size: int = 300):
        """Device-indexed streaming list: bounded batches materialized on
        demand from the index list (reference receiver.go:105-160), with the
        delta overlay merged in key order — unbounded ranges never
        materialize in full on the host."""
        if self._degraded():
            return Scanner.range_stream(self, start, end, read_revision,
                                        batch_size)
        self._snapshot_checked(read_revision)
        self._ensure_published()
        with self._mlock:
            mirror = self._mirror
            overlay = self._delta.overlay(start, end, read_revision)
        # a stream's read records no stage: it answers no unary RPC
        counts_h, rows = self._pull_visible(
            lambda size: self._dev_mask(mirror, start, end, read_revision,
                                        size),
            [(start, end)], [0], mirror.keys_host.shape[1], "single",
            stage=lambda _name: contextlib.nullcontext())
        extra = sorted(
            (k, v) for k, v in overlay.items() if v is not None
        )  # (key, (rev, value)) insertions, key-ascending
        from ...backend.common import KeyValue

        def generate():
            ei = 0
            batch: list[KeyValue] = []

            def push(kv):
                nonlocal batch
                batch.append(kv)
                if len(batch) >= batch_size:
                    out, batch = batch, []
                    return out
                return None

            for p in np.flatnonzero(counts_h):
                for pos in range(0, int(counts_h[p]), 4096):
                    p_rows = rows[p, pos : min(pos + 4096, counts_h[p])]
                    keys, values, revs = mirror.materialize(int(p), p_rows)
                    for uk, val, rv in zip(keys, values, revs):
                        while ei < len(extra) and extra[ei][0] < uk:
                            full = push(KeyValue(extra[ei][0], extra[ei][1][1], extra[ei][1][0]))
                            if full:
                                yield full
                            ei += 1
                        if uk in overlay:
                            continue  # superseded or tombstoned by the delta
                        full = push(KeyValue(uk, val, int(rv)))
                        if full:
                            yield full
            while ei < len(extra):
                full = push(KeyValue(extra[ei][0], extra[ei][1][1], extra[ei][1][0]))
                if full:
                    yield full
                ei += 1
            if batch:
                yield batch

        return generate()

    def count(self, start: bytes, end: bytes, read_revision: int) -> int:
        if self._degraded():
            return Scanner.count(self, start, end, read_revision)
        with TRACER.stage("delta_overlay"):
            self._snapshot_checked(read_revision)
            self._ensure_published()
            with self._mlock:
                mirror = self._mirror
                overlay = self._delta.overlay(start, end, read_revision)
        with TRACER.stage("device_dispatch"):
            out = self._dev_mask(mirror, start, end, read_revision, 0)
        with TRACER.stage("device_compute"):
            total = int(_host_pull(out).sum())  # the counts alone
            del out  # released inside a stage, as in range_
        # the same stage again: one observation per RPC (Tracer.finish)
        with TRACER.stage("delta_overlay"):
            return self._overlay_corrected_count(mirror, total, overlay,
                                                 read_revision)

    def _overlay_corrected_count(self, mirror: Mirror, total: int, overlay,
                                 read_rev: int) -> int:
        """Count = device total + delta-overlay correction. The mirror
        visibility probes for the overlay keys run as ONE vectorized
        searchsorted pass (`_host_visible_batch`) instead of a Python
        binary search (with a key decode per step) per overlay key."""
        if not overlay:
            return total
        keys = list(overlay.keys())
        had = self._host_visible_batch(mirror, keys, read_rev)
        for uk, h in zip(keys, had):
            entry = overlay[uk]
            if entry is None and h:
                total -= 1
            elif entry is not None and not h:
                total += 1
        return total

    def _probe_views(self, mirror: Mirror) -> list:
        """Per-partition void views of the STORED key bytes (valid rows
        only, raw or encoded per the mirror), identity-cached per mirror
        like `_pallas_layout`: void rows compare as raw bytes, so one
        np.searchsorted resolves every probe of a partition at once."""
        # same memo discipline as _pallas_layout: check + install under
        # _mlock, build outside it (kblint KB120)
        with self._mlock:
            cached = self._probe_cache
            if cached is not None and cached[0] is mirror:
                return cached[1]
        w = mirror.keys_host.shape[2] * 4
        views = []
        for p in range(mirror.partitions):
            nv = int(mirror.n_valid[p])
            if nv == 0:
                views.append(np.empty(0, dtype=f"V{w}"))
                continue
            views.append(keyops.u8_void(
                keyops.chunks_to_u8(mirror.keys_host[p, :nv])))
        with self._mlock:
            cur = self._probe_cache
            if cur is not None and cur[0] is mirror:
                return cur[1]  # another thread won the install race
            self._probe_cache = (mirror, views)
        return views

    def _host_visible_batch(self, mirror: Mirror, ukeys: list, read_rev: int) -> list:
        """Vectorized `_host_visible` over many keys: group probes by
        partition, one searchsorted pass per partition against the cached
        byte view (probes enter the mirror's compare domain — encoded
        probes for an encoded mirror; a key the dictionary cannot express
        is absent from the mirror by construction), then a per-group
        (short, ascending) revision pick."""
        if not ukeys:
            return []
        views = self._probe_views(mirror)
        by_part: dict[int, list[int]] = {}
        for j, uk in enumerate(ukeys):
            by_part.setdefault(self._partition_of(mirror, uk), []).append(j)
        out = [False] * len(ukeys)
        encoding = mirror.encoding
        for p, idxs in by_part.items():
            view = views[p]
            if view.shape[0] == 0:
                continue
            if encoding is not None:
                enc_probes = [(j, encoding.encode_probe(ukeys[j])) for j in idxs]
                idxs = [j for j, pb in enc_probes if pb is not None]
                if not idxs:
                    continue  # none of these keys is expressible → absent
                probes_u8 = np.stack([
                    np.frombuffer(pb, np.uint8)
                    for _j, pb in enc_probes if pb is not None])
            else:
                probes_u8 = keyops.chunks_to_u8(np.stack([
                    keyops.pack_one(ukeys[j], self._kw) for j in idxs
                ]))
            probes = keyops.u8_void(probes_u8)
            lo = np.searchsorted(view, probes, side="left")
            hi = np.searchsorted(view, probes, side="right")
            revs = mirror.revs_host[p]
            tombs = mirror.tomb_host[p]
            for j, l, h in zip(idxs, lo, hi):
                if l == h:
                    continue  # key absent from the mirror
                # rows of one key are revision-ascending: last rev <= read_rev
                pos = int(l) + int(np.searchsorted(
                    revs[l:h], np.uint64(read_rev), side="right")) - 1
                if pos >= l:
                    out[j] = not bool(tombs[pos])
        return out

    def _host_visible(self, mirror: Mirror, ukey: bytes, read_rev: int) -> bool:
        """Host-side point visibility check against the published mirror
        (accessor-based binary search; rows are sorted by (key, rev))."""
        p = self._partition_of(mirror, ukey)
        nv = int(mirror.n_valid[p])
        lo, hi = 0, nv
        while lo < hi:  # first row with key >= ukey
            mid = (lo + hi) // 2
            if mirror.user_key(p, mid) < ukey:
                lo = mid + 1
            else:
                hi = mid
        best = None
        for i in range(lo, nv):
            if mirror.user_key(p, i) != ukey:
                break
            if int(mirror.revs_host[p][i]) <= read_rev:
                best = i
        return best is not None and not bool(mirror.tomb_host[p][best])

    @staticmethod
    def _partition_of(mirror: Mirror, ukey: bytes) -> int:
        firsts = mirror.partition_first_keys()
        p = 0
        for i, fk in enumerate(firsts):
            if fk and fk <= ukey:
                p = i
        return p

    # -------------------------------------------------------------- compact
    def _victim_mask(self, mirror: Mirror, s_user: bytes, e_user: bytes,
                     compact_revision: int, ttl_cutoff: int):
        """The victim mask [P, N] of ``[s_user, e_user)`` (``e_user`` empty:
        unbounded) at ``compact_revision`` through the selected kernel — the
        one place a Compact and its warm-up (:meth:`_compact_warm`) assemble
        the kernel's arguments, so the warm compiles what the Compact runs.
        Padded columns are never victims (valid=False)."""
        s, e, unb = self._query_bounds(mirror, s_user, e_user)
        chi, clo = keyops.split_revs(np.array([compact_revision], dtype=np.uint64))
        thi, tlo = keyops.split_revs(np.array([ttl_cutoff], dtype=np.uint64))
        revs = (jnp.asarray(chi[0]), jnp.asarray(clo[0]),
                jnp.asarray(thi[0]), jnp.asarray(tlo[0]))
        if self._scan_kernel == "jnp":
            return _victim_batch(
                mirror.keys_dev, mirror.rh_dev, mirror.rl_dev, mirror.tomb_dev,
                mirror.ttl_dev, mirror.n_valid_dev, s, e, unb, *revs,
                with_ttl=ttl_cutoff > 0,
            )
        kt, rh31, rl31, t8, _n = self._pallas_layout(mirror)
        ttl8 = self._pallas_ttl8(mirror, kt.shape[2])
        return _victim_batch_pallas(
            kt, rh31, rl31, t8, ttl8, mirror.n_valid_dev, s, e, unb, *revs,
            with_ttl=ttl_cutoff > 0,
            interpret=(self._scan_kernel == "pallas_interpret"),
            mesh=self._kernel_mesh,
        )

    def _kick_compact_warm(self, mirror: Mirror) -> None:
        """Warm the compaction's device functions for ``mirror``'s shape on
        a thread of their own (:meth:`_compact_warm`), once per shape: the
        first build, and a publish that changes the padded width, start
        it; no request waits on it."""
        key = mirror.keys_host.shape  # [P, N, C]
        with self._mlock:
            if key == self._warm_key:
                return
            self._warm_key = key
        threading.Thread(target=self._compact_warm, args=(mirror,),
                         name="kb-compact-warm", daemon=True).start()

    def _compact_warm(self, mirror: Mirror) -> None:
        """Compile (or load from the compile cache) what a Compact runs on
        ``mirror``'s shape before a Compact needs it: the victim mark, the
        victim counts and the victim pull's index block
        (:func:`_victim_pull_size`), over the victims and over the
        survivors. It runs them at revision 0, which marks nothing, and
        pulls nothing back. Its seconds are boot's ``compact_warm`` phase
        (``kb_boot_seconds``), annotated ``kb.compact.warm``."""
        with self._mlock:
            if self._mirror is not mirror:
                return  # superseded: its successor's publish warms its shape
        t0 = time.monotonic()
        try:
            with TRACER.annotate("compact.warm"):
                # an engine without native TTLs marks expired rows once the
                # compaction log is old enough: both variants then run
                ttls = (False,) if self._store.support_ttl() else (False, True)
                nv = mirror.n_valid_dev
                for with_ttl in ttls:
                    mask = self._victim_mask(mirror, b"", b"", 0, int(with_ttl))
                    jax.block_until_ready(_victim_part_counts(mask, nv))
                size = _victim_pull_size(int(mask.shape[-1]))
                for m in (mask, _survivor_mask(mask, nv)):
                    jax.block_until_ready(
                        _part_indices_of_mask(m, size=size, mesh=self._mesh))
        except Exception:
            import logging

            # a Compact then compiles what it needs itself, as before
            logging.getLogger("kubebrain").warning(
                "compaction warm-up failed", exc_info=True)
            return
        seconds = time.monotonic() - t0
        with self._mlock:
            self.compact_warm_s = seconds
        if self._metrics is not None:
            self._metrics.emit_gauge("kb.boot.seconds", seconds,
                                     phase="compact_warm")

    def _pull_victim_indices(self, mask_dev, mirror) -> dict[int, np.ndarray]:
        """Per-partition victim row indices via the adaptive SHARD-LOCAL
        two-phase transfer — the compact analogue of the read path's
        :meth:`_pull_visible` and a named KB111 materialization
        funnel. Phase one pulls the per-partition (victims, valid) counts
        (8·P bytes); phase two pulls only the SMALLER index set — victim
        indices on an incremental compact (few victims), survivor indices
        on a bulk one (few survivors) — as a [P, N/8] block compacted
        INSIDE each shard (`_part_indices_of_mask`, over the mask or its
        `_survivor_mask`: no cross-device mask gather on a multi-device
        mesh), rebuilding the complement host-locally. The
        [P, N] byte mask crosses the wire only when the index block would
        be WIDER than the mask itself (victims AND survivors both dense —
        then the mask is the cheaper format, and pulling it is not
        avoidable). The wire should carry victim identities, not the
        keyspace (reference deletes victims by key batch,
        scanner.go:445-491).

        Returns ``{partition -> ascending victim row indices}`` covering
        exactly the partitions with >= 1 victim."""
        n_rows = int(mask_dev.shape[-1])
        vic_dev, valid_dev = _victim_part_counts(mask_dev, mirror.n_valid_dev)
        vic_h = _host_pull(vic_dev)
        valid_h = _host_pull(valid_dev)
        total_vic = int(vic_h.sum())
        if total_vic == 0:
            return {}
        surv_h = valid_h - vic_h
        use_survivors = int(surv_h.sum()) < total_vic
        want = int(surv_h.max()) if use_survivors else int(vic_h.max())
        size = _victim_pull_size(n_rows)
        out: dict[int, np.ndarray] = {}
        if want > size:
            # dense on both sides: index words would out-weigh the byte
            # mask, so the mask IS the minimal wire format here
            mask_h = _host_pull(mask_dev).astype(bool)
            for p in np.nonzero(vic_h)[0]:
                p = int(p)
                out[p] = np.nonzero(mask_h[p, : int(valid_h[p])])[0]
            return out
        if use_survivors:
            idx = _host_pull(_part_indices_of_mask(
                _survivor_mask(mask_dev, mirror.n_valid_dev), size=size,
                mesh=self._mesh))
            for p in np.nonzero(vic_h)[0]:
                p = int(p)
                pmask = np.ones(int(valid_h[p]), dtype=bool)
                pmask[idx[p, : int(surv_h[p])].astype(np.int64)] = False
                out[p] = np.nonzero(pmask)[0]
        else:
            idx = _host_pull(_part_indices_of_mask(
                mask_dev, size=size, mesh=self._mesh))
            for p in np.nonzero(vic_h)[0]:
                p = int(p)
                out[p] = idx[p, : int(vic_h[p])].astype(np.int64)
        return out

    def _compact_victim_rows(self, mirror: Mirror, p: int, rows: np.ndarray):
        """THE victim-only decode point (kblint KB116): raw key bytes for
        exactly the rows compaction is about to delete from the store (the
        engine speaks raw keys) — never a whole partition. Everything else
        the compaction pipeline touches stays in the stored domain."""
        k_u8, lens = mirror.decoded_keys(p, rows)
        return k_u8, np.asarray(lens, np.int32)

    def compact(self, start: bytes, end: bytes, compact_revision: int) -> CompactStats:
        """Device-side victim marking → victim-only host GC → stored-domain
        survivor merge, off the engine lock (docs/compaction.md — the
        north-star "pmap'd compact/GC merge"). ``start``/``end`` are
        internal-key borders from the backend (compact.go:107-126);
        rev-record GC and TTL bookkeeping follow the generic scanner's
        rules, and the store-side deletes are semantically unchanged — only
        the mirror half moved into the stored domain: raw key bytes are
        materialized for VICTIM rows alone (`_compact_victim_rows`),
        survivors are gathered as stored ``(code, suffix)`` blocks and
        k-way merged with any pending delta
        (:func:`blocks.compact_partitions_stored` +
        :func:`blocks.merge_sorted_stored`), republishing only dirty
        shards. No re-encode, no re-dictionary, no re-partition on the
        steady path; ``_mlock`` is held only for the snapshot and the swap,
        so readers keep serving mirror+overlay throughout, with the
        delta-merge retry/backoff → escalate discipline on failure."""
        self._ensure_published(full=True)
        # bypass the delta tracker for our own GC deletes — compact updates
        # the mirror itself at the end
        store = getattr(self._store, "untracked", self._store.exclusive_client)()
        self.compact_history.log(compact_revision)
        ttl_cutoff = 0
        if not store.support_ttl():
            from ...backend.scanner import EVENTS_TTL_SECONDS

            ttl_cutoff = self.compact_history.timeout_revision(EVENTS_TTL_SECONDS)

        phases: dict[str, float] = {}
        applied = False
        superseded = False
        # the WHOLE pass holds _merge_lock: a routine write-kicked delta
        # merge can no longer swap the mirror mid-compaction (which would
        # supersede — and hence quarantine+rebuild — EVERY compaction
        # under ordinary write load). Readers never park on this lock:
        # read-path threshold merges SKIP while _compact_active (the
        # overlay stays exact) and the background merge thread simply
        # waits its single-flight turn. Only an uncertainty rebuild
        # (_force_rebuild under _mlock) can still supersede — the rare
        # case the quarantine handling below exists for.
        with self._merge_lock:
            with self._mlock:
                mirror = self._mirror
                self._compact_active = True
            try:
                t0 = time.monotonic()
                with TRACER.annotate("compact.mark"):
                    # internal borders → user-key bounds for the kernels
                    s_user = (coder.decode(start)[0]
                              if coder.is_internal_key(start) else b"")
                    unbounded = not coder.is_internal_key(end)
                    e_user = b"" if unbounded else coder.decode(end)[0]
                    mask_dev = self._victim_mask(mirror, s_user, e_user,
                                                 compact_revision, ttl_cutoff)
                    victims_by_part = self._pull_victim_indices(mask_dev, mirror)
                phases["mark"] = time.monotonic() - t0

                t0 = time.monotonic()
                stats = CompactStats(scanned=mirror.rows, mirror_path="none",
                                     phase_seconds=phases)
                with TRACER.annotate("compact.gc"):
                    keep_idx = self._compact_gc(mirror, victims_by_part,
                                                store, stats)
                phases["gc"] = time.monotonic() - t0

                n_victims = sum(len(v) for v in victims_by_part.values())
                stats.survivor_rows = mirror.rows - n_victims
                stats.dirty_partitions = len(keep_idx)

                # mirror half, first attempt — still under the pass's
                # merge lock (_mlock only for snapshot + swap)
                try:
                    superseded = self._compact_apply_locked(
                        mirror, keep_idx, stats, phases)
                    applied = True
                except Exception as e:
                    self.compact_errors += 1
                    self._compact_last_error = e
                    if self._metrics is not None:
                        self._metrics.emit_counter("kb.compact.errors", 1)
            finally:
                with self._mlock:
                    self._compact_active = False
        if superseded:
            self._quarantine_superseded_compact(stats)
        elif not applied:
            # attempts 2..K with jittered backoff (sleeps hold NO locks),
            # then the quarantine+rebuild escalation
            self._compact_retry_escalate(mirror, keep_idx, stats, phases)

        self.compact_count += 1
        self.compact_victims_total += n_victims
        self.compact_survivor_rows_total += stats.survivor_rows
        if self._metrics is not None:
            for ph in ("mark", "gc", "merge", "publish"):
                if ph in phases:
                    self._metrics.emit_histogram(
                        "kb.compact.seconds", phases[ph], phase=ph)
            for kind, n in (("superseded", stats.deleted_versions),
                            ("tombstone", stats.deleted_tombstones),
                            ("ttl_expired", stats.expired_ttl),
                            ("rev_record", stats.deleted_rev_records)):
                if n:
                    self._metrics.emit_counter(
                        "kb.compact.victims.total", n, kind=kind)
            if stats.mirror_path == "full_rebuild":
                # a compaction that fell back to the full rebuild must be
                # visible on the SAME series the workload report's
                # steady-state invariant scrapes (kb_mirror_merge_seconds
                # {kind=full_rebuild} — otherwise the "compactions don't
                # drive full rebuilds" check passes vacuously)
                self._metrics.emit_histogram(
                    "kb.mirror.merge.seconds", phases.get("merge", 0.0),
                    kind="full_rebuild")
        return stats

    def _compact_gc(self, mirror: Mirror, victims_by_part: dict, store,
                    stats: CompactStats) -> dict[int, np.ndarray]:
        """The compaction's gc phase: the victims' rows, and the revision
        records of keys left with none, deleted from the store (victim-only
        decode), the victims counted by kind into ``stats``. Returns each
        dirty partition's surviving row indices for the mirror half."""
        retry_min = self._retry_min_revision()
        bulk = getattr(store, "bulk_gc", None)
        BATCH = 256
        pending: list[bytes] = []
        bulk_victims: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        bulk_recs: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = []
        keep_idx: dict[int, np.ndarray] = {}
        for p in sorted(victims_by_part):
            victims = victims_by_part[p]
            nv = int(mirror.n_valid[p])
            pmask = np.zeros(nv, dtype=bool)
            pmask[victims] = True
            keys_p = mirror.keys_host[p, :nv]
            revs_all = mirror.revs_host[p, :nv]
            tomb_all = mirror.tomb_host[p, :nv]
            # group structure (one group = one user key's version chain),
            # computed on the STORED rows — encoded equality == raw
            # equality (the encoding is injective), so no decode here
            same_prev = np.zeros(nv, dtype=bool)
            same_prev[1:] = (keys_p[1:] == keys_p[:-1]).all(axis=1)
            group_starts = np.nonzero(~same_prev)[0]
            group_ends = np.append(group_starts[1:], nv)
            group_sizes = group_ends - group_starts
            doomed_per_group = np.add.reduceat(pmask.astype(np.int64), group_starts)
            last_idx = group_ends - 1
            gid = np.cumsum(~same_prev) - 1  # group id per row

            # victim stats, fully vectorized (no per-row Python;
            # VERDICT r1 weak #3: 1M-victim sweeps must not loop)
            v_tomb = tomb_all[victims].astype(bool)
            v_is_last = victims == last_idx[gid[victims]]
            stats.deleted_tombstones += int(v_tomb.sum())
            stats.deleted_versions += int((~v_tomb & ~v_is_last).sum())
            stats.expired_ttl += int((~v_tomb & v_is_last).sum())

            # rev-record GC candidates: fully-doomed groups whose last
            # revision is below the uncertain-retry fence (scanner.go:472-491)
            dg = np.nonzero(doomed_per_group == group_sizes)[0]
            if len(dg):
                d_last = last_idx[dg]
                d_rev = revs_all[d_last].astype(np.uint64)
                if retry_min:
                    ok = d_rev < np.uint64(retry_min)
                    dg, d_last, d_rev = dg[ok], d_last[ok], d_rev[ok]
            else:
                d_last = np.empty(0, dtype=np.int64)
                d_rev = np.empty(0, dtype=np.uint64)

            # victim-ONLY decode: the rows the store deletes below. A
            # fully-doomed group's first row (the rev-record GC key) is
            # itself a victim, so the decoded set already covers it.
            k_u8_v, lens_v = self._compact_victim_rows(mirror, p, victims)
            firsts = group_starts[dg]
            f_pos = np.searchsorted(victims, firsts)

            if bulk is not None:
                bulk_victims.append((
                    k_u8_v, lens_v, revs_all[victims].astype(np.uint64),
                ))
                bulk_recs.append((
                    k_u8_v[f_pos], lens_v[f_pos], d_rev,
                    tomb_all[d_last].astype(np.uint8),
                ))
            else:
                # k_u8_v/lens_v hold the decoded victims — slice them
                # instead of decoding one row at a time via mirror.user_key
                for j, i in enumerate(victims):
                    uk = k_u8_v[j, : int(lens_v[j])].tobytes()
                    pending.append(
                        coder.encode_object_key(uk, int(revs_all[int(i)]))
                    )
                for j in range(len(dg)):
                    li = int(d_last[j])
                    raw = coder.encode_rev_value(
                        int(d_rev[j]), deleted=bool(tomb_all[li])
                    )
                    fj = int(f_pos[j])
                    uk = k_u8_v[fj, : int(lens_v[fj])].tobytes()
                    try:
                        store.del_current(coder.encode_revision_key(uk), raw)
                        stats.deleted_rev_records += 1
                    except CASFailedError:
                        pass  # rewritten since the mirror snapshot

            keep_idx[p] = np.nonzero(~pmask)[0]
        if bulk is not None and bulk_victims:
            # victims and recs are appended together, once per partition
            vk, vl, vr = (np.concatenate([b[i] for b in bulk_victims]) for i in range(3))
            rk, rl, rr, rt = (np.concatenate([b[i] for b in bulk_recs]) for i in range(4))
            stats.deleted_rev_records += bulk(vk, vl, vr, rk, rl, rr, rt)
        for b0 in range(0, len(pending), BATCH):
            batch = store.begin_batch_write()
            for k in pending[b0 : b0 + BATCH]:
                batch.delete(k)
            batch.commit()

        # engine-level history pruning (see generic scanner): free version
        # chains the logical GC deletes above made unreachable
        pruner = getattr(store, "prune_versions", None)
        if pruner is not None:
            pruner(store.get_timestamp_oracle())
        return keep_idx

    def _compact_retry_escalate(self, mirror, keep_idx, stats, phases) -> None:
        """Attempts 2..K of the compaction's mirror half with the
        background merge's failure discipline (docs/faults.md): jittered-
        backoff retries of :meth:`_compact_apply` (sleeps hold no locks),
        then ESCALATE — the mirror quarantines and one background rebuild
        from the (already GC'd, hence already compacted) authoritative
        store recovers it. The engine deletes are durable either way;
        readers serve the host store while quarantined, byte-identical by
        construction."""
        import random as _random

        backoff = 0.05
        for _attempt in range(1, self._merge_max_retries):
            self.compact_retries_total += 1
            if self._metrics is not None:
                self._metrics.emit_counter("kb.compact.retries", 1)
            time.sleep(backoff * _random.uniform(0.5, 1.5))
            backoff = min(backoff * 2.0, 1.0)
            try:
                self._compact_apply(mirror, keep_idx, stats, phases)
                return
            except Exception as e:
                self.compact_errors += 1
                self._compact_last_error = e
                if self._metrics is not None:
                    self._metrics.emit_counter("kb.compact.errors", 1)
        self.compact_escalations_total += 1
        if self._metrics is not None:
            self._metrics.emit_counter("kb.compact.escalations", 1)
        stats.mirror_path = "escalated"
        with self._mlock:
            self._force_rebuild = True
            self._poison_epoch += 1
            self._enter_degraded_locked("quarantined")
        self._kick_rebuild()

    def _compact_apply(self, mirror, keep_idx, stats, phases) -> None:
        """One RETRY attempt at the mirror half: re-acquire ``_merge_lock``
        (the first attempt runs under :meth:`compact`'s own hold) and
        apply; a supersede quarantines via
        :meth:`_quarantine_superseded_compact`."""
        with self._merge_lock:
            with self._mlock:
                self._compact_active = True
            try:
                superseded = self._compact_apply_locked(
                    mirror, keep_idx, stats, phases)
            finally:
                with self._mlock:
                    self._compact_active = False
        if superseded:
            self._quarantine_superseded_compact(stats)

    def _quarantine_superseded_compact(self, stats) -> None:
        """A mirror superseded mid-pass was rebuilt from the store — but
        possibly from a snapshot PREDATING this compaction's GC deletes.
        Quarantine + one background rebuild re-converges (readers serve
        the host store meanwhile; a silent discard could leave GC'd —
        e.g. TTL-expired, i.e. *visible* — rows serving from the mirror
        indefinitely). With the whole pass under ``_merge_lock`` only an
        uncertainty rebuild can cause this."""
        stats.mirror_path = "superseded"
        with self._mlock:
            self._force_rebuild = True
            self._poison_epoch += 1
            self._enter_degraded_locked("quarantined")
        self._kick_rebuild()

    def _compact_apply_locked(self, mirror, keep_idx, stats, phases) -> bool:
        """ONE attempt at the compaction's mirror half. Caller HOLDS
        ``_merge_lock`` (serializing with delta merges); ``_mlock`` is
        taken only for the delta snapshot and the swap, so readers keep
        serving mirror+overlay throughout. Gathers survivors in the
        stored domain (:func:`compact_partitions_stored`), k-way merges
        any delta sealed before the snapshot, swaps. Returns True when
        the mirror was superseded (an uncertainty rebuild swapped it) —
        the caller must then quarantine."""
        plane = self._fault_plane
        if plane is not None and plane.compact_fault():
            # chaos: fail here, BEFORE any state mutation — readers keep
            # serving mirror+overlay; the caller's retry/backoff/escalation
            # machinery must recover
            raise RuntimeError("injected compact failure (fault plane)")
        t0 = time.monotonic()
        with TRACER.annotate("compact.merge"):
            with self._mlock:
                if self._force_rebuild or self._mirror is not mirror:
                    return True
                blocks_, rows_prefix, overflow = self._delta.snapshot_blocks()
            n_rows = len(rows_prefix)
            ts = self._store.get_timestamp_oracle()
            # an overflowed delta already commits us to the full rebuild —
            # don't pay the stored-domain gather just to discard it
            go_full = n_rows and overflow
            m = (None if go_full
                 else compact_partitions_stored(mirror, keep_idx, self._mesh, ts))
            if m is not None and n_rows:
                delta7 = merge_sorted_stored(blocks_)
                m = merge_partitions_stored(m, delta7, self._mesh, ts)
            full = m is None
            if full:
                # fallback ladder's last rung: pre-ttl_host mirror,
                # stored-width drift, or a delta key the dictionary
                # can't express — the decode-everything full rebuild
                m = self._compact_full_rebuild(mirror, keep_idx, rows_prefix, ts)
        phases["merge"] = time.monotonic() - t0
        t1 = time.monotonic()
        superseded = False
        with TRACER.annotate("compact.publish"), self._mlock:
            if self._force_rebuild or self._mirror is not mirror:
                superseded = True
            elif m is mirror and n_rows == 0:
                # nothing to do (no victims, empty delta)
                stats.mirror_path = "stored_incremental"
            else:
                self._mirror = m
                tail = self._delta.tail_rows(n_rows)
                # bind the fresh delta to the (unchanged) stored
                # domain; rows appended mid-pass stay in the overlay
                self._delta = self._fresh_delta()
                if tail:
                    self._delta.extend(tail)
                self._pallas_cache = None
                self._pallas_ttl_cache = None
                self._probe_cache = None
                if full:
                    self.full_rebuild_total += 1
                stats.mirror_path = (
                    "full_rebuild" if full else "stored_incremental")
        phases["publish"] = time.monotonic() - t1
        if not superseded:
            self._kick_compact_warm(m)
        return superseded

    def _compact_full_rebuild(self, mirror, keep_idx, rows_prefix, ts):
        """The width-drift/dict-overflow fallback: decode every surviving
        row (``flat_arrays`` is the allowed whole-mirror decode path), drop
        the victims, merge the raw delta, re-partition and (when enabled)
        re-dictionary. Steady-state compaction never comes here:
        ``full_rebuild_total`` stays flat."""
        flat = mirror.flat_arrays()
        keepm = np.ones(len(flat[0]), dtype=bool)
        base = 0
        for p in range(mirror.partitions):
            nv = int(mirror.n_valid[p])
            if p in keep_idx:
                pm = np.zeros(nv, dtype=bool)
                pm[keep_idx[p]] = True
                keepm[base : base + nv] = pm
            base += nv
        ki = np.nonzero(keepm)[0]
        arena, offsets = keyops.gather_arena(flat[4], flat[5], ki)
        surv = (flat[0][ki], flat[1][ki], flat[2][ki], flat[3][ki],
                arena, offsets)
        sorted_delta = sort_arrays(rows_to_arrays(rows_prefix, self._kw))
        merged = merge_sorted_arrays(surv, sorted_delta)
        return build_mirror_from_arrays(
            *merged, self._mesh, self._kw, ts,
            n_parts=self._partitions or None, encode=self._encode)


class TpuKvStorage(KvStorage):
    """Decorator pairing a host engine with a TpuScanner delta feed.

    Extracted rows: every committed Put to an object key (revision >= 1) is a
    version row for the mirror. Uncertain commits poison the mirror.
    """

    def __init__(self, inner: KvStorage, mesh=None, key_width: int = keyops.KEY_WIDTH,
                 partitions: int = 0, **scanner_kw):
        self._inner = inner
        self._mesh = mesh
        self._kw = key_width
        self._partitions = partitions
        self._scanner_kw = scanner_kw
        self._scanner: TpuScanner | None = None
        # expose the single-call fast paths only when the host engine has
        # them (instance attributes so hasattr() reflects capability)
        if hasattr(inner, "mvcc_write"):
            self.mvcc_write = self._mvcc_write_tracked
        if hasattr(inner, "mvcc_delete"):
            self.mvcc_delete = self._mvcc_delete_tracked
        if hasattr(inner, "write_batch"):
            self.write_batch = self._write_batch_tracked
        if hasattr(inner, "mvcc_list_wire"):
            # a read: nothing to track, the host path of a wire Range
            self.mvcc_list_wire = inner.mvcc_list_wire

    # ---- scanner wiring (Backend calls make_scanner, storage/__init__.py)
    def make_scanner(self, **kw) -> TpuScanner:
        kw.update(self._scanner_kw)
        self._scanner = TpuScanner(self, mesh=self._mesh, key_width=self._kw,
                                   partitions=self._partitions, **kw)
        return self._scanner

    # ---- engine delegation
    def get_timestamp_oracle(self) -> int:
        return self._inner.get_timestamp_oracle()

    def get_partitions(self, start: bytes, end: bytes) -> list[Partition]:
        """Mesh-partition-aligned shard map so host-fallback scans parallel
        the same way the device does (SURVEY §2.10)."""
        with_mirror = self._scanner and self._scanner._mirror
        if not with_mirror:
            return self._inner.get_partitions(start, end)
        firsts = [fk for fk in self._scanner._mirror.partition_first_keys() if fk]
        borders = [coder.encode_revision_key(fk) for fk in firsts]
        out, left = [], start
        for b in borders:
            if left < b and (not end or b < end):
                out.append(Partition(left, b))
                left = b
        out.append(Partition(left, end))
        return out

    def get(self, key: bytes, snapshot_ts: int | None = None) -> bytes:
        return self._inner.get(key, snapshot_ts)

    def iter(self, start: bytes, end: bytes, snapshot_ts: int | None = None, limit: int = 0):
        return self._inner.iter(start, end, snapshot_ts, limit)

    def begin_batch_write(self) -> BatchWrite:
        return _TrackedBatch(self._inner.begin_batch_write(), self)

    def support_ttl(self) -> bool:
        return self._inner.support_ttl()

    def exclusive_client(self) -> KvStorage:
        return self

    def untracked(self) -> KvStorage:
        """Raw inner engine — used by TpuScanner.compact so its own GC
        deletes don't poison the mirror it is about to update."""
        return self._inner.exclusive_client()

    def close(self) -> None:
        self._inner.close()

    def _mvcc_write_tracked(self, rev_key, rev_val, expected, obj_key, obj_val,
                            last_key, last_val, ttl_seconds=0):
        self._inner.mvcc_write(
            rev_key, rev_val, expected, obj_key, obj_val, last_key, last_val, ttl_seconds
        )
        if coder.is_internal_key(obj_key):
            ukey, rev = coder.decode(obj_key)
            if rev != 0:
                self._on_committed([(ukey, rev, obj_val)])

    def _write_batch_tracked(self, ops: list) -> list:
        """Grouped commit through the inner engine, with the whole group's
        committed version rows recorded into the delta in ONE call, in
        revision order — a group's rows can never interleave with another
        writer's between recordings (the group-commit analogue of the
        per-op tracked fast paths above). Per-op uncertainty (a maybe-
        applied member) poisons the mirror exactly like a lone uncertain
        commit."""
        try:
            results = self._inner.write_batch(ops)
        except UncertainResultError:
            self._on_uncertain()
            raise
        rows: list[tuple[bytes, int, bytes]] = []
        uncertain = False
        for op, res in zip(ops, results):
            status = res[0]
            if status == "uncertain":
                uncertain = True
                continue
            if status != "ok":
                continue
            if op[0] == "delete":
                # ("delete", rev_key, expected_rev, new_rev, new_record,
                #  tombstone, ...)
                rev_key, new_rev, tombstone = op[1], op[3], op[5]
                if coder.is_internal_key(rev_key):
                    rows.append((coder.decode(rev_key)[0], new_rev, tombstone))
            else:
                # ("create", rev_key, new_rev, rev_val, obj_key, obj_val, ...)
                # ("update", rev_key, rev_val, expected, obj_key, obj_val, ...)
                # — both shapes carry (obj_key, obj_val) at slots 4/5
                obj_key, obj_val = op[4], op[5]
                if coder.is_internal_key(obj_key):
                    ukey, rev = coder.decode(obj_key)
                    if rev != 0:
                        rows.append((ukey, rev, obj_val))
        if uncertain:
            self._on_uncertain()
        elif rows:
            self._on_committed(rows)
        return results

    def _mvcc_delete_tracked(self, rev_key, expected_rev, new_rev, new_record,
                             tombstone, last_key, last_val):
        result = self._inner.mvcc_delete(
            rev_key, expected_rev, new_rev, new_record, tombstone, last_key, last_val
        )
        if result[0] == "ok" and coder.is_internal_key(rev_key):
            ukey, _ = coder.decode(rev_key)
            self._on_committed([(ukey, new_rev, tombstone)])
        return result

    def _on_committed(self, rows: list[tuple[bytes, int, bytes]]) -> None:
        if self._scanner is not None and rows:
            self._scanner.record_version_rows(rows)

    def _on_uncertain(self) -> None:
        if self._scanner is not None:
            self._scanner.mark_uncertain()


class _TrackedBatch(BatchWrite):
    def __init__(self, inner: BatchWrite, owner: TpuKvStorage):
        self._inner = inner
        self._owner = owner
        self._rows: list[tuple[bytes, int, bytes]] = []
        self._deletes_object_rows = False

    def _track(self, key: bytes, value: bytes) -> None:
        if coder.is_internal_key(key):
            ukey, rev = coder.decode(key)
            if rev != 0:
                self._rows.append((ukey, rev, value))

    def put_if_not_exist(self, key, value, ttl_seconds=0):
        self._track(key, value)
        self._inner.put_if_not_exist(key, value, ttl_seconds)

    def cas(self, key, new_value, old_value, ttl_seconds=0):
        self._track(key, new_value)
        self._inner.cas(key, new_value, old_value, ttl_seconds)

    def put(self, key, value, ttl_seconds=0):
        self._track(key, value)
        self._inner.put(key, value, ttl_seconds)

    def delete(self, key):
        if coder.is_internal_key(key) and coder.decode(key)[1] != 0:
            self._deletes_object_rows = True
        self._inner.delete(key)

    def del_current(self, key, expected_value):
        if coder.is_internal_key(key) and coder.decode(key)[1] != 0:
            self._deletes_object_rows = True
        self._inner.del_current(key, expected_value)

    def commit(self):
        try:
            self._inner.commit()
        except UncertainResultError:
            self._owner._on_uncertain()
            raise
        # external deletes of version rows (not via TpuScanner.compact, which
        # bypasses tracking and maintains the mirror itself) invalidate the
        # mirror; anything else feeds the delta log
        if self._deletes_object_rows:
            self._owner._on_uncertain()
        else:
            self._owner._on_committed(self._rows)
        self._rows = []


def _tpu_factory(inner: str = "memkv", mesh=None, key_width: int = keyops.KEY_WIDTH,
                 use_pallas: bool | None = None, scan_partitions: int = 0,
                 encode_keys: bool | None = None, inner_wrap=None,
                 merge_threshold: int = 0, **inner_kw) -> TpuKvStorage:
    """``scan_partitions`` is the MIRROR's partition count; it is not named
    ``partitions`` because ``inner_kw`` carries the native host engine's own
    ``partitions`` (its host-scan sampling) through to ``new_storage``."""
    from .. import new_storage

    scanner_kw = {} if use_pallas is None else {"use_pallas": use_pallas}
    if encode_keys is not None:
        scanner_kw["encode_keys"] = encode_keys
    if merge_threshold:
        scanner_kw["merge_threshold"] = merge_threshold
    host = new_storage(inner, **inner_kw)
    if inner_wrap is not None:
        # decorate the HOST engine (chaos mode wraps FaultyStorage here, so
        # injected uncertainty exercises the mirror's quarantine machinery)
        host = inner_wrap(host)
    return TpuKvStorage(
        host, mesh=mesh, key_width=key_width,
        partitions=scan_partitions, **scanner_kw
    )


register_engine("tpu", _tpu_factory)

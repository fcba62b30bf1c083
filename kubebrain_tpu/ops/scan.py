"""Blockwise MVCC range-scan kernels — the north-star hot loop.

Reference hot loop: pkg/backend/scanner/scanner.go worker.run :389-516 — per
row: decode internal key, prefix/range compare, revision filter, "last
version <= read_rev per user key" selection, tombstone suppression. Here the
whole pass is a handful of vectorized ops over a sorted packed block:

    rows sorted by (key asc, revision asc)
    cand[i]    = valid[i] & in_range[i] & rev[i] <= read_rev
    visible[i] = cand[i] & !(same_key[i,i+1] & cand[i+1]) & !tombstone[i]

The "next row differs" test replaces the scan worker's prev-key carry
(scanner.go:408-414,451-470). Blocks are always split at user-key boundaries
(the same trick as adjustPartitionBorders, scanner.go:202-225), so no
cross-block carry is needed and every block/shard is independent — which is
exactly what makes the scan embarrassingly parallel over the device mesh.

All functions are shape-polymorphic pure jax and run under jit/shard_map on
TPU or CPU. The Pallas variant (scan_pallas.py) tiles the same math through
VMEM explicitly for the large-block case.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lex_less(keys: jnp.ndarray, bound: jnp.ndarray) -> jnp.ndarray:
    """keys[N, C] < bound[C] lexicographically over big-endian u32 chunks.

    First-differing-chunk decides: O(N*C) compares, no data-dependent control
    flow — XLA maps it straight onto the VPU.
    """
    eq = keys == bound
    lt = keys < bound
    neq = ~eq
    has_diff = jnp.any(neq, axis=-1)
    first = jnp.argmax(neq, axis=-1)
    lt_first = jnp.take_along_axis(lt, first[..., None], axis=-1)[..., 0]
    return has_diff & lt_first


def lex_geq(keys: jnp.ndarray, bound: jnp.ndarray) -> jnp.ndarray:
    return ~lex_less(keys, bound)


def rev_leq(rev_hi: jnp.ndarray, rev_lo: jnp.ndarray, read_hi, read_lo) -> jnp.ndarray:
    """(hi, lo) uint32 pair compare: rev <= read_rev."""
    return (rev_hi < read_hi) | ((rev_hi == read_hi) & (rev_lo <= read_lo))


def same_as_next(keys: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: row i has the same user key as row i+1 (False for the last
    row — blocks never split a user key's version chain)."""
    nxt = jnp.roll(keys, -1, axis=0)
    same = jnp.all(keys == nxt, axis=-1)
    n = keys.shape[0]
    return same & (jnp.arange(n) != n - 1)


def visibility_mask(
    keys: jnp.ndarray,      # uint32[N, C] packed user keys, sorted
    rev_hi: jnp.ndarray,    # uint32[N]
    rev_lo: jnp.ndarray,    # uint32[N]
    tomb: jnp.ndarray,      # bool[N]
    n_valid: jnp.ndarray,   # int32 scalar: rows beyond are padding
    start: jnp.ndarray,     # uint32[C] packed start bound (inclusive)
    end: jnp.ndarray,       # uint32[C] packed end bound (exclusive)
    unbounded_end: jnp.ndarray,  # bool scalar: ignore `end`
    read_hi: jnp.ndarray,   # uint32 scalar
    read_lo: jnp.ndarray,   # uint32 scalar
) -> jnp.ndarray:
    """bool[N]: rows visible at read_rev within [start, end)."""
    n = keys.shape[0]
    valid = jnp.arange(n) < n_valid
    in_range = lex_geq(keys, start) & (unbounded_end | lex_less(keys, end))
    cand = valid & in_range & rev_leq(rev_hi, rev_lo, read_hi, read_lo)
    cand_next = jnp.roll(cand, -1)
    superseded = same_as_next(keys) & cand_next
    return cand & ~superseded & ~tomb


# Query-batched compares up to this many (query, row) pairs per block are
# vmapped over the query axis; larger ones map it sequentially. The vmapped
# temporaries are ~210 B per pair on a v5e (436 MB at 8 x 262,144 by the TPU
# compiler's memory analysis), so this is < 1 GB.
_VMAP_QUERY_ROWS = 1 << 22


def visibility_mask_queries(
    keys, rev_hi, rev_lo, tomb, n_valid, starts, ends, unbounded_ends,
    read_his, read_los,
) -> jnp.ndarray:
    """Query axis over :func:`visibility_mask`: Q distinct Range/Count
    queries (``starts``/``ends`` uint32[Q, C] packed bounds,
    ``unbounded_ends`` bool[Q], ``read_his``/``read_los`` uint32[Q])
    answered against ONE block in one traced program. Returns bool[Q, N] —
    the jnp fallback of the query-batched Pallas kernel
    (scan_pallas.scan_mask_pallas_q).

    One launch either way, but XLA does not fuse the lex compare's
    argmax/gather: the vmapped program materializes ``pred[Q, N, C]`` with C
    padded to 128 lanes on a TPU — 20 GB at Q=8 over 20M rows, more than a
    chip holds. A block too large for that (shapes are static) maps the
    query axis sequentially instead; a served kube-sized mirror keeps the
    plain vmap."""
    f = lambda q: visibility_mask(
        keys, rev_hi, rev_lo, tomb, n_valid, *q
    )
    small = starts.shape[0] * keys.shape[0] <= _VMAP_QUERY_ROWS
    # batch_size 0 is a full-width vmap, None one query at a time
    return jax.lax.map(
        f, (starts, ends, unbounded_ends, read_his, read_los),
        batch_size=0 if small else None)


@jax.jit
def count_visible(keys, rev_hi, rev_lo, tomb, n_valid, start, end, unbounded_end, read_hi, read_lo):
    mask = visibility_mask(
        keys, rev_hi, rev_lo, tomb, n_valid, start, end, unbounded_end, read_hi, read_lo
    )
    return jnp.sum(mask, dtype=jnp.int32)


@jax.jit
def visible_mask_jit(keys, rev_hi, rev_lo, tomb, n_valid, start, end, unbounded_end, read_hi, read_lo):
    return visibility_mask(
        keys, rev_hi, rev_lo, tomb, n_valid, start, end, unbounded_end, read_hi, read_lo
    )


def visible_indices(mask: jnp.ndarray, size: int) -> jnp.ndarray:
    """First ``size`` set positions of mask (fill = len(mask)); jit-safe with
    static ``size`` — the device-side equivalent of the receiver append loop
    (receiver.go:21-31)."""
    (idx,) = jnp.nonzero(mask, size=size, fill_value=mask.shape[0])
    return idx


def make_point_lookup(n_chunks: int):
    """Point-get kernel: latest version of ONE key at read_rev.

    Returns (found bool, rev_hi, rev_lo, row int32, tombstone bool). The
    binary-search-free formulation: exact-match mask & rev filter & take last.
    """

    @jax.jit
    def lookup(keys, rev_hi, rev_lo, tomb, n_valid, key, read_hi, read_lo):
        n = keys.shape[0]
        valid = jnp.arange(n) < n_valid
        match = valid & jnp.all(keys == key, axis=-1) & rev_leq(rev_hi, rev_lo, read_hi, read_lo)
        # last matching row = highest revision <= read_rev
        idx = n - 1 - jnp.argmax(match[::-1])
        found = jnp.any(match)
        idx = jnp.where(found, idx, 0)
        return found, rev_hi[idx], rev_lo[idx], idx.astype(jnp.int32), tomb[idx]

    return lookup

"""Pallas TPU kernel for the MVCC visibility scan.

Same math as ops.scan.visibility_mask, tiled explicitly for the TPU VPU:

- **chunk-major layout** ``int32[C, N]``: rows ride the 128-wide lane axis,
  key chunks ride sublanes, so per-row reductions (lex compare, equality)
  are cheap sublane reductions instead of cross-lane ones;
- **sign-flipped chunks**: packed big-endian u32 chunks XOR 0x8000_0000 make
  signed int32 order equal unsigned byte order — Mosaic-native compares;
- **31-bit revision split** (hi = rev >> 31, lo = rev & 0x7fff_ffff): both
  halves non-negative int32, so revision compares stay signed-safe;
- **reverse-tile grid + carry**: "is this row superseded?" looks at the NEXT
  row, so tiles run last→first and a VMEM/SMEM scratch carries the next
  tile's first key/candidate across grid steps (TPU grid iterations are
  sequential, so the carry is well-defined — the Pallas analogue of the scan
  worker's prev-key carry, scanner.go:408-414);
- **one kernel, grid = (queries, partitions, reverse tiles)**: partitions and
  queries are explicit grid axes with their scalars read from SMEM by
  ``program_id`` — ``jax.vmap`` over a ``pallas_call`` batches the SMEM
  scalar operand into a block shape the Mosaic lowering rejects;
- the lex compare avoids argmax/gather/cumsum (none lower through Mosaic):
  first-differing-chunk selection via an unrolled prefix-AND over the
  static chunk axis.

Falls back to interpret mode off-TPU (tests run it on CPU against the jnp
kernel as oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows per grid step (a multiple of 128 lanes). Grid iteration overhead
# dominates at small tiles (a 20M-row scan is ~20k steps at 1024) and VMEM
# per step is only ~66B * TILE.
LANE_TILE = 4096


def flip_sign(chunks: np.ndarray) -> np.ndarray:
    """uint32 chunks -> order-preserving int32 (big-endian unsigned order)."""
    return (chunks.astype(np.uint32) ^ np.uint32(0x80000000)).view(np.int32)


def split_revs31(revs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uint64 -> (hi, lo) non-negative int32 halves (31-bit low split)."""
    revs = np.asarray(revs, dtype=np.uint64)
    hi = (revs >> np.uint64(31)).astype(np.int64)
    if (hi >= 2**31).any():
        raise ValueError("revision exceeds 2^62")
    return hi.astype(np.int32), (revs & np.uint64(0x7FFFFFFF)).astype(np.int32)


def _lex_less(keys, bound, neq, lt):
    """columns of keys < bound: first-differing-chunk decides.

    keys/neq/lt: [C, T]; bound: [C, 1]. Returns [1, T] bool.

    Unrolled prefix-AND over the (static, small) chunk axis — Mosaic has no
    cumsum lowering, and C is 16 for 64-byte keys, so a trace-time loop of
    plain VPU mask ops is both lowerable and cheap.
    """
    del keys, bound
    c = neq.shape[0]
    out = lt[0:1, :]
    prefix_eq = ~neq[0:1, :]
    for ci in range(1, c):
        out = out | (prefix_eq & lt[ci : ci + 1, :])
        prefix_eq = prefix_eq & ~neq[ci : ci + 1, :]
    return out


def _tile_visibility(t, n_valid, unbounded, qhi, qlo, start, end,
                     keys_ref, rh_ref, rl_ref, tomb_ref,
                     carry_key, carry_flag):
    """One reverse-order tile of the visibility scan: the shared body of the
    single-query and query-batched kernels (so adding the query grid axis
    cannot drift from the proven single-query math). Returns the int8
    visibility block and updates the carry scratch for tile ``t - 1``."""
    keys = keys_ref[:, :]          # [C, T] int32 (sign-flipped chunks)
    rh = rh_ref[:, :]              # [1, T]
    rl = rl_ref[:, :]
    tomb = tomb_ref[:, :] != 0     # [1, T]
    c, tile = keys.shape

    neq_s = keys != start
    lt_s = keys < start
    less_start = _lex_less(keys, start, neq_s, lt_s)
    neq_e = keys != end
    lt_e = keys < end
    less_end = _lex_less(keys, end, neq_e, lt_e)
    in_range = (~less_start) & ((unbounded != 0) | less_end)

    rev_le = (rh < qhi) | ((rh == qhi) & (rl <= qlo))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    idx = t * tile + lane
    valid = idx < n_valid

    cand = valid & in_range & rev_le

    # same-key-as-next within the tile; the last column compares against the
    # carried first key of the NEXT tile (processed in the previous step)
    nxt_keys = jnp.roll(keys, -1, axis=1)
    carried = carry_key[:, :]  # [C, 1]
    is_last_col = lane == (tile - 1)
    nxt_keys = jnp.where(is_last_col, carried, nxt_keys)
    same_next = jnp.all(keys == nxt_keys, axis=0, keepdims=True)
    # scalar bools broadcast into vector selects lower as i8->i1 truncations
    # Mosaic rejects; keep the carried flags in int32 until the final compare
    have_i = ((t + 1) * tile < n_valid).astype(jnp.int32)
    same_next = same_next & (jnp.where(is_last_col, have_i, 1) != 0)

    cand_next_i = jnp.roll(cand.astype(jnp.int32), -1, axis=1)
    cand_next = jnp.where(is_last_col, carry_flag[0] * have_i, cand_next_i) != 0

    visible = cand & ~(same_next & cand_next) & ~tomb

    # publish this tile's first column for the next grid step (tile t-1)
    carry_key[:, :] = keys[:, 0:1]
    carry_flag[0] = cand.astype(jnp.int32)[0, 0]
    return visible.astype(jnp.int8)


def _kernel(nv_ref, unb_ref, qhi_ref, qlo_ref, start_ref, end_ref,
            keys_ref, rh_ref, rl_ref, tomb_ref,
            mask_ref,
            carry_key, carry_flag):
    """grid = (queries, partitions, reverse tiles). TPU grid steps run
    sequentially with the LAST axis minor, so for each (query, partition)
    the tile sweep i = 0..nt-1 is contiguous and the carry is well-defined.
    No carry reset between sweeps is needed: tile nt-1 (the first step of
    every sweep) masks the carried flag/key out via ``have_i``."""
    q = pl.program_id(0)
    p = pl.program_id(1)
    t = pl.num_programs(2) - 1 - pl.program_id(2)  # reversed tile order

    mask_ref[:, :] = _tile_visibility(
        t, nv_ref[p], unb_ref[q], qhi_ref[q], qlo_ref[q],
        start_ref[:, :], end_ref[:, :],
        keys_ref, rh_ref, rl_ref, tomb_ref,
        carry_key, carry_flag,
    )


def _scan_call(keys_t, rh31, rl31, tomb, n_valid, starts, ends, unbounded,
               qhi31, qlo31, interpret):
    """THE scan ``pallas_call``: Q queries × P partitions in one launch.

    keys_t int32[P, C, N] chunk-major sign-flipped (N % LANE_TILE == 0);
    rh31/rl31 int32[P, N]; tomb int8[P, N]; n_valid int32[P];
    starts/ends int32[Q, C] sign-flipped bounds; unbounded/qhi31/qlo31
    int32[Q]. Returns bool[Q, P, N]. Row vectors ride as [P, 1, N] so every
    block's last two dims equal the array's or are (8, 128)-aligned.
    """
    p, c, n = keys_t.shape
    assert n % LANE_TILE == 0, "pad rows to LANE_TILE"
    nq = starts.shape[0]
    nt = n // LANE_TILE
    i32 = lambda x, shape: jnp.asarray(x, jnp.int32).reshape(shape)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bound = pl.BlockSpec((None, c, 1), lambda q, pi, i: (q, 0, 0))
    row = pl.BlockSpec((None, 1, LANE_TILE),
                       lambda q, pi, i: (pi, 0, nt - 1 - i))
    mask = pl.pallas_call(
        _kernel,
        grid=(nq, p, nt),
        in_specs=[
            smem, smem, smem, smem,     # n_valid[P]; unbounded/qhi/qlo[Q]
            bound, bound,               # start / end bounds
            pl.BlockSpec((None, c, LANE_TILE),
                         lambda q, pi, i: (pi, 0, nt - 1 - i)),  # keys
            row, row, row,              # rev hi, rev lo, tombstones
        ],
        out_specs=pl.BlockSpec((None, None, 1, LANE_TILE),
                               lambda q, pi, i: (q, pi, 0, nt - 1 - i)),
        out_shape=jax.ShapeDtypeStruct((nq, p, 1, n), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.int32),   # carried first key
            pltpu.SMEM((1,), jnp.int32),     # carried first cand
        ],
        interpret=interpret,
    )(
        i32(n_valid, (p,)), i32(unbounded, (nq,)),
        i32(qhi31, (nq,)), i32(qlo31, (nq,)),
        starts.reshape(nq, c, 1), ends.reshape(nq, c, 1),
        keys_t, rh31.reshape(p, 1, n), rl31.reshape(p, 1, n),
        tomb.reshape(p, 1, n),
    )
    return mask.reshape(nq, p, n) != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_mask_pallas(keys_t, rh31, rl31, tomb, n_valid, start, end, unbounded,
                     qhi31, qlo31, interpret=False):
    """Visibility mask of ONE query over ONE block.

    keys_t: int32[C, N] chunk-major sign-flipped; rh31/rl31: int32[N];
    tomb: int8[N]; start/end: int32[C] sign-flipped bounds;
    scalars: n_valid, unbounded, qhi31, qlo31.
    Returns bool[N].
    """
    return _scan_call(
        keys_t[None], rh31[None], rl31[None], tomb[None], n_valid,
        start[None], end[None], unbounded, qhi31, qlo31, interpret)[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_mask_pallas_q(keys_t, rh31, rl31, tomb, n_valid, starts, ends,
                       unbounded, qhi31, qlo31, interpret=False):
    """Query-batched visibility masks: ONE kernel launch answers Q distinct
    Range/Count queries over the same block — a kernel launch amortized
    over Q queries instead of Q launches.

    keys_t: int32[C, N] chunk-major sign-flipped; rh31/rl31: int32[N];
    tomb: int8[N]; starts/ends: int32[Q, C] sign-flipped bounds;
    unbounded/qhi31/qlo31: int32[Q] per-query scalars; n_valid scalar.
    Returns bool[Q, N].
    """
    return _scan_call(
        keys_t[None], rh31[None], rl31[None], tomb[None], n_valid,
        starts, ends, unbounded, qhi31, qlo31, interpret)[:, 0]


def _flip_sign_jnp(x: jnp.ndarray) -> jnp.ndarray:
    """In-graph equivalent of :func:`flip_sign` (uint32 -> int32 bitcast)."""
    return jax.lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000), jnp.int32)


def _split31_jnp(hi32: jnp.ndarray, lo32: jnp.ndarray):
    """(hi, lo) 32-bit uint32 split -> (hi, lo) 31-bit int32 split in-graph.

    Safe for revisions < 2^62 (hi < 2^30, so hi<<1|lo>>31 < 2^31)."""
    rh31 = jax.lax.bitcast_convert_type(
        (hi32 << jnp.uint32(1)) | (lo32 >> jnp.uint32(31)), jnp.int32
    )
    rl31 = jax.lax.bitcast_convert_type(lo32 & jnp.uint32(0x7FFFFFFF), jnp.int32)
    return rh31, rl31


@functools.partial(jax.jit, static_argnames=("interpret",))
def visibility_mask_batch(keys, rh, rl, tomb, n_valid, start, end, unbounded,
                          read_hi, read_lo, interpret=False):
    """Pallas visibility masks straight off the row-major mirror layout,
    converting in-graph on every call — the UNCACHED variant, kept as the
    kernel-level differential-test entry point. Production (`TpuScanner`
    under --use-pallas) uses `prepare_mirror` + `visibility_mask_batch_cached`
    so the layout conversion happens once per mirror publish, not per query.

    Same contract as ``vmap(ops.scan.visibility_mask)``:
    keys uint32[P, N, C] big-endian chunks, rh/rl uint32[P, N] (32-bit rev
    split), tomb bool[P, N], n_valid int32[P], start/end uint32[C] packed
    bounds, unbounded bool, read_hi/read_lo uint32. Returns bool[P, N].

    Layout conversion (transpose to chunk-major, sign flip, 31-bit rev
    resplit, LANE_TILE padding) happens in-graph: XLA fuses it into the
    surrounding program and the kernel sees its native tiling.
    """
    p, n, c = keys.shape
    if n == 0:
        return jnp.zeros((p, 0), dtype=bool)
    pad = (-n) % LANE_TILE
    if pad:
        keys = jnp.pad(keys, ((0, 0), (0, pad), (0, 0)))
        rh = jnp.pad(rh, ((0, 0), (0, pad)))
        rl = jnp.pad(rl, ((0, 0), (0, pad)))
        tomb = jnp.pad(tomb, ((0, 0), (0, pad)))
    keys_t = _flip_sign_jnp(jnp.swapaxes(keys, 1, 2))  # [P, C, Npad]
    rh31, rl31 = _split31_jnp(jnp.asarray(rh, jnp.uint32), jnp.asarray(rl, jnp.uint32))
    qhi31, qlo31 = _split31_jnp(
        jnp.asarray(read_hi, jnp.uint32), jnp.asarray(read_lo, jnp.uint32)
    )
    s = _flip_sign_jnp(jnp.asarray(start, jnp.uint32))
    e = _flip_sign_jnp(jnp.asarray(end, jnp.uint32))
    unb = jnp.asarray(unbounded, jnp.int32)
    mask = _scan_call(keys_t, rh31, rl31, tomb.astype(jnp.int8), n_valid,
                      s[None], e[None], unb, qhi31, qlo31, interpret)
    return mask[0, :, :n]


def prepare_mirror(keys_host: np.ndarray, revs_host: np.ndarray,
                   tomb_host: np.ndarray, tile: int = LANE_TILE):
    """Row-major mirror arrays → Pallas layout, computed ONCE per mirror
    publish (numpy, host-side): chunk-major sign-flipped keys, 31-bit rev
    split, int8 tombstones, rows padded to ``tile``.

    keys_host uint32[P, N, C], revs_host uint64[P, N], tomb_host bool[P, N].
    Returns (keys_t int32[P, C, Npad], rh31 int32[P, Npad],
    rl31 int32[P, Npad], tomb8 int8[P, Npad], n).

    The per-query path (`visibility_mask_batch_cached`) then only converts
    the bounds and read revision — O(C) per scan instead of O(P·N·C).
    """
    p, n, c = keys_host.shape
    pad = (-n) % tile
    if pad:
        keys_host = np.pad(keys_host, ((0, 0), (0, pad), (0, 0)))
        revs_host = np.pad(revs_host, ((0, 0), (0, pad)))
        tomb_host = np.pad(tomb_host, ((0, 0), (0, pad)))
    keys_t = np.ascontiguousarray(np.transpose(flip_sign(keys_host), (0, 2, 1)))
    rh31, rl31 = split_revs31(np.asarray(revs_host, dtype=np.uint64).reshape(-1))
    npad = n + pad
    return (keys_t, rh31.reshape(p, npad), rl31.reshape(p, npad),
            tomb_host.astype(np.int8), n)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def visibility_mask_batch_cached(keys_t, rh31, rl31, tomb8, nv, start, end,
                                 unbounded, read_hi, read_lo, n, interpret=False):
    """Per-query Pallas path over a `prepare_mirror`-cached layout. Only the
    bounds (uint32[C] packed) and read revision (uint32 split) are converted
    in-graph. Returns bool[P, n]."""
    qhi31, qlo31 = _split31_jnp(
        jnp.asarray(read_hi, jnp.uint32), jnp.asarray(read_lo, jnp.uint32)
    )
    s = _flip_sign_jnp(jnp.asarray(start, jnp.uint32))
    e = _flip_sign_jnp(jnp.asarray(end, jnp.uint32))
    unb = jnp.asarray(unbounded, jnp.int32)
    mask = _scan_call(keys_t, rh31, rl31, tomb8, nv, s[None], e[None], unb,
                      qhi31, qlo31, interpret)
    return mask[0, :, :n]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def visibility_mask_batch_cached_q(keys_t, rh31, rl31, tomb8, nv, starts, ends,
                                   unbounded, read_hi, read_lo, n,
                                   interpret=False):
    """Query-batched Pallas path over a `prepare_mirror`-cached layout:
    Q distinct queries × P partitions resolved in ONE dispatch. Only the
    per-query bounds (uint32[Q, C] packed) and read revisions (uint32[Q]
    split) are converted in-graph. Returns bool[Q, P, n]."""
    qhi31, qlo31 = _split31_jnp(
        jnp.asarray(read_hi, jnp.uint32), jnp.asarray(read_lo, jnp.uint32)
    )
    s = _flip_sign_jnp(jnp.asarray(starts, jnp.uint32))
    e = _flip_sign_jnp(jnp.asarray(ends, jnp.uint32))
    unb = jnp.asarray(unbounded, jnp.int32)
    mask = _scan_call(keys_t, rh31, rl31, tomb8, nv, s, e, unb,
                      qhi31, qlo31, interpret)  # [Q, P, Npad]
    return mask[:, :, :n]


def prepare_blocks(chunks: np.ndarray, revs: np.ndarray, tomb: np.ndarray,
                   tile: int = LANE_TILE):
    """Row-major uint32 blocks -> pallas layout (padded, chunk-major)."""
    n, c = chunks.shape
    pad = (-n) % tile
    if pad:
        chunks = np.pad(chunks, ((0, pad), (0, 0)))
        revs = np.pad(revs, (0, pad))
        tomb = np.pad(tomb, (0, pad))
    keys_t = np.ascontiguousarray(flip_sign(chunks).T)
    rh31, rl31 = split_revs31(revs)
    return keys_t, rh31, rl31, tomb.astype(np.int8), n


def pack_bound_flipped(bound_chunks: np.ndarray) -> np.ndarray:
    return flip_sign(bound_chunks.reshape(1, -1)).reshape(-1)

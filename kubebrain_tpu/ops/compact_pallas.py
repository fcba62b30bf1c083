"""Pallas TPU kernel for the compaction/GC victim mask.

Same victim rule as ops.compact.victim_mask (reference: the compact branches
of the scan worker, scanner.go:445-491 + TTL derivation scanner.go:566-591),
tiled for the VPU exactly like the scan kernel (ops/scan_pallas.py): rows on
the 128-wide lane axis, chunk-major sign-flipped keys, 31-bit revision
split, reverse-tile grid with a carry.

Three verdicts per row, all needing the NEXT row of the same key:

- superseded: row and its next-newer version are both <= compact_rev;
- dead tombstone: row is the newest version <= compact_rev and a tombstone;
- TTL-expired: the whole group's newest version is <= the TTL cutoff —
  a backward broadcast from each group's last row, done with an in-tile
  log-step segmented OR (in-tile run links only; the tile's last column is
  seeded from the carried verdict of the next tile's first row, so group
  chains of ANY length propagate across tiles — one tile per grid step,
  grid steps run in order).

The carry holds the next tile's first key, its <=compact_rev flag, and its
group-expired verdict. The range restriction ([start, end) borders from the
backend's compact fences) is folded into the same kernel pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .scan_pallas import (
    LANE_TILE,
    _flip_sign_jnp,
    _lex_less,
    _split31_jnp,
)


def _kernel(nv_ref, scal_ref, start_ref, end_ref,
            keys_ref, rh_ref, rl_ref, tomb_ref, ttl_ref,
            mask_ref,
            carry_key, carry_flags,
            *, with_ttl: bool):
    # grid = (partitions, reverse tiles): the tile sweep of each partition is
    # contiguous, and its first step (tile nt-1) masks the carry via have_i
    t = pl.num_programs(1) - 1 - pl.program_id(1)  # reversed tile order

    n_valid = nv_ref[pl.program_id(0)]
    unbounded = scal_ref[0]
    chi = scal_ref[1]  # compact revision, 31-bit split
    clo = scal_ref[2]
    thi = scal_ref[3]  # TTL cutoff revision, 31-bit split
    tlo = scal_ref[4]

    keys = keys_ref[:, :]          # [C, T] int32 sign-flipped chunks
    rh = rh_ref[:, :]              # [1, T] int32 31-bit rev hi
    rl = rl_ref[:, :]
    tomb = tomb_ref[:, :] != 0     # [1, T]
    c, tile = keys.shape

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    idx = t * tile + lane
    valid = idx < n_valid
    is_last_col = lane == (tile - 1)
    have_i = ((t + 1) * tile < n_valid).astype(jnp.int32)

    le_compact = valid & ((rh < chi) | ((rh == chi) & (rl <= clo)))

    # range restriction (compact borders), same lex compare as the scan
    start = start_ref[:, :]
    end = end_ref[:, :]
    less_start = _lex_less(keys, start, keys != start, keys < start)
    less_end = _lex_less(keys, end, keys != end, keys < end)
    in_range = (~less_start) & ((unbounded != 0) | less_end)

    # same-key-as-next across the tile boundary via the carried first key
    nxt_keys = jnp.roll(keys, -1, axis=1)
    nxt_keys = jnp.where(is_last_col, carry_key[:, :], nxt_keys)
    same_next = jnp.all(keys == nxt_keys, axis=0, keepdims=True)
    same_next = same_next & (jnp.where(is_last_col, have_i, 1) != 0)

    le_next_i = jnp.roll(le_compact.astype(jnp.int32), -1, axis=1)
    le_next = jnp.where(is_last_col, carry_flags[0] * have_i, le_next_i) != 0

    superseded = le_compact & same_next & le_next
    is_last_le = le_compact & ~(same_next & le_next)
    victims = superseded | (is_last_le & tomb)

    if with_ttl:
        ttlk = ttl_ref[:, :] != 0
        # seed: each group's true last row carries the group verdict
        seed = (valid & ~same_next) & ((rh < thi) | ((rh == thi) & (rl <= tlo)))
        # the tile's last column inherits the carried verdict when its group
        # continues into the next tile (same_next at last col implies have)
        seed_i = seed.astype(jnp.int32)
        boundary = same_next & is_last_col
        seed_i = jnp.where(boundary, carry_flags[1], seed_i)
        # in-tile links only: the last column's link is the boundary seed.
        # The segmented OR runs on int32 0/1 vectors rolled along the lane
        # axis: Mosaic has no lowering for rolls of i1 vectors
        expired_i = seed_i
        run_i = (same_next & ~is_last_col).astype(jnp.int32)
        step = 1
        while step < tile:
            # wrapping rolls are safe: run windows containing the cut last
            # column are 0, so wrapped values never land
            expired_i = expired_i | (run_i & jnp.roll(expired_i, -step, axis=1))
            run_i = run_i & jnp.roll(run_i, -step, axis=1)
            step *= 2
        victims = victims | ((expired_i != 0) & ttlk & valid)
        carry_flags[1] = expired_i[0, 0]

    mask_ref[:, :] = (victims & in_range).astype(jnp.int8)

    # publish this tile's first column for the next grid step (tile t-1)
    carry_key[:, :] = keys[:, 0:1]
    carry_flags[0] = le_compact.astype(jnp.int32)[0, 0]


def _victim_call(keys_t, rh31, rl31, tomb8, ttl8, n_valid, start, end,
                 unbounded, chi31, clo31, thi31, tlo31, with_ttl, interpret):
    """THE victim ``pallas_call`` over P partitions (grid = partitions ×
    reverse tiles; explicit axis, not ``jax.vmap`` — see ops/scan_pallas.py).

    keys_t int32[P, C, N] chunk-major sign-flipped (N % LANE_TILE == 0);
    rh31/rl31 int32[P, N]; tomb8/ttl8 int8[P, N]; n_valid int32[P];
    start/end int32[C] sign-flipped bounds; scalars unbounded/compact/
    ttl-cutoff. Returns bool[P, N].
    """
    p, c, n = keys_t.shape
    assert n % LANE_TILE == 0, "pad rows to LANE_TILE"
    nt = n // LANE_TILE
    scal = jnp.stack([jnp.asarray(x, jnp.int32) for x in (
        unbounded, chi31, clo31, thi31, tlo31)])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bound = pl.BlockSpec((c, 1), lambda pi, i: (0, 0))
    row = pl.BlockSpec((None, 1, LANE_TILE), lambda pi, i: (pi, 0, nt - 1 - i))
    mask = pl.pallas_call(
        functools.partial(_kernel, with_ttl=with_ttl),
        grid=(p, nt),
        in_specs=[
            smem, smem,                 # n_valid[P]; scalars
            bound, bound,               # start / end bounds
            pl.BlockSpec((None, c, LANE_TILE),
                         lambda pi, i: (pi, 0, nt - 1 - i)),  # keys
            row, row, row, row,         # rev hi, rev lo, tombstones, ttl keys
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((p, 1, n), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.int32),                   # carried first key
            pltpu.SMEM((2,), jnp.int32),                     # le_compact, expired
        ],
        interpret=interpret,
    )(
        jnp.asarray(n_valid, jnp.int32).reshape(p), scal,
        start.reshape(c, 1), end.reshape(c, 1),
        keys_t, rh31.reshape(p, 1, n), rl31.reshape(p, 1, n),
        tomb8.reshape(p, 1, n), ttl8.reshape(p, 1, n),
    )
    return mask.reshape(p, n) != 0


@functools.partial(jax.jit, static_argnames=("with_ttl", "interpret"))
def victim_mask_pallas(keys_t, rh31, rl31, tomb8, ttl8, n_valid, start, end,
                       unbounded, chi31, clo31, thi31, tlo31,
                       with_ttl=True, interpret=False):
    """Victim mask over one partition.

    keys_t int32[C, N] chunk-major sign-flipped (N % LANE_TILE == 0);
    rh31/rl31 int32[N]; tomb8/ttl8 int8[N]; start/end int32[C] sign-flipped
    bounds; scalars n_valid/unbounded/compact/ttl-cutoff. Returns bool[N].
    """
    return _victim_call(
        keys_t[None], rh31[None], rl31[None], tomb8[None], ttl8[None],
        n_valid, start, end, unbounded, chi31, clo31, thi31, tlo31,
        with_ttl, interpret)[0]


@functools.partial(jax.jit, static_argnames=("with_ttl", "interpret"))
def victim_mask_batch_cached(keys_t, rh31, rl31, tomb8, ttl8, nv, start, end,
                             unbounded, compact_hi, compact_lo,
                             ttl_hi, ttl_lo, with_ttl=True, interpret=False):
    """Batched (all partitions in one launch) victim masks over the
    `prepare_mirror`-cached layout, mirroring engine._victim_batch's contract:
    32-bit uint revision splits in, bool[P, Npad] out (caller slices padding).

    start/end are uint32[C] packed bounds; compact/ttl revisions are 32-bit
    (hi, lo) uint32 splits, re-split to 31-bit in-graph."""
    chi31, clo31 = _split31_jnp(
        jnp.asarray(compact_hi, jnp.uint32), jnp.asarray(compact_lo, jnp.uint32)
    )
    thi31, tlo31 = _split31_jnp(
        jnp.asarray(ttl_hi, jnp.uint32), jnp.asarray(ttl_lo, jnp.uint32)
    )
    s = _flip_sign_jnp(jnp.asarray(start, jnp.uint32))
    e = _flip_sign_jnp(jnp.asarray(end, jnp.uint32))
    unb = jnp.asarray(unbounded, jnp.int32)
    return _victim_call(keys_t, rh31, rl31, tomb8, ttl8, nv, s, e, unb,
                        chi31, clo31, thi31, tlo31, with_ttl, interpret)

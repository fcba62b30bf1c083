"""The single fan-out kernel dispatch funnel (docs/watch.md).

:func:`fanout_dispatch` is the ONE place the block-batched path launches the
range-match kernel (``ops.fanout.fanout_mask_range_wmajor``) — kblint KB127
confines
``fanout_mask*`` references to this module and the legacy per-batch funnel
(``ops/fanout.py``), the way KB109 confines the scan kernels to their
assembly points. Everything above (matcher, hub, backend) works in terms of
compacted (watcher, event) index pairs and never sees the [E, W] mask.

Layout contract (mirrors the PR 7 ``_part_indices_of_mask`` discipline):

- Watcher columns arrive sharded over the mesh's first axis (``wat`` from
  the CLI); event columns are replicated — every shard matches every event
  against its own watcher slice, so the [E, W] mask only ever exists
  shard-local and is consumed in-register.
- Per shard the mask is compacted to watcher-major flat indices
  ``w_local * E + e`` scatter-free: one popcount cumsum over the flat mask,
  then a batched binary search that asks, for each of the ``size`` output
  slots, where the running count first reaches it (``_compact``). Measured
  on CPU this beats ``jnp.nonzero(size=)`` (sort-based) ~9x and a
  drop-mode scatter ~5x, and the cost is flat in match density. Output:
  real matches first in ascending order, then ``fill = Wl * E``. The host
  reads the first ``sum(shard counts)`` entries of each shard's slice — a
  transfer O(matched pairs) + O(W) counts, never O(E·W).
- ``size`` and ``mesh`` are static (two jit cache keys per (epad, W,
  size) triple); ``n_ev`` is a traced scalar so drain-depth churn within an
  E bucket never recompiles, and E-padding rows are masked out on device
  (a zero-key padding event would otherwise match every unbounded
  min_rev=0 watcher).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.fanout import fanout_mask_range_wmajor


def _wat_shard_map(f, mesh, n_wat_args: int, n_rep_args: int, n_out: int):
    """shard_map ``f`` along the mesh's first axis when it is multi-device:
    the LAST ``n_wat_args`` args shard on axis 0, the first ``n_rep_args``
    replicate, and every output shards on axis 0 (counts over W, indices
    over the per-shard slices). Single-device / no mesh: run unsharded —
    the compaction layout degenerates to one shard covering the table."""
    if mesh is None or mesh.devices.size <= 1:
        return f
    from jax.sharding import PartitionSpec as PS

    axis = mesh.axis_names[0]
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(PS(),) * n_rep_args + (PS(axis),) * n_wat_args,
        out_specs=(PS(axis),) * n_out,
        check_vma=False,
    )


@functools.partial(jax.jit, static_argnames=("size", "mesh"))
def fanout_dispatch(
    event_keys: jnp.ndarray,   # uint32[E, C] packed event keys (E-padded)
    ev_rev_hi: jnp.ndarray,    # uint32[E]
    ev_rev_lo: jnp.ndarray,    # uint32[E]
    n_ev: jnp.ndarray,         # int32 scalar: real events (rest is padding)
    w_start: jnp.ndarray,      # uint32[W, C] sharded over wat
    w_end: jnp.ndarray,        # uint32[W, C]
    w_unbounded: jnp.ndarray,  # bool[W]
    min_rev_hi: jnp.ndarray,   # uint32[W]
    min_rev_lo: jnp.ndarray,   # uint32[W]
    size: int,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Match one drain block against the whole watcher table in one launch.

    Returns ``(counts int32[W], idx int32[n_shards * size])``: per-slot
    match counts plus each shard's compacted watcher-major flat indices
    (``w_local * E + e``, ascending; first sum-of-shard-counts entries
    real, rest ``fill = Wl * E``). ``sum(shard counts) > size`` means that
    shard's indices were truncated — the caller re-dispatches with a
    bigger static ``size``.
    """
    def local(ek, ehi, elo, nev, ws, we, wu, whi, wlo):
        # watcher-major from the source: the compaction consumes the mask
        # flat in w_local * E + e order, and producing [Wl, E] directly
        # fuses with the compare (an explicit .T re-materializes [E, W])
        mask = fanout_mask_range_wmajor(ek, ehi, elo, ws, we, wu, whi, wlo)
        e = mask.shape[1]
        mask = mask & (jnp.arange(e, dtype=jnp.int32) < nev)[None, :]
        counts = jnp.sum(mask, axis=1, dtype=jnp.int32)               # [Wl]
        # watcher-major flat indices: w_local * E + e
        return counts, _compact(mask.reshape(-1), size)

    f = _wat_shard_map(local, mesh, n_wat_args=5, n_rep_args=4, n_out=2)
    return f(event_keys, ev_rev_hi, ev_rev_lo, jnp.asarray(n_ev, jnp.int32),
             w_start, w_end, w_unbounded, min_rev_hi, min_rev_lo)


def _compact(flat: jnp.ndarray, size: int) -> jnp.ndarray:
    """Compact a flat bool mask to its ``True`` indices: ascending, first
    ``popcount(flat)`` entries real, ``fill = len(flat)``, truncated at
    ``size`` (the caller detects truncation from the exact counts and
    re-dispatches bigger).

    Scatter-free: the j-th match's flat index is the first position whose
    running popcount reaches j+1, so one cumsum plus a batched binary
    search over the ``size`` output slots replaces any scatter of the n
    candidate positions. On XLA CPU a 5M-element drop-mode scatter costs
    ~0.3s where cumsum + searchsorted costs ~0.07s, and unlike
    ``jnp.nonzero(size=)`` (sort-based, ~9x slower) the cost is flat in
    the match density — dense broad-watcher populations that grow ``size``
    toward n pay the same single pass. Queries past the total count find
    no position and return n: the fill value, by construction.
    Shard-local under shard_map."""
    csum = jnp.cumsum(flat.astype(jnp.int32))
    q = jnp.arange(1, size + 1, dtype=jnp.int32)
    return jnp.searchsorted(csum, q).astype(jnp.int32)

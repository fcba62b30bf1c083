"""The delta merge's one primitive against the code it replaced.

``blocks._merge_sorted_blocks`` places each sorted block into the merged
order by binary search and ``keyops.gather_arena`` moves the values as
runs of consecutive source rows. The ORACLE below is the implementation
they replaced, kept verbatim (one stable argsort over the concatenation,
one int64 index per value byte): every output array must be equal, dtype
included, so the successor ``Mirror`` of a merge is byte-identical.
"""

from __future__ import annotations

import tracemalloc

import numpy as np
import pytest

from kubebrain_tpu.backend.common import TOMBSTONE
from kubebrain_tpu.ops import keys as keyops
from kubebrain_tpu.storage.tpu import blocks

WIDTH = 16


def oracle_gather_arena(arena, offsets, perm):
    offsets = offsets.astype(np.int64)
    lens = (offsets[1:] - offsets[:-1])[perm]
    new_offsets = np.zeros(len(perm) + 1, dtype=np.int64)
    np.cumsum(lens, out=new_offsets[1:])
    total = int(new_offsets[-1])
    if total == 0:
        return np.zeros(0, dtype=np.uint8), new_offsets.astype(np.uint64)
    starts = offsets[:-1][perm]
    idx = np.arange(total, dtype=np.int64)
    idx += np.repeat(starts - new_offsets[:-1], lens)
    return arena[idx], new_offsets.astype(np.uint64)


def oracle_merge_sorted_blocks(blks):
    ncols = len(blks[0]) - 3
    keys_u8 = np.concatenate([b[0] for b in blks])
    cols = [np.concatenate([b[1 + c] for b in blks]) for c in range(ncols)]
    revs = cols[1]
    n, w = keys_u8.shape
    rev_be = revs[:, None].astype(">u8").view(np.uint8).reshape(n, 8)
    sort_rows = np.ascontiguousarray(np.concatenate([keys_u8, rev_be], axis=1))
    void = sort_rows.view([("v", f"V{w + 8}")]).reshape(n)
    perm = np.argsort(void, kind="stable")
    arena = np.concatenate([b[-2] for b in blks])
    bases = np.cumsum([0] + [len(b[-2]) for b in blks[:-1]]).astype(np.int64)
    offsets = np.concatenate(
        [b[-1].astype(np.int64)[:-1] + base for b, base in zip(blks, bases)]
        + [np.array([len(arena)], dtype=np.int64)]
    ).astype(np.uint64)
    new_arena, new_offsets = oracle_gather_arena(arena, offsets, perm)
    return (keys_u8[perm], *(c[perm] for c in cols), new_arena, new_offsets)


def assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


def sorted_block(rng, rows, stored):
    """(key, rev, value) rows → a sorted sextuple, or a septuple with a
    TTL column when ``stored``."""
    rows = sorted(rows, key=lambda r: (r[0].ljust(WIDTH, b"\0"), r[1]))
    k, lens, r, t, arena, off = blocks.rows_to_arrays(rows, WIDTH)
    if stored:
        return (k, lens, r, t, rng.random(len(rows)) < 0.3, arena, off)
    return (k, lens, r, t, arena, off)


def random_rows(rng, n, *, lo=b"a", hi=b"z", max_val=40, rev_hi=50):
    """Few distinct keys and revisions, so chains and ties are common;
    values of 0..max_val bytes, every eighth a tombstone."""
    out = []
    for i in range(n):
        key = bytes(rng.integers(lo[0], hi[0] + 1, rng.integers(1, 4),
                                 dtype=np.uint8))
        val = (TOMBSTONE if i % 8 == 7 else
               rng.integers(0, 256, rng.integers(0, max_val + 1),
                            dtype=np.uint8).tobytes())
        out.append((key, int(rng.integers(1, rev_hi)), val))
    return out


def case_blocks(case, rng):
    part = random_rows(rng, 300)
    if case == "random":
        return [part, random_rows(rng, 40)]
    if case == "empty_values":
        return [[(k, r, b"") for k, r, _ in part],
                [(k, r, b"") for k, r, _ in random_rows(rng, 40)]]
    if case == "all_tombstones":
        return [part, [(k, r, TOMBSTONE) for k, r, _ in random_rows(rng, 40)]]
    if case == "same_keys_new_revisions":
        return [part, [(k, r + 100, b"v2") for k, r, _ in part[::7]]]
    if case == "same_key_and_revision":
        # the mirror's row must come first (checked by value below)
        return [part, [(k, r, b"from-delta") for k, r, _ in part[::5]]]
    if case == "empty_delta":
        return [part, []]
    if case == "empty_partition":
        return [[], random_rows(rng, 40)]
    if case == "delta_below":
        return [random_rows(rng, 300, lo=b"m"), random_rows(rng, 40, hi=b"c")]
    if case == "delta_above":
        return [random_rows(rng, 300, hi=b"m"), random_rows(rng, 40, lo=b"x")]
    if case == "delta_inside":
        return [part, random_rows(rng, 40, lo=b"k", hi=b"m")]
    if case == "one_block":
        return [part]
    if case == "eight_blocks":
        return [random_rows(rng, n) for n in (64, 64, 1, 64, 0, 64, 30, 64)]
    raise AssertionError(case)


CASES = ["random", "empty_values", "all_tombstones", "same_keys_new_revisions",
         "same_key_and_revision", "empty_delta", "empty_partition",
         "delta_below", "delta_above", "delta_inside", "one_block",
         "eight_blocks"]


@pytest.mark.parametrize("stored", [False, True], ids=["raw6", "stored7"])
@pytest.mark.parametrize("case", CASES)
def test_merge_matches_the_argsort_it_replaced(case, stored):
    rng = np.random.default_rng(CASES.index(case) * 2 + stored)
    blks = [sorted_block(rng, rows, stored) for rows in case_blocks(case, rng)]
    want = oracle_merge_sorted_blocks(blks)
    assert_same(blocks._merge_sorted_blocks(blks), want)
    if stored:
        assert_same(blocks.merge_sorted_stored(blks), want)
    elif len(blks) == 2:
        assert_same(blocks.merge_sorted_arrays(*blks), want)
    if case == "same_key_and_revision":
        # within one (key, revision) no mirror row follows a delta row
        keys, _lens, revs, *_rest, arena, off = want
        from_delta = [arena[int(off[i]):int(off[i + 1])].tobytes()
                      == b"from-delta" for i in range(len(keys))]
        pairs = [i for i in range(1, len(keys)) if from_delta[i - 1]
                 and (keys[i] == keys[i - 1]).all() and revs[i] == revs[i - 1]]
        assert sum(from_delta) == len(blks[1][0]) > 0
        assert all(from_delta[i] for i in pairs)


@pytest.mark.parametrize("stored", [False, True], ids=["raw6", "stored7"])
def test_sort_arrays_matches_the_argsort_it_replaced(stored):
    """Commit-order rows (what the delta seals) sort as they did when the
    engine pushed them through the merge with an empty first block."""
    rng = np.random.default_rng(77 + stored)
    rows = random_rows(rng, 200)
    k, lens, r, t, arena, off = blocks.rows_to_arrays(rows, WIDTH)
    blk = ((k, lens, r, t, rng.random(200) < 0.3, arena, off) if stored
           else (k, lens, r, t, arena, off))
    empty = tuple(a[:0] for a in blk[:-1]) + (np.zeros(1, np.uint64),)
    assert_same(blocks.sort_arrays(blk),
                oracle_merge_sorted_blocks([empty, blk]))


def _arena(rng, n, max_val=40):
    lens = rng.integers(0, max_val + 1, n)
    lens[::9] = 0
    off = np.zeros(n + 1, dtype=np.uint64)
    off[1:] = np.cumsum(lens).astype(np.uint64)
    # slack past the last row, as a partition's arena slice may have
    return rng.integers(0, 256, int(off[-1]) + 13, dtype=np.uint8), off


PERMS = {
    "identity": lambda rng, n: np.arange(n),
    "reversed": lambda rng, n: np.arange(n)[::-1],
    "survivors": lambda rng, n: np.flatnonzero(rng.random(n) < 0.7),
    "random": lambda rng, n: rng.permutation(n),
    "repeats": lambda rng, n: rng.integers(0, n, 2 * n),
    "merge_like": lambda rng, n: np.insert(
        np.arange(n - 20), np.sort(rng.integers(0, n - 19, 20)),
        np.arange(n - 20, n)),
    "empty": lambda rng, n: np.zeros(0, dtype=np.int64),
    "int32_index": lambda rng, n: rng.permutation(n).astype(np.int32),
}


@pytest.mark.parametrize("budget", [1 << 20, 64], ids=["under_gil", "mixed"])
@pytest.mark.parametrize("name", list(PERMS))
def test_gather_arena_matches_the_byte_index_it_replaced(name, budget,
                                                         monkeypatch):
    """``budget`` 64 sends about every third run through the copy that
    gives the GIL up; at the default every run here stays under it."""
    monkeypatch.setattr(keyops, "_GIL_BUDGET_BYTES", budget)
    rng = np.random.default_rng(list(PERMS).index(name))
    arena, off = _arena(rng, 257)
    perm = PERMS[name](rng, 257)
    assert_same(keyops.gather_arena(arena, off, perm),
                oracle_gather_arena(arena, off, perm))


def test_gather_arena_of_empty_values_only():
    off = np.zeros(6, dtype=np.uint64)
    assert_same(keyops.gather_arena(np.zeros(0, np.uint8), off, np.arange(5)),
                oracle_gather_arena(np.zeros(0, np.uint8), off, np.arange(5)))


def test_merge_partitions_stored_builds_no_index_per_value_byte():
    """A count, not a timing: one merge of 20,000 rows x 512 B + 1,024
    delta rows peaks under 4x the arena's bytes. The argsort-and-byte-index
    merge peaked over 8x (an int64 per value byte, twice)."""
    rng = np.random.default_rng(5)
    n, dn, vlen = 20_000, 1_024, 512

    def rows(count, rev0, tag):
        idx = rng.choice(10 * n, count, replace=False)
        return [(b"/registry/pods/ns-%02d/%s-%06d" % (i % 25, tag, i),
                 rev0 + j, rng.bytes(vlen)) for j, i in enumerate(idx)]

    base = sorted(rows(n, 1, b"pod"))
    mirror = blocks.build_mirror_from_arrays(
        *blocks.rows_to_arrays(base, 64), None, 64, snapshot_ts=n,
        encode=False)
    k, lens, r, t, arena, off = blocks.sort_arrays(
        blocks.rows_to_arrays(rows(dn, n + 1, b"new"), 64))
    delta = (k, lens, r, t, blocks.compute_ttl_flags(k, lens), arena, off)
    arena_bytes = n * vlen

    tracemalloc.start()
    try:
        merged = blocks.merge_partitions_stored(mirror, delta, None, n + dn)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert merged.rows == n + dn
    assert len(merged.val_arena[0]) == (n + dn) * vlen
    assert peak < 4 * arena_bytes, (peak, arena_bytes)

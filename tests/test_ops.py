"""Kernel unit tests: packing, lex compare, visibility, fan-out, compaction —
differential-tested against Python oracles on random MVCC datasets."""

import numpy as np
import pytest

import jax.numpy as jnp

from kubebrain_tpu.ops import keys as keyops
from kubebrain_tpu.ops.compact import compact_block, victim_mask
from kubebrain_tpu.ops.fanout import fanout_mask
from kubebrain_tpu.ops import scan
from kubebrain_tpu.ops.scan import lex_less, visibility_mask


def test_pack_roundtrip():
    ks = [b"/registry/pods/a", b"", b"x" * 128, b"ab"]
    chunks, lens = keyops.pack_keys(ks)
    assert chunks.shape == (4, 32) and list(lens) == [16, 0, 128, 2]
    assert keyops.chunks_to_bytes(chunks, lens) == ks
    with pytest.raises(ValueError):
        keyops.pack_keys([b"y" * 129])


def test_pack_order_preserving():
    rng = np.random.RandomState(0)
    ks = sorted(
        bytes(rng.randint(1, 255, rng.randint(1, 60), dtype=np.uint8)) for _ in range(200)
    )
    chunks, _ = keyops.pack_keys(ks)
    # tuple order of packed chunks == lexicographic byte order
    as_tuples = [tuple(int(x) for x in row) for row in chunks]
    assert as_tuples == sorted(as_tuples)


def test_split_revs():
    revs = np.array([0, 1, 2**31, 2**32 + 5, 2**53], dtype=np.uint64)
    hi, lo = keyops.split_revs(revs)
    assert (keyops.join_revs(hi, lo) == revs).all()


def test_lex_less_matches_python():
    rng = np.random.RandomState(1)
    ks = [bytes(rng.randint(1, 255, rng.randint(1, 40), dtype=np.uint8)) for _ in range(100)]
    bound = ks[50]
    chunks, _ = keyops.pack_keys(ks)
    got = np.asarray(lex_less(jnp.asarray(chunks), jnp.asarray(keyops.pack_one(bound))))
    want = np.array([k < bound for k in ks])
    assert (got == want).all()


def _oracle_visible(rows, start, end, read_rev):
    """rows: sorted (key, rev, tomb). Returns set of visible (key, rev)."""
    best = {}
    for k, rev, tomb in rows:
        if k < start or (end and k >= end):
            continue
        if rev <= read_rev:
            best[k] = (rev, tomb)
    return {(k, rv) for k, (rv, tomb) in best.items() if not tomb}


def _random_dataset(seed, n_keys=60, max_revs=6):
    rng = np.random.RandomState(seed)
    keys = sorted(
        {b"/reg/" + bytes(rng.randint(97, 123, rng.randint(1, 12), dtype=np.uint8)) for _ in range(n_keys)}
    )
    rows = []
    rev = 0
    per_key = {k: [] for k in keys}
    order = [k for k in keys for _ in range(rng.randint(1, max_revs))]
    rng.shuffle(order)
    for k in order:
        rev += 1
        tomb = rng.rand() < 0.2
        per_key[k].append((rev, tomb))
    for k in keys:
        for r, t in per_key[k]:
            rows.append((k, r, t))
    rows.sort(key=lambda x: (x[0], x[1]))
    return rows, rev


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_visibility_mask_vs_oracle(seed):
    rows, max_rev = _random_dataset(seed)
    chunks, _ = keyops.pack_keys([r[0] for r in rows])
    hi, lo = keyops.split_revs(np.array([r[1] for r in rows], dtype=np.uint64))
    tomb = np.array([r[2] for r in rows])
    n = len(rows)
    for read_rev in [1, max_rev // 2, max_rev]:
        for start, end in [(b"", b""), (b"/reg/c", b"/reg/p"), (b"/reg/zz", b"")]:
            mask = np.asarray(
                visibility_mask(
                    jnp.asarray(chunks), jnp.asarray(hi), jnp.asarray(lo),
                    jnp.asarray(tomb), jnp.asarray(np.int32(n)),
                    jnp.asarray(keyops.pack_one(start)), jnp.asarray(keyops.pack_one(end)),
                    jnp.asarray(not end), *[jnp.asarray(x[0]) for x in keyops.split_revs(np.array([read_rev], dtype=np.uint64))],
                )
            )
            got = {(rows[i][0], rows[i][1]) for i in np.nonzero(mask)[0]}
            want = _oracle_visible(rows, start, end, read_rev)
            assert got == want, f"seed={seed} rev={read_rev} range=({start},{end})"


@pytest.mark.parametrize("vmap_rows", [1 << 22, 0])
def test_visibility_mask_queries_both_forms_match_single(monkeypatch, vmap_rows):
    """The query axis — vmapped on a small block, mapped sequentially on one
    too large for the vmapped temporaries — is Q single-query masks."""
    monkeypatch.setattr(scan, "_VMAP_QUERY_ROWS", vmap_rows)
    rows, max_rev = _random_dataset(3)
    chunks, _ = keyops.pack_keys([r[0] for r in rows])
    hi, lo = keyops.split_revs(np.array([r[1] for r in rows], dtype=np.uint64))
    block = [jnp.asarray(x) for x in (
        chunks, hi, lo, np.array([r[2] for r in rows]), np.int32(len(rows)))]
    queries = [(b"", b"", max_rev), (b"/reg/c", b"/reg/p", max_rev // 2),
               (b"/reg/zz", b"", 1), (b"/reg/a", b"/reg/m", max_rev)]
    qhi, qlo = keyops.split_revs(np.array([q[2] for q in queries], dtype=np.uint64))
    starts = jnp.asarray(np.stack([keyops.pack_one(q[0]) for q in queries]))
    ends = jnp.asarray(np.stack([keyops.pack_one(q[1]) for q in queries]))
    unb = jnp.asarray(np.array([not q[1] for q in queries]))
    got = np.asarray(scan.visibility_mask_queries(
        *block, starts, ends, unb, jnp.asarray(qhi), jnp.asarray(qlo)))
    for j in range(len(queries)):
        want = np.asarray(visibility_mask(
            *block, starts[j], ends[j], unb[j], qhi[j], qlo[j]))
        assert (got[j] == want).all(), f"query {j}"


def test_visibility_padding_rows_excluded():
    rows = [(b"/a", 1, False), (b"/b", 2, False)]
    chunks, _ = keyops.pack_keys([r[0] for r in rows] + [b"", b""])
    hi, lo = keyops.split_revs(np.array([1, 2, 0, 0], dtype=np.uint64))
    tomb = np.zeros(4, dtype=bool)
    mask = np.asarray(
        visibility_mask(
            jnp.asarray(chunks), jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(tomb),
            jnp.asarray(np.int32(2)),
            jnp.asarray(keyops.pack_one(b"")), jnp.asarray(keyops.pack_one(b"")),
            jnp.asarray(True),
            *[jnp.asarray(x[0]) for x in keyops.split_revs(np.array([5], dtype=np.uint64))],
        )
    )
    assert mask.tolist() == [True, True, False, False]


def test_fanout_mask():
    events = [b"/registry/pods/default/a", b"/registry/services/x", b"/registry/pods/kube/b"]
    ek, _ = keyops.pack_keys(events)
    ehi, elo = keyops.split_revs(np.array([10, 11, 12], dtype=np.uint64))
    prefixes = [b"/registry/pods/", b"/registry/", b"/registry/pods/kube"]
    min_revs = [0, 11, 0]
    pc, pm = keyops.chunk_prefix_masks(prefixes)
    whi, wlo = keyops.split_revs(np.array(min_revs, dtype=np.uint64))
    mask = np.asarray(
        fanout_mask(jnp.asarray(ek), jnp.asarray(ehi), jnp.asarray(elo),
                    jnp.asarray(pc), jnp.asarray(pm), jnp.asarray(whi), jnp.asarray(wlo))
    )
    assert mask.tolist() == [
        [True, False, False],   # ev0 rev10: pods✓, registry(minrev11)✗, kube✗
        [False, True, False],   # ev1 rev11: services
        [True, True, True],     # ev2 rev12: all match
    ]


def test_victim_mask_and_compact_block():
    # key /a: revs 1,3 (3 live); /b: rev 2 tombstone; /events/e: revs 4,5
    rows = [
        (b"/a", 1, False),
        (b"/a", 3, False),
        (b"/b", 2, True),
        (b"/events/e", 4, False),
        (b"/events/e", 5, False),
    ]
    chunks, _ = keyops.pack_keys([r[0] for r in rows])
    hi, lo = keyops.split_revs(np.array([r[1] for r in rows], dtype=np.uint64))
    tomb = np.array([r[2] for r in rows])
    ttl = np.array([r[0].startswith(b"/events/") for r in rows])
    n = jnp.asarray(np.int32(len(rows)))

    def run(compact_rev, ttl_cutoff):
        chi, clo = keyops.split_revs(np.array([compact_rev], dtype=np.uint64))
        thi, tlo = keyops.split_revs(np.array([ttl_cutoff], dtype=np.uint64))
        return np.asarray(
            victim_mask(jnp.asarray(chunks), jnp.asarray(hi), jnp.asarray(lo),
                        jnp.asarray(tomb), jnp.asarray(ttl), n,
                        jnp.asarray(chi[0]), jnp.asarray(clo[0]),
                        jnp.asarray(thi[0]), jnp.asarray(tlo[0]))
        )

    # compact@3, no TTL: /a rev1 superseded (rev3 survives as last <=3);
    # /b tombstone dead; /events keep
    assert run(3, 0).tolist() == [True, False, True, False, False]
    # compact@5 + TTL cutoff 5: /events group fully expired on top
    assert run(5, 5).tolist() == [True, False, True, True, True]
    # compact@1: nothing superseded (rev1 is last <=1 for /a, live)
    assert run(1, 0).tolist() == [False, False, False, False, False]

    mask = jnp.asarray(run(3, 0))
    k2, h2, l2, t2, cnt = compact_block(jnp.asarray(chunks), jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(tomb), mask)
    assert int(cnt) == 3  # /a@3, /events@4, /events@5
    kept = keyops.chunks_to_bytes(np.asarray(k2)[: int(cnt)], np.array([2, 9, 9]))
    assert kept == [b"/a", b"/events/e", b"/events/e"]

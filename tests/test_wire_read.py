"""``kb_wire_read``'s key decode against ``Mirror.decoded_keys``.

The wire read decodes each visible row's key in C as it writes the reply
(``wire_key`` in native/kbstore.cc); every other caller decodes through
``Mirror.decoded_keys`` → ``KeyEncoding.decode_rows`` / ``chunks_to_u8``
(kblint KB116). The two are twins, and this differential test is what
holds them together: the keys of a reply parsed back from the wire are the
keys the Python funnel gives for the same rows — over random dictionaries
built from kube-style keys, the edge shapes of a dictionary, and raw
mirrors — and what the native call reads through raw pointers is checked
before it is read.
"""

import numpy as np
import pytest

from kubebrain_tpu.ops import keys as keyops
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.storage import native
from kubebrain_tpu.storage.errors import StorageError
from kubebrain_tpu.storage.tpu.blocks import Mirror
from kubebrain_tpu.storage.tpu.encode import KeyEncoding, build_encoding


def host_mirror(stored_u8: np.ndarray, lens: np.ndarray, key_width: int,
                encoding: KeyEncoding | None) -> Mirror:
    """One partition of stored keys (encoded or raw bytes, zero-padded to
    whole chunks) with a value and a revision a row, host columns alone."""
    n = len(stored_u8)
    values = [b"v%d" % i * (i % 3) for i in range(n)]
    offsets = np.zeros(n + 1, dtype=np.uint64)
    offsets[1:] = np.cumsum([len(v) for v in values])
    return Mirror(
        keys_dev=None, rh_dev=None, rl_dev=None, tomb_dev=None, ttl_dev=None,
        n_valid_dev=None,
        keys_host=keyops.bytes_to_chunks(stored_u8)[None],
        lens_host=np.asarray(lens, dtype=np.int32)[None],
        revs_host=np.arange(1, n + 1, dtype=np.uint64)[None] * 1000,
        tomb_host=np.zeros((1, n), dtype=bool),
        n_valid=np.array([n], dtype=np.int32),
        val_arena=[np.frombuffer(b"".join(values), dtype=np.uint8)],
        val_offsets=[offsets], snapshot_ts=0, max_rev=n * 1000,
        key_width=key_width, encoding=encoding)


def wire_keys(mirror: Mirror, rows: np.ndarray) -> list[bytes]:
    """The keys of a wire read of ``rows`` (ascending) of partition 0."""
    idx = np.ascontiguousarray(rows, dtype=np.int32)[None]
    blob, n, more = native.wire_read(
        mirror.wire_cols, mirror.val_offsets, mirror.keys_host.shape[2],
        mirror.raw_key_width,
        None if mirror.encoding is None else mirror.encoding.wire_table,
        np.array([len(rows)]), idx, {})
    kvs = rpc_pb2.RangeResponse.FromString(blob).kvs
    assert (n, more) == (len(rows), False) and len(kvs) == n
    for kv, r in zip(kvs, rows):
        assert kv.value == mirror.value(0, int(r))
        assert kv.mod_revision == kv.create_revision == int(mirror.revs_host[0, r])
    return [kv.key for kv in kvs]


def funnel_keys(mirror: Mirror, rows: np.ndarray) -> list[bytes]:
    k_u8, k_lens = mirror.decoded_keys(0, rows)
    return [k_u8[i, : int(k_lens[i])].tobytes() for i in range(len(rows))]


def pad_rows(keys: list[bytes], width: int) -> tuple[np.ndarray, np.ndarray]:
    out = np.zeros((len(keys), width), dtype=np.uint8)
    for i, k in enumerate(keys):
        out[i, : len(k)] = np.frombuffer(k, dtype=np.uint8)
    return out, np.array([len(k) for k in keys], dtype=np.int32)


def kube_keys(rng: np.random.Generator, n: int, width: int) -> list[bytes]:
    """Sorted, distinct, NUL-free keys with the shared prefixes of a
    registry, a few short ones and one as wide as the width allows."""
    kinds = [b"pods", b"leases", b"events", b"configmaps"]
    keys = {b"/", b"/registry", b"z" * width}
    while len(keys) < n:
        name = bytes(rng.integers(97, 123, size=int(rng.integers(1, 30))).tolist())
        keys.add((b"/registry/%s/ns-%02d/%s" % (
            kinds[int(rng.integers(len(kinds)))], int(rng.integers(12)), name))[:width])
    return sorted(keys)


@pytest.mark.parametrize("seed", range(6))
def test_the_c_decode_is_decoded_keys_on_random_dictionaries(seed):
    rng = np.random.default_rng(seed)
    width = (64, 128)[seed % 2]
    keys = kube_keys(rng, 400, width)
    raw_u8, raw_lens = pad_rows(keys, width)
    encoding = build_encoding(raw_u8, raw_lens, raw_width=width)
    enc_u8, sfx_lens = encoding.encode_keys(raw_u8, raw_lens)
    mirror = host_mirror(enc_u8, sfx_lens, width, encoding)
    for rows in (np.arange(len(keys)),
                 np.sort(rng.choice(len(keys), size=57, replace=False)),
                 np.array([0]), np.array([len(keys) - 1])):
        got = wire_keys(mirror, rows)
        assert got == funnel_keys(mirror, rows) == [keys[r] for r in rows]


@pytest.mark.parametrize("seed", range(3))
def test_the_c_decode_is_decoded_keys_on_raw_mirrors(seed):
    """No dictionary (``--key-encoding raw``): the chunks are the key, cut
    at its length — the empty key and one as wide as the row among them."""
    rng = np.random.default_rng(100 + seed)
    width = (32, 64, 128)[seed]
    keys = sorted({b"", b"k" * width, *kube_keys(rng, 60, width)})
    raw_u8, raw_lens = pad_rows(keys, width)
    mirror = host_mirror(raw_u8, raw_lens, width, None)
    rows = np.arange(len(keys))
    assert wire_keys(mirror, rows) == funnel_keys(mirror, rows) == keys


#: name → (strips, suffix_width, raw_width, rows as (code, suffix))
EDGE_SHAPES = {
    "no_strip": ([b"", b"/a/"], 8, 16,
                 [(0, b""), (0, b"x"), (0, b"zzzzzzzz"), (1, b"q")]),
    "suffix_at_the_full_width": ([b"/r/"], 8, 16,
                                 [(0, b"12345678"), (0, b"1234567"), (0, b"")]),
    "strip_leaves_less_than_the_suffix_width": (
        [b"/registry/pods/"], 8, 16, [(0, b"a"), (0, b"")]),
    "strip_as_wide_as_the_key": ([b"/0123456789abcde", b"/x/"], 4, 16,
                                 [(0, b""), (1, b"tail")]),
    "the_longest_key": ([b"/registry/", b"/s/"], 24, 32,
                        [(0, b"a" * 22), (1, b"b" * 24), (1, b"c")]),
    "one_code_chunk_no_suffix": ([b"/only", b"/strips"], 0, 8,
                                 [(0, b""), (1, b"")]),
    "a_suffix_length_past_the_raw_width": (
        # what no mirror holds (a key of 18 bytes in a width of 16): both
        # decodes cut it at the raw width, neither reads past its row
        [b"/registry/pods/"], 8, 16, [(0, b"abc")]),
}


@pytest.mark.parametrize("shape", list(EDGE_SHAPES))
def test_the_c_decode_is_decoded_keys_at_the_edges_of_a_dictionary(shape):
    strips, suffix_width, raw_width, rows = EDGE_SHAPES[shape]
    # boundaries only route ENcoding; a decode reads strips by code alone
    encoding = KeyEncoding(
        boundaries=[b"\xff" * (j + 1) for j in range(len(strips) - 1)],
        strips=strips, suffix_width=suffix_width, raw_width=raw_width)
    enc_u8 = np.zeros((len(rows), encoding.width), dtype=np.uint8)
    for i, (code, sfx) in enumerate(rows):
        enc_u8[i, 3] = code
        enc_u8[i, 4 : 4 + len(sfx)] = np.frombuffer(sfx, dtype=np.uint8)
    lens = [len(sfx) for _c, sfx in rows]
    want = [strips[code] + sfx for code, sfx in rows]
    if shape == "a_suffix_length_past_the_raw_width":
        # the stored length claims 8 suffix bytes where 1 fits the raw width
        lens, want = [8], [(strips[0] + b"abc")[:raw_width]]
    mirror = host_mirror(enc_u8, lens, raw_width, encoding)
    idx = np.arange(len(rows))
    assert wire_keys(mirror, idx) == funnel_keys(mirror, idx) == want


def test_a_code_the_dictionary_lacks_and_a_row_past_the_arrays_are_refused():
    """What the call reads through raw pointers and cannot be checked
    before it — a row index, a row's key code — is checked IN it: the read
    raises, nothing is written and nothing past an array is read."""
    encoding = KeyEncoding(boundaries=[b"m"], strips=[b"/a/", b"/n/"],
                           suffix_width=4, raw_width=16)
    enc_u8 = np.zeros((3, encoding.width), dtype=np.uint8)
    enc_u8[:, 3] = (0, 1, 2)  # the third row's code: past the dictionary
    mirror = host_mirror(enc_u8, [0, 0, 0], 16, encoding)
    assert wire_keys(mirror, np.array([0, 1])) == [b"/a/", b"/n/"]
    with pytest.raises(StorageError, match="outside the mirror's arrays"):
        wire_keys(mirror, np.array([0, 2]))
    with pytest.raises(StorageError, match="outside the mirror's arrays"):
        wire_keys(mirror, np.array([1, 3]))  # the partition has three rows
    with pytest.raises(StorageError, match="outside the mirror's arrays"):
        wire_keys(mirror, np.array([-1]))


def _columns():
    m = host_mirror(*pad_rows([b"/a", b"/b"], 8), 8, None)
    return [m.keys_host, m.lens_host, m.revs_host, m.val_arena, m.val_offsets]


@pytest.mark.parametrize("spoil", ["keys_uint64", "lens_int64", "revs_strided",
                                   "offsets_int64", "arena_missing",
                                   "lens_of_another_shape"])
def test_mirror_columns_of_the_wrong_layout_are_refused_not_read(spoil):
    """``kb_wire_read`` reads a mirror's columns through raw pointers: a
    column whose dtype, stride or shape is off raises where the table of
    their addresses is made (under ``python -O`` too), never in C."""
    cols = _columns()
    assert native.wire_columns(*cols).shape == (1, 6)
    if spoil == "keys_uint64":
        cols[0] = cols[0].astype(np.uint64)
    elif spoil == "lens_int64":
        cols[1] = cols[1].astype(np.int64)
    elif spoil == "revs_strided":
        cols[2] = np.asfortranarray(np.repeat(cols[2], 2, axis=0))[:1]
    elif spoil == "offsets_int64":
        cols[4] = [o.astype(np.int64) for o in cols[4]]
    elif spoil == "arena_missing":
        cols[3] = []
    else:
        cols[1] = cols[1][:, :1]
    with pytest.raises(ValueError, match="wire columns"):
        native.wire_columns(*cols)


@pytest.mark.parametrize("spoil", ["rows_int64", "rows_strided", "count_past_the_rows",
                                   "counts_of_another_length"])
def test_row_indices_of_the_wrong_layout_are_refused_not_read(spoil):
    m = host_mirror(*pad_rows([b"/a", b"/b", b"/c"], 8), 8, None)
    counts, rows = np.array([2]), np.array([[0, 2]], dtype=np.int32)
    if spoil == "rows_int64":
        rows = rows.astype(np.int64)
    elif spoil == "rows_strided":
        rows = np.array([[0, 9, 2, 9]], dtype=np.int32)[:, ::2]
    elif spoil == "count_past_the_rows":
        counts = np.array([3])
    else:
        counts = np.array([1, 1])
    with pytest.raises(ValueError, match="wire read"):
        native.wire_read(m.wire_cols, m.val_offsets, 2, 8, None, counts, rows, {})


def test_a_buffer_sized_short_is_never_written_past_and_the_read_made_again(monkeypatch):
    """The reply's buffer is sized from what the rows can need at most; were
    that ever short, the call answers the size and writes nothing, and the
    read is made again with it — the same bytes."""
    m = host_mirror(*pad_rows([b"/a", b"/b", b"/c"], 8), 8, None)
    counts, rows = np.array([3]), np.arange(3, dtype=np.int32)[None]
    overlay = {b"/b": (9000, b"overlaid" * 8), b"/bb": None, b"/d": (9001, b"")}
    want = native.wire_read(m.wire_cols, m.val_offsets, 2, 8, None, counts, rows,
                            overlay)
    assert want[1:] == (4, False)
    calls = []
    real = native.load_lib().kb_wire_read

    def spy(*args):
        calls.append(args[13])  # out_cap
        return real(*args)

    monkeypatch.setattr(native._lib, "kb_wire_read", spy, raising=False)
    monkeypatch.setattr(native, "WIRE_ROW_OVERHEAD", 0)  # size it short
    assert native.wire_read(m.wire_cols, m.val_offsets, 2, 0, None, counts, rows,
                            overlay) == want
    assert len(calls) == 2 and calls[0] < len(want[0]) == calls[1]

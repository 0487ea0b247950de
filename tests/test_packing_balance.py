"""pack_sample balances a micro-batch's sequences over every row the mesh needs.

Under batch sharding the row count is FFD's, rounded up to the mesh's
batch-sharding degree.  The parent padded that count with EMPTY rows, so a
micro-batch that FFD-packs into one row put 8,192 tokens on one chip and
zeros on the other three.  Now the sequences are spread over all the rows
by load (`packing._rows_over_mesh`).  The invariants, each a test below
over the same cases (multiples 1, 2, 4, 8 x three length sets x with and
without shard blocks):

1. every sequence lands in exactly one row, whole;
2. `seq_map` / `RowPack.unpack` return outputs in the sample's packed order;
3. `n_rows % n_rows_multiple == 0`, and no row's load exceeds the cap;
4. the grid `n_rows x row_len` is never larger than the parent's;
5. where the parent appended no empty row, the RowPack is byte-for-byte
   the parent's (pinned below as literals, computed at commit c857e01);
6. the layout depends on nothing but the lengths, the blocks and the
   multiple: two calls, and two members holding different data, agree.
"""

import hashlib

import numpy as np
import pytest

from areal_tpu.api.data_api import SequenceSample
from areal_tpu.base import datapack
from areal_tpu.engines import packing

KEY = "packed_input_ids"

# name -> (sequence lengths, max_tokens_per_row).  `uniform` is one
# micro-batch of the four-chip cell (20 responses of 354-414 tokens under a
# cap of 8,192: FFD packs ONE row); `lognormal` FFD-packs four rows;
# `giant` two, one of them the giant's alone.
LENGTH_SETS = {
    "uniform": (
        [387, 409, 384, 358, 388, 395, 358, 382, 375, 395,
         396, 356, 365, 357, 400, 364, 408, 413, 390, 372],
        8192,
    ),
    "lognormal": (
        [265, 167, 39, 108, 91, 15, 103, 40, 52, 39, 45, 67,
         69, 18, 51, 21, 23, 48, 126, 106, 39, 36, 33, 71],
        512,
    ),
    "giant": (
        [900, 30, 32, 51, 42, 60, 47, 48, 44, 19, 40, 34, 40, 33, 21, 27],
        1024,
    ),
}
MULTIPLES = (1, 2, 4, 8)
CASES = [
    pytest.param(name, mult, sharded, id=f"{name}-x{mult}-{'blocks' if sharded else 'flat'}")
    for name in LENGTH_SETS
    for mult in MULTIPLES
    for sharded in (False, True)
]

# The parent's layout of every case (commit c857e01, the same inputs, with
# extra_keys=("prompt_mask",)): (n_rows, row_len, empty rows, sha1 of the
# seq_map and of every array's bytes).
PARENT = {
    ("uniform", 1, False): (1, 8192, 0, "9687502dc0e792d657bf05eff3c65aa8dbc60aa7"),
    ("uniform", 1, True): (2, 5120, 0, "dd1f81709b9a4e2799883dd8cba7ea25f6977ba5"),
    ("uniform", 2, False): (2, 8192, 1, "ada46762d0ecb8fb57a430d789e927933de4e9ca"),
    ("uniform", 2, True): (2, 5120, 0, "dd1f81709b9a4e2799883dd8cba7ea25f6977ba5"),
    ("uniform", 4, False): (4, 8192, 3, "5390bc7f1f073eb600c44e9b8df15d428a38fb24"),
    ("uniform", 4, True): (4, 5120, 2, "f6733c87480ac0985869885c3de9239e6966ef24"),
    ("uniform", 8, False): (8, 8192, 7, "315e59390e199ecac9c58ba5b215af555e12043c"),
    ("uniform", 8, True): (8, 5120, 6, "f136907f852445377270fbeae286722edcb71163"),
    ("lognormal", 1, False): (4, 512, 0, "e25c742aafc46d713ddd27d05927732dd10d3633"),
    ("lognormal", 1, True): (6, 512, 2, "7146ec8ff69a7e7184c31a7572118c77fa1d4269"),
    ("lognormal", 2, False): (4, 512, 0, "e25c742aafc46d713ddd27d05927732dd10d3633"),
    ("lognormal", 2, True): (6, 512, 2, "7146ec8ff69a7e7184c31a7572118c77fa1d4269"),
    ("lognormal", 4, False): (4, 512, 0, "e25c742aafc46d713ddd27d05927732dd10d3633"),
    ("lognormal", 4, True): (8, 512, 4, "592d9d2b254d66de34f97d8abeed1bd042161e01"),
    ("lognormal", 8, False): (8, 512, 4, "d0591e854c8d3f0d0f98bdaf8109d81c7f50b362"),
    ("lognormal", 8, True): (8, 512, 4, "592d9d2b254d66de34f97d8abeed1bd042161e01"),
    ("giant", 1, False): (2, 1024, 0, "6e993ca897f1557dd88a68ef1ce865ecb5d48f6f"),
    ("giant", 1, True): (4, 1024, 1, "b8e784ebc621b3a7cc6d5487f1ef9d6c6c838583"),
    ("giant", 2, False): (2, 1024, 0, "6e993ca897f1557dd88a68ef1ce865ecb5d48f6f"),
    ("giant", 2, True): (4, 1024, 1, "b8e784ebc621b3a7cc6d5487f1ef9d6c6c838583"),
    ("giant", 4, False): (4, 1024, 2, "93aa96a1f8dc130d7b2046ba2cbc448e4a30aebd"),
    ("giant", 4, True): (4, 1024, 1, "b8e784ebc621b3a7cc6d5487f1ef9d6c6c838583"),
    ("giant", 8, False): (8, 1024, 6, "6a997be11eec7ca8a95bf59a610f42ee3345802c"),
    ("giant", 8, True): (8, 1024, 5, "26a1de1d27eb49b5fcf68ada6acdadf29eb05047"),
}


def _sample(lens, salt=0):
    total = sum(lens)
    return SequenceSample(
        keys={KEY, "prompt_mask"},
        ids=[f"s{i}" for i in range(len(lens))],
        seqlens={k: [[n] for n in lens] for k in (KEY, "prompt_mask")},
        data={
            KEY: ((np.arange(total) * 7 + salt) % 251).astype(np.int32),
            "prompt_mask": ((np.arange(total) + salt) % 3 == 0).astype(np.int32),
        },
    )


def _blocks(n, sharded):
    """Two uneven contiguous shard blocks (2/3 and 1/3 of the sequences),
    so that one shard's FFD count is short of the common block size."""
    if not sharded:
        return None
    cut = (2 * n) // 3
    return [list(range(cut)), list(range(cut, n))]


def _pack(name, mult, sharded, extra_keys=("prompt_mask",), salt=0):
    lens, cap = LENGTH_SETS[name]
    return packing.pack_sample(
        _sample(lens, salt),
        KEY,
        extra_keys=extra_keys,
        n_rows_multiple=mult,
        max_tokens_per_row=cap,
        shard_blocks=_blocks(len(lens), sharded),
    )


def _digest(pk):
    h = hashlib.sha1(repr(pk.seq_map).encode())
    for k in sorted(pk.arrays):
        a = pk.arrays[k]
        h.update(f"{k}{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _row_loads(pk):
    loads = [0] * pk.n_rows
    for r, _, n in pk.seq_map:
        loads[r] += n
    return loads


def _empty_rows(pk):
    return int((~(pk.arrays["segment_ids"] > 0).any(axis=1)).sum())


def _parent_rows(name, mult, sharded):
    """The parent's rule, restated: FFD, then empty rows up to the count."""
    lens, cap = LENGTH_SETS[name]
    blocks = _blocks(len(lens), sharded) or [list(range(len(lens)))]
    per = [
        datapack.ffd_allocate([lens[i] for i in b], capacity=cap)
        for b in blocks
    ]
    per_mult = mult // len(blocks) if mult % len(blocks) == 0 else mult
    per_mult = max(per_mult, 1)
    rows = max(len(g) for g in per)
    rows = -(-rows // per_mult) * per_mult
    heaviest = max(
        sum(lens[b[i]] for i in g) for b, gs in zip(blocks, per) for g in gs
    )
    empty = sum(rows - len(g) for g in per)
    return rows * len(blocks), heaviest, empty


@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_every_sequence_lands_whole_in_exactly_one_row(name, mult, sharded):
    lens, _ = LENGTH_SETS[name]
    pk = _pack(name, mult, sharded)
    assert [n for _, _, n in pk.seq_map] == lens
    seg = pk.arrays["segment_ids"]
    covered = np.zeros(seg.shape, bool)
    for r, s, n in pk.seq_map:
        assert 0 <= r < pk.n_rows and s + n <= pk.row_len
        assert not covered[r, s : s + n].any()  # no two sequences overlap
        covered[r, s : s + n] = True
        assert seg[r, s] > 0 and (seg[r, s : s + n] == seg[r, s]).all()
        np.testing.assert_array_equal(
            pk.arrays["positions"][r, s : s + n], np.arange(n)
        )
    # Real tokens are exactly the covered cells; the rest is padding.
    np.testing.assert_array_equal(seg > 0, covered)
    # Within a row every sequence is its own segment.
    for r in range(pk.n_rows):
        ids = [seg[rr, s] for rr, s, _ in pk.seq_map if rr == r]
        assert len(set(ids)) == len(ids)


@pytest.mark.parametrize("extra_keys", [(), ("prompt_mask",)], ids=["tokens", "extras"])
@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_unpack_returns_the_packed_order(name, mult, sharded, extra_keys):
    lens, _ = LENGTH_SETS[name]
    sample = _sample(lens)
    pk = _pack(name, mult, sharded, extra_keys=extra_keys)
    assert set(pk.arrays) == {"tokens", "segment_ids", "positions", *extra_keys}
    np.testing.assert_array_equal(pk.unpack(pk.arrays["tokens"]), sample.data[KEY])
    for k in extra_keys:
        np.testing.assert_array_equal(pk.unpack(pk.arrays[k]), sample.data[k])


@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_row_count_is_a_multiple_and_no_row_exceeds_the_cap(name, mult, sharded):
    _, cap = LENGTH_SETS[name]
    pk = _pack(name, mult, sharded)
    assert pk.n_rows > 0 and pk.n_rows % mult == 0
    assert pk.arrays["tokens"].shape == (pk.n_rows, pk.row_len)
    assert max(_row_loads(pk)) <= cap
    assert pk.row_len == packing.bucket_len(max(_row_loads(pk)))


@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_grid_is_never_larger_than_the_parents(name, mult, sharded):
    pk = _pack(name, mult, sharded)
    n_rows, row_len, empty, _ = PARENT[name, mult, sharded]
    # The pinned table is the parent's rule (FFD, then empty rows).
    rows, heaviest, pad = _parent_rows(name, mult, sharded)
    assert (n_rows, row_len, empty) == (rows, packing.bucket_len(heaviest), pad)
    assert max(_row_loads(pk)) <= heaviest
    assert pk.n_rows == n_rows
    assert pk.row_len <= row_len
    assert _empty_rows(pk) <= empty


@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_parents_layout_kept_where_no_row_was_empty_else_rows_filled(
    name, mult, sharded
):
    lens, _ = LENGTH_SETS[name]
    pk = _pack(name, mult, sharded)
    _, _, empty, digest = PARENT[name, mult, sharded]
    if empty == 0:
        # Same programs, same shapes, same compile-cache keys.
        assert _digest(pk) == digest
        return
    assert _digest(pk) != digest
    # A row stays empty only where a block has fewer sequences than rows.
    blocks = _blocks(len(lens), sharded) or [lens]
    rows_per_block = pk.n_rows // len(blocks)
    assert _empty_rows(pk) == sum(
        max(rows_per_block - len(b), 0) for b in blocks
    )
    if not sharded:
        # One block: the heaviest row got lighter.
        assert max(_row_loads(pk)) < _parent_rows(name, mult, sharded)[1]


@pytest.mark.parametrize("name,mult,sharded", CASES)
def test_layout_follows_from_metadata_alone(name, mult, sharded):
    a, b = _pack(name, mult, sharded), _pack(name, mult, sharded)
    assert _digest(a) == _digest(b)
    # Another SPMD member: same lengths, other token values.
    c = _pack(name, mult, sharded, salt=5)
    assert c.seq_map == a.seq_map and (c.n_rows, c.row_len) == (a.n_rows, a.row_len)
    for k in ("segment_ids", "positions"):
        np.testing.assert_array_equal(c.arrays[k], a.arrays[k])
    assert not np.array_equal(c.arrays["tokens"], a.arrays["tokens"])


@pytest.mark.parametrize("name", LENGTH_SETS)
def test_multiple_of_one_is_the_parents_seq_map(name):
    """The one-chip cells: `batch_shard` is 1 and nothing may move."""
    pk = _pack(name, 1, False)
    assert pk.seq_map == PARENT_SEQ_MAPS_X1[name]
    assert _digest(pk) == PARENT[name, 1, False][3]


def test_the_four_chip_cells_micro_batches_fill_four_rows():
    """`q7b-realloc-4chip`: 20 and 12 sequences of 354-414 tokens under a
    cap of 8,192 were one row of 8,192 / 5,120 and three empty ones."""
    lens, cap = LENGTH_SETS["uniform"]
    for n_seqs, parent_len in ((20, 8192), (12, 5120)):
        s = _sample(lens[:n_seqs])
        one = packing.pack_sample(s, KEY, max_tokens_per_row=cap)
        assert (one.n_rows, one.row_len) == (1, parent_len)
        pk = packing.pack_sample(s, KEY, n_rows_multiple=4, max_tokens_per_row=cap)
        assert (pk.n_rows, pk.row_len) == (4, 2048)
        per_row = [sum(1 for r, _, _ in pk.seq_map if r == i) for i in range(4)]
        assert per_row == [n_seqs // 4] * 4
        assert _empty_rows(pk) == 0


def test_five_ffd_rows_under_a_multiple_of_four_become_eight():
    lens = [300] * 10
    s = _sample(lens)
    ffd = packing.pack_sample(s, KEY, max_tokens_per_row=600)
    assert (ffd.n_rows, ffd.row_len) == (5, 1024)
    pk = packing.pack_sample(s, KEY, n_rows_multiple=4, max_tokens_per_row=600)
    assert (pk.n_rows, pk.row_len) == (8, 1024)  # parent: 8 x 1024, 3 empty
    assert _empty_rows(pk) == 0
    assert sorted(_row_loads(pk)) == [300] * 6 + [600] * 2
    pk = packing.pack_sample(s, KEY, n_rows_multiple=2, max_tokens_per_row=600)
    assert (pk.n_rows, pk.row_len) == (6, 1024)
    assert sorted(_row_loads(pk)) == [300] * 2 + [600] * 4


def test_fewer_sequences_than_rows_leaves_empty_rows_last():
    pk = packing.pack_sample(_sample([40, 90, 70]), KEY, n_rows_multiple=4)
    assert pk.n_rows == 4 and pk.row_len == 128
    assert [r for r, _, _ in pk.seq_map] == [0, 1, 2]
    assert not (pk.arrays["segment_ids"][3] > 0).any()


def test_empty_shard_block_gets_an_all_padding_block():
    pk = packing.pack_sample(
        _sample([40, 90, 70]), KEY, n_rows_multiple=4, max_tokens_per_row=256,
        shard_blocks=[[0, 1, 2], []],
    )
    assert (pk.n_rows, pk.row_len) == (4, 128)
    assert sorted(_row_loads(pk)) == [0, 0, 90, 110]
    assert not (pk.arrays["segment_ids"][2:] > 0).any()


def test_shard_blocks_stay_in_their_own_row_block():
    lens, cap = LENGTH_SETS["lognormal"]
    blocks = _blocks(len(lens), True)
    pk = _pack("lognormal", 8, True)
    per = pk.n_rows // 2
    for shard, block in enumerate(blocks):
        for i in block:
            assert shard * per <= pk.seq_map[i][0] < (shard + 1) * per


def test_ffd_kept_where_the_balanced_layout_would_be_heavier(monkeypatch):
    """LPT over more rows has not been seen to lose to FFD over fewer, but
    nothing proves it cannot: the guard keeps the grid from ever growing."""
    sizes = [5, 5, 4, 4, 3, 3]
    ffd = datapack.ffd_allocate(sizes, capacity=8)
    assert ffd == [[0, 4], [1, 5], [2, 3]]
    monkeypatch.setattr(
        datapack, "partition_balanced",
        lambda s, k: [[0, 1], [2], [3], [4, 5]],  # heaviest 10 > FFD's 8
    )
    assert packing._rows_over_mesh(sizes, ffd, 4) == ffd + [[]]
    monkeypatch.undo()
    spread = packing._rows_over_mesh(sizes, ffd, 4)
    assert spread == [[0], [1], [2, 4], [3, 5]]
    # FFD already has the rows the mesh needs: returned as it is.
    assert packing._rows_over_mesh(sizes, ffd, 3) is ffd


def test_explicit_row_len_is_honoured():
    pk = packing.pack_sample(
        _sample(LENGTH_SETS["uniform"][0]), KEY, n_rows_multiple=4,
        max_tokens_per_row=8192, row_len=4096,
    )
    assert (pk.n_rows, pk.row_len) == (4, 4096)


# The parent's seq_map with n_rows_multiple=1 (commit c857e01), in full.
PARENT_SEQ_MAPS_X1 = {
    "uniform": [
        (0, 0, 387), (0, 387, 409), (0, 796, 384), (0, 1180, 358),
        (0, 1538, 388), (0, 1926, 395), (0, 2321, 358), (0, 2679, 382),
        (0, 3061, 375), (0, 3436, 395), (0, 3831, 396), (0, 4227, 356),
        (0, 4583, 365), (0, 4948, 357), (0, 5305, 400), (0, 5705, 364),
        (0, 6069, 408), (0, 6477, 413), (0, 6890, 390), (0, 7280, 372),
    ],
    "lognormal": [
        (0, 0, 265), (0, 265, 167), (1, 0, 39), (2, 0, 108), (1, 39, 91),
        (3, 0, 15), (2, 108, 103), (1, 130, 40), (1, 170, 52), (1, 222, 39),
        (1, 261, 45), (1, 306, 67), (2, 211, 69), (3, 15, 18), (1, 373, 51),
        (3, 33, 21), (3, 54, 23), (1, 424, 48), (2, 280, 126), (2, 406, 106),
        (1, 472, 39), (3, 77, 36), (3, 113, 33), (0, 432, 71),
    ],
    "giant": [
        (0, 0, 900), (1, 0, 30), (1, 30, 32), (0, 900, 51), (1, 62, 42),
        (0, 951, 60), (1, 104, 47), (1, 151, 48), (1, 199, 44), (1, 243, 19),
        (1, 262, 40), (1, 302, 34), (1, 336, 40), (1, 376, 33), (1, 409, 21),
        (1, 430, 27),
    ],
}

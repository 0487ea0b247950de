"""SequenceSample ⇄ dense packed rows.

The engines' bridge between the host data plane (packed 1D numpy arrays with
seqlens) and XLA-friendly dense [B, S] buffers: sequences are FFD-packed into
rows, rows padded to a bucketed length (bounding the number of distinct
compiled shapes), and outputs are scattered back into the original
per-sequence packed order.

This is the TPU answer to the reference's cu_seqlens/varlen plumbing
(realhf/impl/model/utils/padding + flash_attn_varlen): instead of one long
ragged buffer per micro-batch we build a static [B, S] grid with segment ids.
"""

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from areal_tpu.api.data_api import SequenceSample
from areal_tpu.base import datapack

# Pad row lengths to multiples of this (TPU lane width × a few sublanes).
_BUCKET_QUANTUM = 128


def bucket_len(n: int, quantum: int = _BUCKET_QUANTUM, large_step: int = 0) -> int:
    """Round up to a bucketed static length: next power of two below 1024,
    then multiples of `large_step` (default `quantum`·8 = 1024) — bounds
    distinct compile shapes for the TRAINING pack path, where every new
    shape costs a full fwd+bwd compile."""
    n = max(n, 1)
    if n <= 128:
        return 128
    if n <= 1024:
        p = 128
        while p < n:
            p *= 2
        return p
    step = large_step or quantum * 8  # 1024
    return ((n + step - 1) // step) * step


def flash_tile_counts(
    segment_ids: np.ndarray, block: int = _BUCKET_QUANTUM,
    window: Optional[int] = None,
) -> Tuple[int, int]:
    """(live, grid) tiles of the rows' `block` x `block` attention squares:
    how many hold an unmasked (causal, same-sequence) element, against all
    of them.  The flash kernels visit the live ones
    (`ops/pallas/flash_attention.live_schedule` derives the same set on
    the device); times heads and layers it is their work for a call.
    `window`: a sliding-window layer's schedule, the tiles whose last key
    lies within `window` places of their first query."""
    seg = np.asarray(segment_ids)
    block = min(block, seg.shape[1])
    n = seg.shape[1] // block
    blocks = seg[:, : n * block].reshape(len(seg), n, block)
    hi = blocks.max(axis=-1)  # [rows, n]; a block of padding: lo > hi
    lo = np.where(blocks > 0, blocks, np.iinfo(seg.dtype).max).min(axis=-1)
    meet = (lo[:, None, :] <= hi[:, :, None]) & (
        hi[:, None, :] >= lo[:, :, None]
    )
    meet = np.tril(meet)
    if window is not None:
        i = np.arange(n)
        meet &= (i[:, None] * block - (i[None, :] * block + block - 1)) < window
    return int(meet.sum()), len(seg) * n * n


def decode_bucket_len(n: int) -> int:
    """Finer buckets (256 above 1024) for DECODE cache windows: every
    decode step streams the whole window, so coarse buckets directly tax
    every generated token (a 1024 quantum made a 1152-token request pay
    for a 2048-deep window); decode-step compiles are far cheaper than
    train-step compiles, so the extra shapes are affordable."""
    return bucket_len(n, large_step=_BUCKET_QUANTUM * 2)


def split_sharded(
    sample: SequenceSample, mb_spec
) -> List[Tuple[SequenceSample, Optional[List[List[int]]]]]:
    """Micro-batch split that stays consistent across data-plane shards.

    When the sample carries per-id `shard_of` tags (set by the worker when
    the master shipped each SPMD member only its own rows), every member
    must derive the SAME number of micro-batches with the SAME per-shard
    membership from metadata alone — a plain global FFD would interleave
    shards' rows and diverge the jitted programs across processes.  Each
    shard is FFD-split independently into a common group count k;
    micro-batch j is the concatenation of every shard's j-th group, and
    the returned per-microbatch shard blocks give each shard's positions
    within it (feeding pack_sample's row-block layout).

    Without shard tags this is exactly `sample.split(mb_spec)`.
    """
    blocks = sample.shard_blocks()
    if not blocks or len(blocks) <= 1:
        return [(mb, None) for mb in sample.split(mb_spec)]
    key = sample.main_key()
    lens = [sum(sample.seqlens[key][i]) for i in range(sample.bs)]
    cap = mb_spec.max_tokens_per_mb or (sum(lens) + 1)
    k = max(mb_spec.n_mbs, 1)
    while True:
        per = [
            datapack.ffd_allocate(
                [lens[i] for i in b], capacity=cap, min_groups=min(k, len(b))
            )
            if b
            else []
            for b in blocks
        ]
        k2 = max((len(g) for g in per), default=1)
        if k2 <= k:
            break
        k = k2  # a shard needed more groups; re-split everyone to match
    out = []
    for j in range(k):
        idx: List[int] = []
        row_blocks: List[List[int]] = []
        for b, gs in zip(blocks, per):
            g = [b[i] for i in gs[j]] if j < len(gs) else []
            row_blocks.append(list(range(len(idx), len(idx) + len(g))))
            idx.extend(g)
        if not idx:
            continue
        mb = sample.select_idx(idx)
        # pack_sample's shard_blocks index SEQUENCES, not batch rows —
        # a PPO row carries `group` sequences, so the two only coincide
        # for 1-sequence rows.  Expand each shard's contiguous row range
        # to its sequence range (rows are ordered shard-major, so the
        # sequence blocks stay contiguous).
        row_nseq = [len(sample.seqlens[key][i]) for i in idx]
        mb_blocks: List[List[int]] = []
        pos = 0
        for rb in row_blocks:
            n_seq = sum(row_nseq[r] for r in rb)
            mb_blocks.append(list(range(pos, pos + n_seq)))
            pos += n_seq
        out.append((mb, mb_blocks))
    return out


@dataclasses.dataclass
class RowPack:
    """Dense row layout + the mapping back to packed-1D order.

    arrays: key -> [B, S, *trailing] dense array (tokens, segment_ids,
    positions, plus aligned extras).
    seq_map: per original sequence (in sample packed order):
    (row, start, length).
    """

    arrays: Dict[str, np.ndarray]
    seq_map: List[Tuple[int, int, int]]
    n_rows: int
    row_len: int
    # Two streams a sequence (`pack_sample(block_length=...)`): where a
    # sequence's per-token output lies, (row, slot of out[lo], lo, hi) —
    # out[lo:hi] read from the MASKED stream, zero elsewhere — and the
    # streams' slot counts (`stream_stats`).
    out_map: Optional[List[Tuple[int, int, int, int]]] = None
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)

    def unpack(self, dense: np.ndarray) -> np.ndarray:
        """[B, S, ...] -> packed 1D [sum(lens), ...] in original order."""
        if self.out_map is not None:
            parts = []
            for (_, _, l), (r, at, lo, hi) in zip(self.seq_map, self.out_map):
                out = np.zeros((l,) + dense.shape[2:], dense.dtype)
                out[lo:hi] = dense[r, at : at + hi - lo]
                parts.append(out)
            return np.concatenate(parts, axis=0)
        parts = [dense[r, s : s + l] for (r, s, l) in self.seq_map]
        return np.concatenate(parts, axis=0)


def _rows_over_mesh(
    sizes: Sequence[int], ffd: List[List[int]], n_rows: int
) -> List[List[int]]:
    """Lay `sizes` out over exactly `n_rows` rows, given their FFD packing.

    FFD packs as few rows as fit; where the mesh needs more (`n_rows` >
    len(ffd)) the extra rows would be empty, and under batch sharding an
    empty row is a chip that trains zeros while another trains a full
    row.  So the sequences are spread over all `n_rows` by load instead
    (LPT), which also shortens the heaviest row and with it the bucketed
    row length.  FFD's layout is kept, padded with empty rows, in the
    rare case where the balanced layout's heaviest row would be heavier
    (the grid never grows), and as it is when it already has `n_rows`.
    Depends on nothing but `sizes` and `n_rows`: every SPMD member
    derives the same layout from metadata alone.
    """
    if len(ffd) >= n_rows:
        return ffd

    def heaviest(groups):
        return max((sum(sizes[i] for i in g) for g in groups), default=0)

    balanced = datapack.partition_balanced(sizes, n_rows)
    if heaviest(balanced) > heaviest(ffd):
        return ffd + [[] for _ in range(n_rows - len(ffd))]
    # FFD's row order: by smallest contained index, empty rows last.
    balanced.sort(key=lambda g: g[0] if g else 1 << 62)
    return balanced


def pack_sample(
    sample: SequenceSample,
    token_key: str,
    extra_keys: Sequence[str] = (),
    n_rows_multiple: int = 1,
    max_tokens_per_row: Optional[int] = None,
    row_len: Optional[int] = None,
    shard_blocks: Optional[List[List[int]]] = None,
    block_length: int = 0,
    mask_token_id: int = 0,
    wanted_key: Optional[str] = None,
) -> RowPack:
    """Pack every sequence of `sample[token_key]` into dense rows.

    `block_length` B > 0 (a model that generates by diffusion over blocks,
    `ModelConfig.block_length`): a sequence lies in its row as TWO STREAMS
    (`_stream_layout`), the row's budget counts stream slots, and the
    token-aligned extras and the model's per-token output live at the
    masked stream's places (`RowPack.out_map`).  `wanted_key`: the extra
    key (> 0 at storage index j - 1) that says which tokens j want a
    log-prob; None, every token but a sequence's first.

    extra_keys must be token-aligned with token_key (same seqlens).  The
    number of rows is FFD's count under `max_tokens_per_row`, rounded up to
    a multiple of `n_rows_multiple` (the mesh's batch-sharding degree);
    where that rounding adds rows, the sequences are balanced over all of
    them (`_rows_over_mesh`), so a row is empty only when there are fewer
    sequences than rows.  With a multiple of 1, or an FFD count that is
    already a multiple, the layout is FFD's own.

    shard_blocks (per-shard lists of sequence indices, together covering
    every sequence exactly once) pins each shard's sequences to its own
    equal-size contiguous ROW block, aligned with the contiguous
    batch-coordinate layout `_device_batch` shards rows by.  On a
    process-spanning mesh each process then materializes real data only
    for its own block (the sharded data plane zero-fills the rest), and
    identical metadata yields an identical layout on every member.  A
    shard whose FFD count is short of the common block size is balanced
    over its block by the same rule.
    """
    lens = sample.seqlens_of(token_key)
    for k in extra_keys:
        if sample.seqlens_of(k) != lens:
            raise ValueError(
                f"extra key {k!r} is not token-aligned with {token_key!r}"
            )
    seq_lens = lens
    if block_length:
        layouts = _stream_layouts(
            sample, token_key, wanted_key, block_length)
        lens = [lay.slots for lay in layouts]  # what a row's budget counts
    cap = max_tokens_per_row or max(lens, default=1)
    cap = max(cap, max(lens, default=1))
    if shard_blocks is not None and len(shard_blocks) > 1:
        n_shards = len(shard_blocks)
        if sorted(i for b in shard_blocks for i in b) != list(
            range(len(lens))
        ):
            raise ValueError("shard_blocks must partition the sequences")
        per_groups = [
            datapack.ffd_allocate(
                [lens[i] for i in block], capacity=cap
            )
            for block in shard_blocks
        ]
        # Equal row blocks: every shard gets the same row count, itself a
        # multiple of its slice of the batch-sharding degree.
        mult = max(n_rows_multiple, 1)
        per_mult = max(mult // n_shards, 1) if mult % n_shards == 0 else mult
        rows_per_shard = max(len(g) for g in per_groups)
        while rows_per_shard % per_mult:
            rows_per_shard += 1
        groups = []
        for block, gs in zip(shard_blocks, per_groups):
            gs = _rows_over_mesh(
                [lens[i] for i in block], gs, rows_per_shard
            )
            groups.extend([block[i] for i in g] for g in gs)
    else:
        groups = datapack.ffd_allocate(lens, capacity=cap)
        # Round the row count up to a multiple.
        mult = max(n_rows_multiple, 1)
        groups = _rows_over_mesh(lens, groups, -(-len(groups) // mult) * mult)
    n_rows = len(groups)
    s_pad = row_len or bucket_len(
        max((sum(lens[i] for i in g) for g in groups), default=1)
    )

    if block_length:
        return _pack_streams(
            sample, token_key, extra_keys, groups, n_rows, s_pad, seq_lens,
            layouts, block_length, mask_token_id)
    tok_src = np.asarray(sample.data[token_key])
    bounds = sample.cu_seqlens(token_key)
    extra_src = {k: np.asarray(sample.data[k]) for k in extra_keys}
    ex_bounds = {k: sample.cu_seqlens(k) for k in extra_keys}

    def alloc(src):
        shape = (n_rows, s_pad) + src.shape[1:]
        return np.zeros(shape, dtype=src.dtype)

    tokens = alloc(tok_src)
    seg = np.zeros((n_rows, s_pad), np.int32)
    pos = np.zeros((n_rows, s_pad), np.int32)
    extras = {k: alloc(v) for k, v in extra_src.items()}

    seq_map: List[Optional[Tuple[int, int, int]]] = [None] * len(lens)
    for r, g in enumerate(groups):
        off = 0
        for seq_no, i in enumerate(g, start=1):
            l = lens[i]
            tokens[r, off : off + l] = tok_src[bounds[i] : bounds[i + 1]]
            seg[r, off : off + l] = seq_no
            pos[r, off : off + l] = np.arange(l)
            for k in extra_keys:
                eb = ex_bounds[k]
                extras[k][r, off : off + l] = extra_src[k][eb[i] : eb[i + 1]]
            seq_map[i] = (r, off, l)
            off += l

    arrays = {"tokens": tokens, "segment_ids": seg, "positions": pos}
    arrays.update(extras)
    return RowPack(
        arrays=arrays, seq_map=seq_map, n_rows=n_rows, row_len=s_pad
    )


# --------------------------------------------------------------------------
# Two streams a sequence: the train forward of a model that generates by
# diffusion over blocks (`ModelConfig.block_length`)
# --------------------------------------------------------------------------

# Rows the log-prob head reads are gathered to a static count: the most a
# row holds, rounded up to this.
_HEAD_QUANTUM = 512


class _StreamLayout(NamedTuple):
    """One sequence of L tokens as two streams of a row, B = block length:
    a CLEAN stream — x_0 .. x_{L-1} at positions 0 .. L-1, padded to a
    multiple of B (`clean` slots) — and a MASKED stream — B mask tokens a
    block, at the positions of blocks `m0` .. `m1`, the blocks from the
    first to the last that hold a wanted token (`masked` slots; 0 where
    none is wanted).  `wanted` [L] marks the tokens that want a log-prob."""

    clean: int
    m0: int
    masked: int
    wanted: np.ndarray

    @property
    def slots(self) -> int:
        return self.clean + self.masked


def _stream_layouts(sample, token_key, wanted_key, blk: int):
    lens = sample.seqlens_of(token_key)
    src = bounds = None
    if wanted_key is not None:
        src = np.asarray(sample.data[wanted_key])
        bounds = sample.cu_seqlens(wanted_key)
    out = []
    for i, l in enumerate(lens):
        wanted = np.zeros(l, bool)
        if src is None:
            wanted[1:] = True
        else:  # storage index j - 1 holds token j's
            wanted[1:] = src[bounds[i] : bounds[i] + l - 1] > 0
        at = np.flatnonzero(wanted)
        m0, m1 = (at[0] // blk, at[-1] // blk) if len(at) else (0, -1)
        out.append(_StreamLayout(
            -(-l // blk) * blk, int(m0), int(m1 - m0 + 1) * blk, wanted))
    return out


def _pack_streams(
    sample, token_key, extra_keys, groups, n_rows, s_pad, lens, layouts,
    blk: int, mask_id: int,
) -> RowPack:
    """`pack_sample`'s rows for two streams a sequence.  One segment id a
    sequence; `stream_ids` 0 clean, 1 masked; `positions` absolute in the
    sequence, so a token's block is position // B; every stream starts on
    a multiple of B in the row, so no block straddles a flash tile.  At
    the masked stream's place of position j: `labels` x_j, `label_mask`
    whether j wants a log-prob, and every extra key's value of storage
    index j - 1.  `head_index` [rows, K]: the slots a row's wanted tokens
    lie at (K the most a row has, rounded up to `_HEAD_QUANTUM`; the row
    length where a row has fewer) — the log-prob head reads these alone."""
    tok_src = np.asarray(sample.data[token_key])
    bounds = sample.cu_seqlens(token_key)
    extra_src = {k: np.asarray(sample.data[k]) for k in extra_keys}
    ex_bounds = {k: sample.cu_seqlens(k) for k in extra_keys}

    def grid(dtype, trailing=()):
        return np.zeros((n_rows, s_pad) + tuple(trailing), dtype)

    tokens, seg, pos, stream, labels = (grid(np.int32) for _ in range(5))
    tokens = tokens.astype(tok_src.dtype)
    label_mask = grid(np.float32)
    extras = {k: grid(v.dtype, v.shape[1:]) for k, v in extra_src.items()}
    seq_map = [None] * len(lens)
    out_map = [None] * len(lens)
    n_clean = n_masked = n_align = 0
    for r, g in enumerate(groups):
        off = 0
        for seq_no, i in enumerate(g, start=1):
            lay, l = layouts[i], lens[i]
            toks = tok_src[bounds[i] : bounds[i + 1]]
            tokens[r, off : off + l] = toks
            seg[r, off : off + l] = seq_no
            pos[r, off : off + l] = np.arange(l)
            seq_map[i] = (r, off, l)
            m = off + lay.clean  # the masked stream's first slot
            first = lay.m0 * blk  # and its first position
            n = lay.masked
            tokens[r, m : m + n] = mask_id
            seg[r, m : m + n] = seq_no
            pos[r, m : m + n] = first + np.arange(n)
            stream[r, m : m + n] = 1
            hi = min(first + n, l)  # positions [first, hi) are tokens
            labels[r, m : m + hi - first] = toks[first:hi]
            label_mask[r, m : m + hi - first] = lay.wanted[first:hi]
            lo = max(first, 1)  # storage index j - 1, j from lo
            for k in extra_keys:
                eb = ex_bounds[k][i]
                extras[k][r, m + lo - first : m + hi - first] = extra_src[k][
                    eb + lo - 1 : eb + hi - 1]
            out_map[i] = (r, m + lo - first, lo - 1, max(hi - 1, lo - 1))
            n_clean, n_masked = n_clean + l, n_masked + n
            n_align += lay.clean - l
            off += lay.slots
    counts = (label_mask > 0).sum(axis=1)
    k_rows = min(-(-max(int(counts.max()), 1) // _HEAD_QUANTUM)
                 * _HEAD_QUANTUM, s_pad)
    head_index = np.full((n_rows, k_rows), s_pad, np.int32)  # pad: dropped
    for r in range(n_rows):
        at = np.flatnonzero(label_mask[r] > 0)
        head_index[r, : len(at)] = at
    arrays = {
        "tokens": tokens, "segment_ids": seg, "positions": pos,
        "stream_ids": stream, "labels": labels, "label_mask": label_mask,
        "head_index": head_index,
    }
    arrays.update(extras)
    return RowPack(
        arrays=arrays, seq_map=seq_map, n_rows=n_rows, row_len=s_pad,
        out_map=out_map,
        stats={
            "clean_slots": n_clean, "masked_slots": n_masked,
            "align_pad_slots": n_align, "head_rows": n_rows * k_rows,
            "wanted_tokens": int(counts.sum()),
        },
    )

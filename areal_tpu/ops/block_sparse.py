"""Block-sparse softmax attention by SELECTION (InfLLM-V2, minicpm_sala's
`minicpm4` mixer): a query of a sequence of at least `dense_len` tokens
attends, causally, over `topk` blocks of `block` keys — the sequence's
first `init_blocks`, the `window / block` blocks ending at its own, and the
highest by score against COMPRESSED keys.

    kc_j   = mean(k[stride j : stride j + kernel])        per key head, every
             kernel that lies whole inside the sequence
    p_t    = softmax_j(q_t . kc_j / sqrt(d))               per query head, over
             the kernels whose last token is at or before t
    s_t[j] = sum of p_t[j] over the key head's query heads
    S_t[b] = max of s_t over the kernels that overlap block b   (kernel = 2
             strides, block = 4: kernels 4b - 1 .. 4b + 3)
    chosen = forced blocks, then the highest S_t, `topk` in all, among the
             blocks that start at or before t

The selection carries no gradient (indices).  Three programs, one
arithmetic:

- `packed_attention` (the train step's forward, recomputation and backward;
  `forward`; prefill): packed rows, segments at any offset.  Compressed
  keys and block scores live in SLOT space — the kernel that ends at row
  index i is slot i // 16, the block b of a segment whose first kernel is
  slot c0 is global block c0 // 4 + b, pooled at phase c0 % 4 — so no
  per-segment gather is needed.  The selection (`_row_selection`: XLA ops,
  a chunk of queries at a time, no gradient) gives every query its choice
  over GLOBAL blocks — every block for a query of a sequence under
  `dense_len` — and every key its global block; the attention under that
  choice takes one of two forms (`use_flash`, the argument that picks
  `ops.attention.packed_attention`'s form):
  on a TPU backend, one device, whole tiles (`kernel_fits`) — and, from
  the model's programs, in the GRADIENT programs alone: `transformer.
  _sparse_packed` hands `forward` and prefill False where nobody
  forces True — the FLASH KERNELS with the choice as one more term of
  the tile mask
  (`flash_attention.BlockChoice`: scores, mask and probabilities stay in
  VMEM, the causal half alone is multiplied, the backward is the kernels'
  own `custom_vjp` from `o` and the logsumexp — 44 ms forward / 98
  forward + backward a 13,312-token row against the mask form's 107 / 251:
  PERF.md section 6, PR 56); elsewhere — off a TPU, on a mesh, with
  `use_flash=False` — DENSE UNDER THE MASK in chunks of queries
  (`_row_attend_mask`: scores against every key of the row, the choice
  expanded to keys by a one-hot matmul, a `jax.checkpoint` a chunk), the
  form the tests hold the kernels to.  With random weights neighbouring
  queries choose unrelated blocks, so a tile of queries reads every block
  anyway and neither form skips a tile for the choice; the chosen blocks
  GATHERED with a key head's query heads as the matmul's rows (the
  published kernel's form) ran at a thirteenth of the mask form's speed as
  XLA ops and is kept where it was measured, `scripts/sala_controls.py`
  (PERF.md section 6, PR 55).
- `decode_attention` (one token a row through the cache): reads the
  compressed keys (one row per `stride` tokens) and the chosen blocks' rows
  of K and V, never the window.
- `compressed_step`: the cache's new compressed key where the token
  completes a kernel.
"""

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38
QUERY_CHUNK = 256
_FORCED = 1e9  # a forced block's score: above any sum of 16 probabilities


@dataclasses.dataclass(frozen=True)
class Sizes:
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "Sizes":
        return cls(
            cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
            cfg.sparse_block_size, cfg.sparse_topk, cfg.sparse_init_blocks,
            cfg.sparse_window, cfg.sparse_dense_len,
        )

    @property
    def window_blocks(self) -> int:
        return self.window // self.block

    @property
    def pool(self) -> int:  # strides a block: the pool's step
        return self.block // self.stride


# --------------------------------------------------------------------------
# Shared pieces
# --------------------------------------------------------------------------


def _segments(segment_ids: jax.Array):
    """[S] -> (position within the segment, the segment's start index, its
    length), each [S] int32."""
    s = segment_ids.shape[0]
    idx = jnp.arange(s, dtype=jnp.int32)
    prev = jnp.pad(segment_ids[:-1], (1, 0), constant_values=-1)
    nxt = jnp.pad(segment_ids[1:], (0, 1), constant_values=-1)
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(segment_ids != prev, idx, 0))
    end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(segment_ids != nxt, idx, s), reverse=True)
    return idx - start, start, end - start + 1


def _window_mean(k: jax.Array, size: int) -> jax.Array:
    """mean(k[i - size + 1 : i + 1]) for every i along axis 0, fp32, by
    doubling (size a power of two): a fixed tree of adds, exact to the
    order of an fp32 sum."""
    x = k.astype(jnp.float32)
    span = 1
    while span < size:
        x = x + jnp.pad(x[:-span], ((span, 0),) + ((0, 0),) * (x.ndim - 1))
        span *= 2
    if span != size:
        raise NotImplementedError(f"kernel_size {size} is no power of two")
    return x / size


def _pool(s: jax.Array, phase, sz: Sizes, n_blocks: int) -> jax.Array:
    """Kernel scores s [..., NK] (0 where not visible) -> block scores
    [..., n_blocks]: block B the max over kernels pool * B + phase - 1 ..
    pool * B + phase + pool - 1, the kernels (two strides each) that
    overlap a block of `pool` strides where the sequence's first kernel
    lies at `phase`: [...] int32 in [0, pool), or a Python int."""
    p = sz.pool
    nk = s.shape[-1]
    need = p * n_blocks + 2 * p
    padded = jnp.pad(
        s, ((0, 0),) * (s.ndim - 1) + ((1, max(need - nk - 1, 0)),))
    # views[u][..., B] = s[p * B - 1 + u]
    views = [padded[..., u::p][..., :n_blocks] for u in range(2 * p)]

    def at(r):  # kernels p*B + r - 1 .. p*B + r + p - 1
        out = views[r]
        for u in range(r + 1, r + p + 1):
            out = jnp.maximum(out, views[u])
        return out

    if isinstance(phase, int):
        return at(phase)
    out = at(0)
    for r in range(1, p):
        out = jnp.where((phase == r)[..., None], at(r), out)
    return out


def _choose(scores, block, own, sz: Sizes):
    """Block scores [..., NB], each block's number within the sequence
    `block` [..., NB] (any int outside [0, own] is not visible) and the
    query's own block `own` [..., 1] -> bool [..., NB]: the `topk` chosen."""
    visible = (block >= 0) & (block <= own)
    forced = (block < sz.init_blocks) | (block > own - sz.window_blocks)
    ranked = jnp.where(forced, _FORCED, scores)
    ranked = jnp.where(visible, ranked, -1.0)
    # The topk highest, ties to the lower block, by RANK: a block is chosen
    # where fewer than topk blocks come before it.  [.., NB, NB] compares
    # fused into one reduction; `lax.top_k` is a sort of every (query, key
    # head) row on a TPU, 1.9 of a 14 s step at 13 k tokens (my chip run,
    # PR 55).
    at = jnp.arange(scores.shape[-1])
    mine, other = ranked[..., :, None], ranked[..., None, :]
    before = (other > mine) | ((other == mine) & (at[None, :] < at[:, None]))
    rank = jnp.sum(before, axis=-1, dtype=jnp.int32)
    return (rank < sz.topk) & visible


def _kernel_scores(q, kc, visible, n_kv: int):
    """q [T, Hq, d], kc [NK, Hkv, d], visible [T, NK] -> s [T, Hkv, NK]
    fp32: each query head's softmax over its visible kernels, summed over
    the key head's query heads (zeros where none is visible)."""
    t, hq, d = q.shape
    qg = q.reshape(t, n_kv, hq // n_kv, d)
    logits = jnp.einsum(
        "tgrd,cgd->tgrc", qg, kc.astype(q.dtype),
        preferred_element_type=jnp.float32) * d**-0.5
    logits = jnp.where(visible[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(visible[:, None, None, :], probs, 0.0)
    return jnp.sum(probs, axis=2)


# --------------------------------------------------------------------------
# Packed rows: training, forward, prefill
# --------------------------------------------------------------------------


def compress_row(k: jax.Array, pos: jax.Array, segment_ids, sz: Sizes):
    """One row's keys [S, Hkv, d] -> (kc [NK, Hkv, d] in k's type: slot c
    the kernel that ENDS at an index in [stride c, stride (c + 1)) — at
    most one does — zeros where none; its number within its segment [NK]
    and its segment [NK], -1 where none)."""
    s = k.shape[0]
    st = sz.stride
    nk = -(-s // st)
    mean = _window_mean(k, sz.kernel)
    ends = (pos >= sz.kernel - 1) & ((pos - (sz.kernel - 1)) % st == 0)
    pad = nk * st - s
    mean = jnp.pad(mean, ((0, pad),) + ((0, 0),) * (k.ndim - 1))
    ends = jnp.pad(ends, (0, pad)).reshape(nk, st)
    number = jnp.pad((pos - (sz.kernel - 1)) // st, (0, pad)).reshape(nk, st)
    kc = jnp.sum(
        jnp.where(ends[..., None, None], mean.reshape(nk, st, *k.shape[1:]), 0),
        axis=1)
    number = jnp.max(jnp.where(ends, number, -1), axis=1)
    seg = jnp.pad(segment_ids, (0, pad)).reshape(nk, st)
    seg = jnp.max(jnp.where(ends, seg, -1), axis=1)
    return kc.astype(k.dtype), number, seg


def _select_chunk(q, kc, knum, kseg, seg, pos, start, sz: Sizes):
    """A chunk of queries q [T, Hq, d] of one row against the row's
    compressed keys -> chosen [T, Hkv, NBg] bool over GLOBAL blocks."""
    n_kv = kc.shape[1]
    nk = kc.shape[0]
    n_blocks = nk // sz.pool + 2
    # Kernel slot c is visible where it is the query's segment's and its
    # last token (number * stride + kernel - 1) is at or before the query.
    visible = (
        (kseg[None, :] == seg[:, None]) & (knum[None, :] >= 0)
        & (knum[None, :] * sz.stride + sz.kernel - 1 <= pos[:, None])
    )
    s = _kernel_scores(q, kc, visible, n_kv)  # [T, Hkv, NK]
    c0 = (start + sz.kernel - 1) // sz.stride  # the segment's first slot
    pooled = _pool(s, (c0 % sz.pool)[:, None], sz, n_blocks)
    block = jnp.arange(n_blocks)[None, :] - (c0 // sz.pool)[:, None]  # [T, NBg]
    own = (pos // sz.block)[:, None]
    return _choose(pooled, block[:, None, :], own[:, None, :], sz)


def _attend_mask_chunk(q, k, v, chosen, key_block, seg_q, seg_k, idx_q):
    """Dense under the mask: q [T, Hq, d] against every key of the row
    [S, Hkv, d]; chosen [T, Hkv, NBg] or None (plain causal); key_block [S]
    each key's global block."""
    t, hq, d = q.shape
    s, n_kv, _ = k.shape
    qg = q.reshape(t, n_kv, hq // n_kv, d)
    logits = jnp.einsum(
        "tgrd,sgd->gtrs", qg, k, preferred_element_type=jnp.float32
    ) * d**-0.5  # [Hkv, T, R, S]
    mask = (seg_q[:, None] == seg_k[None, :]) & (
        jnp.arange(s)[None, :] <= idx_q[:, None])  # [T, S]
    expand = jax.nn.one_hot(key_block, chosen.shape[-1], dtype=q.dtype)
    picked = jnp.einsum(
        "tgb,sb->gts", chosen.astype(q.dtype), expand,
        preferred_element_type=jnp.float32) > 0.5
    mask = mask[None] & picked
    logits = jnp.where(mask[:, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "gtrs,sgd->tgrd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return out.reshape(t, hq, d).astype(q.dtype)


def _chunked(chunk: int, *xs, fills=()):
    """Arrays [S, ...] -> each as [chunks, chunk, ...], padded with its
    fill (0 where `fills` gives none)."""
    pad = -xs[0].shape[0] % chunk
    return tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                constant_values=fill).reshape(-1, chunk, *x.shape[1:])
        for x, fill in zip(xs, tuple(fills) + (0,) * len(xs)))


def _row_selection(q, k, segment_ids, sz: Sizes, chunk: int):
    """One packed row: q [S, Hq, d], k [S, Hkv, d] -> (chosen [S, Hkv, NBg]
    bool, a query's choice over GLOBAL blocks — every block for a query of
    a sequence under `dense_len`: data, no second path —, each key's global
    block [S], kc [NK, Hkv, d], each kernel's number within its segment
    [NK]).  Selection runs a chunk of queries at a time and carries no
    gradient."""
    s = q.shape[0]
    pos, start, length = _segments(segment_ids)
    with jax.named_scope("compress"):
        kc, knum, kseg = compress_row(
            jax.lax.stop_gradient(k), pos, segment_ids, sz)
    c0 = (start + sz.kernel - 1) // sz.stride
    key_block = pos // sz.block + c0 // sz.pool  # [S] global block of a key
    sparse = length >= sz.dense_len  # [S] per token, its sequence's

    def select(xs):
        segc, qc, posc, startc, sparsec = xs
        with jax.named_scope("select"):
            chosen = _select_chunk(qc, kc, knum, kseg, segc, posc, startc, sz)
            return chosen | ~sparsec[:, None, None]

    chosen = jax.lax.map(select, _chunked(
        chunk, segment_ids, jax.lax.stop_gradient(q), pos, start, sparse,
        fills=(-3,)))
    return chosen.reshape(-1, *chosen.shape[2:])[:s], key_block, kc, knum


def _row_attend_mask(q, k, v, segment_ids, chosen, key_block, chunk: int):
    """One packed row under its choice, the `jnp` form: a chunk of queries
    at a time, each under its own `jax.checkpoint`."""
    s = q.shape[0]

    @jax.checkpoint
    def attend(k, v, xs):
        segc, idxc, qc, chosenc = xs
        with jax.named_scope("attend"):
            return _attend_mask_chunk(
                qc, k, v, chosenc, key_block, segc, segment_ids, idxc)

    # Dense under the mask a chunk multiplies EVERY key of the row, twice
    # the causal half.  Runs of chunks against the keys up to their own
    # end (62% of the square at four runs) were tried and read WORSE: the
    # score fusions over 5,632 and 6,656 keys ran at a tenth of the rate
    # of the one over all 13,312 (my chip run, PR 55: 0.62 s a step each
    # against 0.06), so in this form every chunk takes the whole row; the
    # kernel form (`packed_attention`) multiplies the causal half alone.
    out = jax.lax.map(functools.partial(attend, k, v), _chunked(
        chunk, segment_ids, jnp.arange(s, dtype=jnp.int32), q, chosen,
        fills=(-3, s)))
    return out.reshape(-1, *q.shape[1:])[:s]


def kernel_fits(s: int, sz: Sizes) -> bool:
    """Whether the flash kernels take a row of `s` tokens under this
    choice: whole tiles, and a trip's keys within half a window of blocks
    (`flash_attention.BlockChoice`: a key's global block is within one of
    (its index + kernel - 1) // block wherever segments start)."""
    from areal_tpu.ops.pallas import flash_attention as fa

    return (s % min(s, fa.DEFAULT_BLOCK_K) == 0
            and fa.TRIP_ROWS // sz.block + 2 < fa.CHOICE_BLOCKS // 2)


def packed_attention(
    q: jax.Array,  # [B, S, Hq, d]
    k: jax.Array,  # [B, S, Hkv, d]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S], 0 = pad
    sz: Sizes,
    chunk: int = QUERY_CHUNK,
    use_flash=None,  # None = by platform | bool | a Mesh (the `jnp` form)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal-within-segment attention over packed rows, by selection for
    every sequence of at least `dense_len` tokens -> (out [B, S, Hq, d], the
    rows' compressed keys by slot [B, NK, Hkv, d], each slot's kernel number
    within its segment [B, NK], -1 where the slot holds none).  `use_flash`
    (`ops.attention.packed_attention`'s): whether `attend` is the flash
    kernels under the choice — None, on a TPU backend where `kernel_fits`;
    True forces them (interpreted off a TPU); False, or a mesh of several
    devices, the `jnp` form."""
    from areal_tpu.ops.pallas import flash_attention as fa

    s = q.shape[1]
    interpret = fa._interpret()
    if use_flash is None:
        use_flash = kernel_fits(s, sz) and not interpret
    kernels = use_flash is True
    if kernels and not kernel_fits(s, sz):
        raise ValueError(
            f"the flash kernels take no row of {s} tokens under blocks of "
            f"{sz.block} keys")
    return _packed_attention(
        q, k, v, segment_ids, sz=sz, chunk=min(chunk, s), kernels=kernels,
        interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("sz", "chunk", "kernels", "interpret"))
def _packed_attention(q, k, v, segment_ids, *, sz, chunk, kernels, interpret):
    """`packed_attention` behind ONE `jit` entry point: a program's call
    sites (forward, the recomputation, every bucket's) are traced and
    lowered once.  `interpret` is what the kernels ask at trace time
    (`flash_attention._interpret`), here for the cached trace's key: a
    trace must not be another backend's."""
    from areal_tpu.ops.pallas import flash_attention as fa

    del interpret
    with jax.named_scope("layer/sparse_attn"):
        chosen, key_block, kc, knum = jax.vmap(
            lambda q, k, seg: _row_selection(q, k, seg, sz, chunk)
        )(q, k, segment_ids)
        if kernels:
            with jax.named_scope("attend"):
                out = fa.flash_attention(
                    q, k, v, segment_ids,
                    choice=fa.BlockChoice(chosen, key_block))
        else:
            out = jax.vmap(
                lambda *row: _row_attend_mask(*row, chunk)
            )(q, k, v, segment_ids, chosen, key_block)
    return out, kc, knum


def compressed_of_last(kc, knum, segment_ids, sz: Sizes, n_slots: int):
    """What prefill leaves in the cache: the compressed keys of each row's
    LAST segment by kernel number, [B, n_slots, Hkv, d] (zeros past the
    last whole kernel).  kc [B, NK, Hkv, d] and knum [B, NK] by slot."""
    def row(kc, knum, seg):
        _, start, _ = _segments(seg)
        c0 = (start[-1] + sz.kernel - 1) // sz.stride
        at = c0 + jnp.arange(n_slots)
        ok = (at < kc.shape[0])
        at = jnp.minimum(at, kc.shape[0] - 1)
        ok = ok & (knum[at] == jnp.arange(n_slots))
        return jnp.where(ok[:, None, None], kc[at], 0)

    return jax.vmap(row)(kc, knum, segment_ids)


# --------------------------------------------------------------------------
# One token a row through the cache
# --------------------------------------------------------------------------


def compressed_step(ck, k_cache, li, slot, valid_from, sz: Sizes):
    """The compressed keys [L, B, NKmax, Hkv, d] of layer `li` after the
    token at cache slot `slot` (already in `k_cache` [L, B, S, Hkv, d]): a
    row whose token completes a kernel (position t with t - kernel + 1 a
    whole number of strides) gets that kernel's mean — the cache's last
    `kernel` slots, the same for every row — at the kernel's number."""
    b = ck.shape[1]
    t = slot - valid_from  # [B] the token's position
    number = (t - (sz.kernel - 1)) // sz.stride
    done = (t >= sz.kernel - 1) & ((t - (sz.kernel - 1)) % sz.stride == 0)
    done = done & (number < ck.shape[2])
    last = jax.lax.dynamic_slice(
        k_cache, (li, 0, slot - (sz.kernel - 1), 0, 0),
        (1, b, sz.kernel, *k_cache.shape[3:]))[0]
    mean = _window_mean(jnp.moveaxis(last, 1, 0), sz.kernel)[-1]  # [B, Hkv, d]
    at = jnp.clip(number, 0, ck.shape[2] - 1)
    rows = jnp.arange(b)
    layer = jax.lax.dynamic_index_in_dim(ck, li, axis=0, keepdims=False)
    new = jnp.where(done[:, None, None], mean.astype(ck.dtype), layer[rows, at])
    layer = layer.at[rows, at].set(new)
    return jax.lax.dynamic_update_index_in_dim(ck, layer, li, axis=0)


def decode_attention(
    q: jax.Array,  # [B, 1, Hq, d] — one new token per row
    k_cache: jax.Array,  # [B, S_max, Hkv, d], the token's k/v written
    v_cache: jax.Array,
    ck: jax.Array,  # [B, NKmax, Hkv, d] compressed keys by kernel number
    valid_from: jax.Array,  # [B] first cache slot of the row's sequence
    slot: jax.Array,  # scalar: the token's cache slot, every row's
    sz: Sizes,
) -> Tuple[jax.Array, jax.Array]:
    """Attention of one token a row BY SELECTION through the cache -> (out
    [B, 1, Hq, d], counts [3] fp32: keys read — chosen blocks x block +
    compressed rows, a key head's, summed over the rows —, keys cached, rows
    still under `dense_len`).  A row whose sequence (its cache length) is
    under `dense_len` attends over all of it instead; the dense form is
    only RUN in a step in which some row is."""
    b, _, hq, d = q.shape
    s_max, n_kv = k_cache.shape[1], k_cache.shape[2]
    nk = ck.shape[1]
    t = slot - valid_from  # [B] position of the token
    n_blocks = nk // sz.pool + 2
    with jax.named_scope("layer/sparse_attn"):
        with jax.named_scope("select"):
            visible = (
                jnp.arange(nk)[None, :] * sz.stride + sz.kernel - 1 <= t[:, None])
            s = jax.vmap(
                lambda q, kc, vis: _kernel_scores(q, kc, vis[None], n_kv)[0]
            )(q, ck, visible)  # [B, Hkv, NK]
            pooled = _pool(s, 0, sz, n_blocks)
            block = jnp.arange(n_blocks)[None, None, :]
            own = (t // sz.block)[:, None, None]
            chosen = _choose(pooled, block, own, sz)  # [B, Hkv, NB]
            n = min(sz.topk, n_blocks)
            order = jnp.argsort(~chosen, axis=-1, stable=True)[..., :n]
            live = jnp.take_along_axis(chosen, order, axis=-1)  # [B, Hkv, n]
        with jax.named_scope("attend"):
            starts = valid_from[:, None, None] + order * sz.block
            clamped = jnp.clip(starts, 0, s_max - sz.block)

            def blocks_of(cache, at, head):
                return jax.lax.dynamic_slice(
                    cache, (at, head, 0), (sz.block, 1, d))[:, 0]

            def gather(cache):  # [B, S, Hkv, d] -> [B, Hkv, n, block, d]
                per_head = jax.vmap(
                    jax.vmap(blocks_of, in_axes=(None, 0, None)),
                    in_axes=(None, 0, 0))
                return jax.vmap(
                    lambda c, at: per_head(c, at, jnp.arange(n_kv))
                )(cache, clamped)

            kg, vg = gather(k_cache), gather(v_cache)
            rows = clamped[..., None] + jnp.arange(sz.block)  # cache slots
            keep = live[..., None] & (rows >= starts[..., None]) & (rows <= slot)
            qg = q[:, 0].reshape(b, n_kv, hq // n_kv, d)
            logits = jnp.einsum(
                "bgrd,bgnsd->bgrns", qg, kg.astype(q.dtype),
                preferred_element_type=jnp.float32) * d**-0.5
            logits = jnp.where(keep[:, :, None], logits, NEG_INF)
            shape = logits.shape
            probs = jax.nn.softmax(
                logits.reshape(*shape[:3], -1), axis=-1).reshape(shape)
            out = jnp.einsum(
                "bgrns,bgnsd->bgrd", probs.astype(vg.dtype), vg,
                preferred_element_type=jnp.float32)
            out = out.reshape(b, 1, hq, d).astype(q.dtype)
    dense = t + 1 < sz.dense_len  # [B]

    def with_dense(out):
        from areal_tpu.ops.attention import _decode_attention

        full = _decode_attention(q, k_cache, v_cache, valid_from, slot + 1)
        return jnp.where(dense[:, None, None, None], full, out)

    out = jax.lax.cond(jnp.any(dense), with_dense, lambda out: out, out)
    cached = (t + 1).astype(jnp.float32)
    read = jnp.mean(jnp.sum(live, axis=-1).astype(jnp.float32), axis=-1) * (
        sz.block) + jnp.sum(visible, axis=-1).astype(jnp.float32)
    read = jnp.where(dense, cached, read)
    counts = jnp.stack([
        jnp.sum(read), jnp.sum(cached), jnp.sum(dense).astype(jnp.float32)])
    return out, counts

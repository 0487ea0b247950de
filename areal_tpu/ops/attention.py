"""Packed variable-length attention.

The framework's training/inference batches are *packed rows*: shape [B, S]
where each row concatenates several sequences back-to-back, identified by
`segment_ids` (0 = padding).  Attention is causal within a segment and never
crosses segments — the TPU-native replacement for the reference's
flash_attn_varlen_func over cu_seqlens (realhf/impl/model/modules/attn.py:24).

Two implementations:
- `packed_attention_reference`: dense masked softmax (jnp).  Used on CPU
  tests and as the numerics oracle.
- `packed_flash_attention`: Pallas TPU flash kernel (see
  areal_tpu/ops/pallas/flash_attention.py), dispatched on TPU.
"""

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38  # close to bf16 min, the usual TPU mask value


def inner_scope(scope: Optional[str]):
    """An inner scope under a part's own: a plan that mixes window and
    full attention layers tells them apart by `window` / `full` inside
    `layer/attn_qkv`, `layer/attn` and `layer/attn_out`; None (every other
    plan) adds nothing to the names."""
    return jax.named_scope(scope) if scope else contextlib.nullcontext()


def make_packed_mask(
    segment_ids: jax.Array, causal: bool = True, window: Optional[int] = None,
    blocks=None,
) -> jax.Array:
    """[B, S] segment ids -> [B, 1, S, S] boolean mask (True = attend).
    `window` (with `causal`): a query sees the last `window` keys of its
    sequence, itself included.  `blocks`: (block ids, stream ids), [B, S]
    int32 each — the BLOCK-causal mask of generation by diffusion over
    blocks, in the place of the causal term: a query sees a key of its
    sequence that is a clean (stream 0) token of an EARLIER block, or a
    token of its own stream and block (which may lie after it in the
    row)."""
    seg_q = segment_ids[:, :, None]
    seg_k = segment_ids[:, None, :]
    mask = (seg_q == seg_k) & (seg_q > 0)
    if blocks is not None:
        blk, stream = blocks
        earlier = (stream[:, None, :] == 0) & (
            blk[:, None, :] < blk[:, :, None])
        own = (stream[:, None, :] == stream[:, :, None]) & (
            blk[:, None, :] == blk[:, :, None])
        return (mask & (earlier | own))[:, None, :, :]
    if causal:
        s = segment_ids.shape[-1]
        idx = jnp.arange(s)
        mask &= idx[:, None] >= idx[None, :]
        if window is not None:
            mask &= idx[:, None] - idx[None, :] < window
    return mask[:, None, :, :]


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, n_kv, d] -> [B, S, n_kv*n_rep, d] (GQA head expansion)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


def packed_attention_reference(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,  # [B, S, n_kv, d]
    segment_ids: jax.Array,  # [B, S] int, 0 = pad
    causal: bool = True,
    logits_soft_cap: Optional[float] = None,
    window: Optional[int] = None,
    blocks=None,
) -> jax.Array:
    n_q, n_kv = q.shape[2], k.shape[2]
    k = repeat_kv(k, n_q // n_kv)
    v = repeat_kv(v, n_q // n_kv)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    mask = make_packed_mask(
        segment_ids, causal=causal, window=window, blocks=blocks)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked (padding) rows produce uniform probs; zero them out.
    probs = jnp.where(mask.any(axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_reference(
    q: jax.Array,  # [B, 1, n_q, d] — one new token per row
    k_cache: jax.Array,  # [B, S_max, n_kv, d]
    v_cache: jax.Array,  # [B, S_max, n_kv, d]
    cache_len: jax.Array,  # [B] int — valid prefix length per row
) -> jax.Array:
    """Single-token decode attention over a dense KV cache (fp32 oracle)."""
    n_q, n_kv = q.shape[2], k_cache.shape[2]
    k = repeat_kv(k_cache, n_q // n_kv)
    v = repeat_kv(v_cache, n_q // n_kv)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    s_max = k_cache.shape[1]
    valid = jnp.arange(s_max)[None, :] < cache_len[:, None]  # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


@jax.named_scope("layer/attn")
def decode_attention(
    q: jax.Array,  # [B, 1, n_q, d] — one new token per row
    k_cache: jax.Array,  # [B, S_max, n_kv, d]
    v_cache: jax.Array,  # [B, S_max, n_kv, d]
    valid_from: jax.Array,  # [B] int — first valid cache slot per row
    valid_to: jax.Array,  # scalar/[B] int — one past the last valid slot
    k_scale: "Optional[jax.Array]" = None,  # [B, S_max, n_kv]: int8 cache
    v_scale: "Optional[jax.Array]" = None,
    valid: "Optional[jax.Array]" = None,  # [B, S_max] bool, in the range's place
    scope: Optional[str] = None,  # an inner name under `layer/attn`
) -> jax.Array:
    """Single-token GQA decode attention, HBM-lean: no repeat_kv expansion
    (query heads grouped per KV head) and no fp32 materialization of the
    cache — bf16 operands with fp32 MXU accumulation.  `[valid_from,
    valid_to)` is the live window (right-aligned prompt layout); a cache
    whose live entries are no one range (a ring that has wrapped) gives
    `valid`, the entries to read, and the range is not looked at.
    With `k_scale`/`v_scale` the caches are int8 and dequantized here.
    XLA ops on every backend: the arithmetic the paged kernel is held to.

    Replaces the reference's flash_attn_with_kvcache decode path
    (realhf/impl/model/modules/attn.py:251)."""
    with inner_scope(scope):
        return _decode_attention(
            q, k_cache, v_cache, valid_from, valid_to, k_scale, v_scale, valid
        )


def _decode_attention(
    q, k_cache, v_cache, valid_from, valid_to, k_scale=None, v_scale=None,
    valid=None,
):
    """`decode_attention` outside its scope: `ragged_paged_attention`'s
    XLA form runs it under its own."""
    if k_scale is not None:
        from areal_tpu.ops.quant import kv_dequant

        k_cache = kv_dequant(k_cache, k_scale, q.dtype)
        v_cache = kv_dequant(v_cache, v_scale, q.dtype)
    b, _, n_q, d = q.shape
    n_kv = k_cache.shape[2]
    n_rep = n_q // n_kv
    qh = q[:, 0].reshape(b, n_kv, n_rep, d)
    scale = d**-0.5
    logits = (
        jnp.einsum(
            "bgrd,bsgd->bgrs", qh, k_cache.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [B, n_kv, n_rep, S] fp32
    if valid is None:
        idx = jnp.arange(k_cache.shape[1])
        valid = (idx[None, :] >= valid_from[:, None]) & (
            idx[None, :] < jnp.broadcast_to(valid_to, (b,))[:, None]
        )  # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows (empty live window) softmax all-NEG_INF into a
    # uniform distribution over garbage; zero them instead.
    probs = jnp.where(valid.any(axis=-1)[:, None, None, None], probs, 0.0)
    out = jnp.einsum(
        "bgrs,bsgd->bgrd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, n_q, d).astype(q.dtype)


@jax.named_scope("layer/attn")
def latent_decode_attention(
    q: jax.Array,  # [B, n_q, c + r] — absorbed queries over latent rows
    cache: jax.Array,  # [L, B, S_max, c + r] — one latent row a token
    layer: jax.Array,  # scalar int32 — the layer whose rows are read
    valid_from: jax.Array,  # [B] int — first valid cache slot per row
    valid_to: jax.Array,  # scalar/[B] int — one past the last valid slot
    n_value: int,  # c: the leading columns of a row that are its value
    scale: float,
    use_kernel=None,  # None=by backend | bool | Mesh (shard_map the kernel)
) -> jax.Array:
    """Single-token decode attention of latent attention's ABSORBED form:
    every head scores the SAME rows of the STACKED cache's layer `layer`
    (the normed latent vector beside the shared roped key part) with its
    own absorbed query, and the weighted sum is taken over the rows' first
    `n_value` columns -> [B, n_q, c] (the value up-projection comes after,
    `transformer._attn_out`).  bf16 operands, fp32 accumulation and
    softmax, as `decode_attention`; no per-head key or value exists
    anywhere.

    On a TPU backend the Pallas kernel `latent_decode` (`ops/pallas/
    latent_attention.py`: the rows read in place and once for both
    products), elsewhere the XLA form below.  `use_kernel`: None, by the
    backend; a bool forces either; a MESH whose batch axes spread the rows
    over several devices — rows are independent, so the kernel is
    `shard_map`ped over (data, fsdp) and each device runs it on its own
    rows of the cache (the XLA form off a TPU backend, as on one device)."""
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form

    use_kernel, mesh = row_kernel_form(use_kernel)
    if use_kernel:
        from areal_tpu.ops.pallas.latent_attention import (
            latent_decode_kernel,
            latent_decode_kernel_sharded,
        )

        args = (q, cache, layer, jnp.asarray(valid_from, jnp.int32), valid_to)
        if mesh is not None:
            return latent_decode_kernel_sharded(
                *args, mesh, n_value=n_value, scale=scale)
        return latent_decode_kernel(*args, n_value=n_value, scale=scale)
    rows = jax.lax.dynamic_index_in_dim(cache, layer, axis=0, keepdims=False)
    b = q.shape[0]
    with jax.named_scope("latent_scores"):
        logits = jnp.einsum(
            "bhc,bsc->bhs", q, rows.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * scale  # [B, n_q, S] fp32
        idx = jnp.arange(rows.shape[1])
        valid = (idx[None, :] >= valid_from[:, None]) & (
            idx[None, :] < jnp.broadcast_to(valid_to, (b,))[:, None]
        )  # [B, S]
        logits = jnp.where(valid[:, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        # Empty live windows: exact zeros, as `decode_attention`.
        probs = jnp.where(valid.any(axis=-1)[:, None, None], probs, 0.0)
    with jax.named_scope("latent_out"):
        out = jnp.einsum(
            "bhs,bsc->bhc", probs.astype(rows.dtype), rows[..., :n_value],
            preferred_element_type=jnp.float32,
        )
    return out.astype(q.dtype)


@jax.named_scope("layer/attn")
def decode_attention_chunk(
    q: jax.Array,  # [B, Q, n_q, d] — Q consecutive new tokens per row
    k_cache: jax.Array,  # [B, S_max, n_kv, d]
    v_cache: jax.Array,  # [B, S_max, n_kv, d]
    valid_from: jax.Array,  # [B] int — first valid cache slot per row
    valid_to0: jax.Array,  # [B] int — one past query 0's last visible slot
    k_scale: "Optional[jax.Array]" = None,  # [B, S_max, n_kv]: int8 cache
    v_scale: "Optional[jax.Array]" = None,
) -> jax.Array:
    """Multi-query decode attention over a dense window: query i attends
    [valid_from, valid_to0 + i) — the causal extension of
    `decode_attention` to a chunk of Q consecutive positions (each sees
    the cache up to and including its own just-written slot).  Same
    GQA-grouped, bf16-operand/fp32-accumulate formulation.  No generation
    program calls it (the serving chunk packs such rows into
    `ragged_paged_attention` lanes); it stays as the arithmetic reference
    of the serving and speculative-decoding tests."""
    if k_scale is not None:
        from areal_tpu.ops.quant import kv_dequant

        k_cache = kv_dequant(k_cache, k_scale, q.dtype)
        v_cache = kv_dequant(v_cache, v_scale, q.dtype)
    b, nq_tok, n_q, d = q.shape
    n_kv = k_cache.shape[2]
    n_rep = n_q // n_kv
    qh = q.reshape(b, nq_tok, n_kv, n_rep, d)
    scale = d**-0.5
    logits = (
        jnp.einsum(
            "bqgrd,bsgd->bgqrs", qh, k_cache.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [B, n_kv, Q, n_rep, S]
    idx = jnp.arange(k_cache.shape[1])
    valid = (idx[None, None, :] >= valid_from[:, None, None]) & (
        idx[None, None, :]
        < (valid_to0[:, None] + jnp.arange(nq_tok)[None, :])[:, :, None]
    )  # [B, Q, S]
    logits = jnp.where(valid[:, None, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # Zero fully-masked (empty-window) rows: see decode_attention.
    probs = jnp.where(
        valid.any(axis=-1)[:, None, :, None, None], probs, 0.0
    )
    out = jnp.einsum(
        "bgqrs,bsgd->bqgrd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, nq_tok, n_q, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Ragged paged attention (block-paged KV pool, models/transformer.py
# PagedKVCache): the Pallas kernel on a TPU backend, the gather-based XLA
# form elsewhere.  Both read the STACKED pool at (layer, page).
# --------------------------------------------------------------------------


def clamp_page_table(page_table: jax.Array, n_pool: int) -> jax.Array:
    """The ONE sentinel rule for paged reads, shared by the Pallas
    kernel and the XLA gather form: unmapped entries (>= n_pool)
    clamp to the LAST pool page so every dereference is a legal index,
    and correctness comes from masking — pages are mapped contiguously
    from flat position 0, so any position addressed through a sentinel
    entry lies at or past the row's live window and the causal/ragged
    mask removes it.  Never rely on the clamped page's CONTENTS (it
    aliases whatever sequence owns that page)."""
    return jnp.minimum(page_table.astype(jnp.int32), n_pool - 1)


def paged_gather(
    pool: jax.Array,  # [L, P, ...] the stacked pool (or its scales)
    layer: jax.Array,  # int32 scalar
    page_table: jax.Array,  # [B, max_pages] int32 (sentinel >= P)
) -> jax.Array:
    """Gather each row's pages [B, max_pages, ...] of one layer from the
    stacked pool through the page table, indexed `layer * P + page` —
    the layer's pool is never sliced out of the stack.  Sentinel
    (unmapped) entries clamp to the last page (`clamp_page_table`) —
    their positions lie past every row's live window, so the attention
    mask removes them.  This reads each slot's MAPPED pages only (plus
    the clamped repeats for unmapped slots), not the whole pool."""
    n_layers, n_pool = pool.shape[:2]
    pt = layer.astype(jnp.int32) * n_pool + clamp_page_table(page_table, n_pool)
    return jnp.take(
        pool.reshape(n_layers * n_pool, *pool.shape[2:]), pt, axis=0
    )


@jax.named_scope("layer/attn")
def ragged_paged_attention(
    q: jax.Array,  # [T, n_q, d] — packed token stream (no batch/Q dims)
    k_pool: jax.Array,  # [L, P, ps, n_kv * d] — the STACKED pool
    v_pool: jax.Array,
    layer: jax.Array,  # int32 scalar — the layer whose pages are read
    page_table_tok: jax.Array,  # [T, max_pages] int32 — PER-TOKEN tables
    valid_to: jax.Array,  # [T] int — one past each token's window; 0 = dead
    k_scale: "Optional[jax.Array]" = None,  # [L, P, n_kv, ps]: int8 pool
    v_scale: "Optional[jax.Array]" = None,
    use_kernel=None,  # None = the platform picks | bool
    schedule=None,  # the kernel's `live_page_schedule`, made once a forward
) -> jax.Array:
    """Ragged paged attention over a PACKED token stream.

    The serving chunk's attention op: instead of a [n_slots, W] slab
    where every row pays W query lanes, the caller packs all live query
    lanes of the chunk — decode rows (1 lane), chunked-prefill /
    episode-observation rows (their granted slice), spec-verify rows
    (pending + drafts) — into one [T] stream.  Token t attends its own
    window [0, valid_to[t]) of the row it belongs to, addressed through
    its own (pre-gathered) page-table row.  Dead stream lanes carry
    valid_to == 0 and emit exact zeros.

    On a TPU backend this is the Pallas kernel
    (`ops/pallas/paged_attention.py`): it reads each lane's live pages in
    place from the stacked pool, dead lanes and pages past a window are
    not read at all.  Elsewhere — and where the caller says the operands
    are sharded over more than one device (`use_kernel=False`) — it is
    the XLA form below, the arithmetic reference of the tests: per-token
    windows gathered at `layer * P + page`, bf16 operands with fp32
    accumulation and an fp32 softmax (`decode_attention`).

    Returns [T, n_q, d] in q.dtype.
    """
    if use_kernel is None:
        from areal_tpu.base.distributed import is_tpu_backend

        use_kernel = is_tpu_backend()
    if use_kernel:
        from areal_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_kernel,
        )

        return ragged_paged_attention_kernel(
            q, k_pool, v_pool, layer, page_table_tok, valid_to,
            k_scale, v_scale, schedule,
        )
    t, _, d = q.shape
    n_kv = k_pool.shape[-1] // d
    layer = jnp.asarray(layer, jnp.int32)

    def window(pool):  # pages [T, mp, ps, n_kv*d] -> [T, mp*ps, n_kv, d]
        return paged_gather(pool, layer, page_table_tok).reshape(t, -1, n_kv, d)

    def scales(pool):  # pages [T, mp, n_kv, ps] -> [T, mp*ps, n_kv]
        if pool is None:
            return None
        pages = paged_gather(pool, layer, page_table_tok)
        return jnp.swapaxes(pages, 2, 3).reshape(t, -1, n_kv)

    k_cache, v_cache = window(k_pool), window(v_pool)
    ks, vs = scales(k_scale), scales(v_scale)
    # Q=1 decode formulation with T "rows": each packed token is its own
    # attention problem.  It zeroes empty-window rows, which
    # is exactly the dead-lane (valid_to == 0) contract.
    out = _decode_attention(
        q[:, None], k_cache, v_cache, jnp.zeros((t,), jnp.int32),
        jnp.asarray(valid_to, jnp.int32), k_scale=ks, v_scale=vs,
    )
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("causal",))
def _dispatch_ref(q, k, v, segment_ids, causal):
    return packed_attention_reference(q, k, v, segment_ids, causal=causal)


@jax.named_scope("layer/attn")
def packed_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    causal: bool = True,
    use_flash=None,  # None=auto | bool | Mesh (shard_map the kernel)
    window: Optional[int] = None,
    scope: Optional[str] = None,
    blocks=None,
) -> jax.Array:
    """Dispatch: Pallas flash kernel on TPU, dense reference elsewhere.
    A Mesh value runs the kernel under shard_map with the standard layout
    (batch over data/fsdp, heads over model) — the multi-chip flash path.
    `window`: a Python int, a query sees the last `window` keys of its
    sequence (None: all of them, the program it always was).  `scope`: an
    inner name under `layer/attn` (a mixed plan tells its kinds apart).
    `blocks`: (block ids, stream ids) of the block-causal mask
    (`make_packed_mask`), one device's kernel or the reference; None: the
    program it always was."""
    with inner_scope(scope):
        return _packed_attention(
            q, k, v, segment_ids, causal, use_flash, window, blocks)


def _packed_attention(
    q, k, v, segment_ids, causal, use_flash, window, blocks=None
):
    from jax.sharding import Mesh

    if isinstance(use_flash, Mesh):
        if blocks is not None:
            raise NotImplementedError(
                "the block-causal mask runs on one device's flash kernel: a "
                "mesh that shards the kernel is not built for it")
        from areal_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        return flash_attention_sharded(
            q, k, v, segment_ids, use_flash, causal=causal, window=window
        )
    if use_flash is None:
        from areal_tpu.base.distributed import is_tpu_backend

        use_flash = is_tpu_backend()
    if use_flash:
        from areal_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(
            q, k, v, segment_ids, causal=causal, window=window, blocks=blocks)
    return packed_attention_reference(
        q, k, v, segment_ids, causal=causal, window=window, blocks=blocks
    )


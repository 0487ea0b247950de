"""Lightning attention: the linear-attention mixer of minicpm_sala (three
layers in four), a recurrence with ONE CONSTANT decay a head.

Per head h of H, with a state S [d, d] kept in fp32:

    S_t = lambda_h S_{t-1} + k_t^T v_t         lambda_h = exp(-2^(-8 (h+1) / H))
    y_t = (q_t / sqrt(d)) S_t

before it q = rmsnorm_head(W_q x), k = rmsnorm_head(W_k x) (a [d] weight
each), v = W_v x, rope on q and k over the whole head; after it
o = W_o (sigmoid(W_g x) * rmsnorm_head(y)).  No conv, no activation on
q / k / v.  The decay is no parameter: Lightning Attention-2's slope rule,
the same in every layer (`decay_log`).

Two forms of the same recurrence:
- `lightning_forward` (training, `forward`, prefill): chunks of CHUNK
  tokens.  Inside a chunk the pairwise form (q k^T * lambda^(i-j)) v for
  all chunks at once; across chunks a `lax.scan` that carries S through
  [C, d] x [d, d] matmuls — the products that touch the fp32 state at
  HIGHEST precision (a default fp32 matmul on a TPU is one bf16 pass).
- `lightning_step` (decode): one token against the carried S, as fp32
  multiply-and-sum.

Packed rows: S restarts at every segment start.  Inside a chunk that is a
same-segment mask on the pairwise decays; across chunks the carried state
is dropped for every token whose segment is not the one the previous chunk
ended in (`linear_attention.gated_delta_chunked`'s scaffolding).  Pads
(segment 0) are a segment like any other: what they compute is never read.

Parameters (leaves of `params["blocks"]`, stacked [n_lightning_layers, ...]):
    lt_wq, lt_wk, lt_wv  [D, H * d]
    lt_wg                [D, H * d]      the output gate
    lt_q_norm, lt_k_norm [d]
    lt_norm              [d]             the output norm's weight
    lt_wo                [H * d, D]
"""

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.branches import (
    Branch,
    HybridLayoutError,
    Refusal,
    nbytes,
)
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.norms import apply_rotary, rms_norm

CHUNK = 128
Params = Dict[str, jax.Array]
_HIGHEST = jax.lax.Precision.HIGHEST

LIGHTNING_LEAVES = (
    "lt_wq", "lt_wk", "lt_wv", "lt_wg", "lt_q_norm", "lt_k_norm", "lt_norm",
    "lt_wo",
)


def init_lightning(cfg: ModelConfig, key: jax.Array, n: int, dense) -> Params:
    """`n` layers' leaves, the norms at one; `dense(key, shape, fan_in)` is
    the caller's matrix init."""
    D, W, d = cfg.hidden_dim, cfg.lightning_dim, cfg.lightning_head_dim
    ks = jax.random.split(key, 5)
    ones = jnp.ones((n, d), cfg.dtype)
    return {
        "lt_wq": dense(ks[0], (n, D, W), D),
        "lt_wk": dense(ks[1], (n, D, W), D),
        "lt_wv": dense(ks[2], (n, D, W), D),
        "lt_wg": dense(ks[3], (n, D, W), D),
        "lt_q_norm": ones, "lt_k_norm": ones, "lt_norm": ones,
        "lt_wo": dense(ks[4], (n, W, D), W),
    }


def decay_log(n_heads: int) -> jax.Array:
    """log lambda_h = -2^(-8 (h + 1) / H), [H] fp32: head 0 forgets within
    two tokens, the last head over 2^8."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / n_heads)


def _in_proj(h, blk: Params, cfg: ModelConfig, cos, sin):
    """normed h [..., S, D] -> q, k (normed a head, roped), v [..., S, H, d]
    in the compute type and the gate's pre-activation.  q is NOT scaled
    here: d ** -0.5 is taken in fp32 inside the recurrence (2 ** -3.5 is
    no power of two: a bf16 q would round it)."""
    with jax.named_scope("in_proj"):
        H, d = cfg.lightning_n_heads, cfg.lightning_head_dim
        lead = h.shape[:-1]
        q = rms_norm((h @ blk["lt_wq"]).reshape(*lead, H, d),
                     blk["lt_q_norm"], cfg.rms_norm_eps)
        k = rms_norm((h @ blk["lt_wk"]).reshape(*lead, H, d),
                     blk["lt_k_norm"], cfg.rms_norm_eps)
        v = (h @ blk["lt_wv"]).reshape(*lead, H, d)
        q, k = apply_rotary(q, k, cos, sin)
        return q, k, v, h @ blk["lt_wg"]


def _out(y, gate, blk: Params, cfg: ModelConfig):
    """W_o (sigmoid(gate) * rmsnorm_head(y)): y [..., H, d] fp32."""
    with jax.named_scope("out_norm_proj"):
        lead = y.shape[:-2]
        yn = rms_norm(y, blk["lt_norm"].astype(jnp.float32), cfg.rms_norm_eps)
        yn = yn.reshape(*lead, cfg.lightning_dim)
        out = (jax.nn.sigmoid(gate.astype(jnp.float32)) * yn).astype(gate.dtype)
        return out @ blk["lt_wo"]


def lightning_chunked(
    q: jax.Array,  # [B, S, H, d], unscaled
    k: jax.Array,  # [B, S, H, d]
    v: jax.Array,  # [B, S, H, d]
    segment_ids: jax.Array,  # [B, S]
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """The constant-decay recurrence over packed rows in chunks ->
    (y [B, S, H, d] fp32, the state after each row's last token [B, H, d, d]
    fp32).  The operands keep their type into the matmuls (bf16 products
    are exact in the fp32 accumulator); every decay and the state are fp32."""
    b, s, h, d = q.shape
    pad = -s % chunk
    if pad:
        # Whole chunks: zero tokens of a segment of their own IN FRONT, so
        # that the scan's last carry stays the state after the row's last
        # token.
        def zpad(x):
            return jnp.pad(x, ((0, 0), (pad, 0), (0, 0), (0, 0)))

        q, k, v = zpad(q), zpad(k), zpad(v)
        segment_ids = jnp.pad(
            segment_ids, ((0, 0), (pad, 0)), constant_values=-2)
    n = (s + pad) // chunk
    g = decay_log(h)  # [H]

    def chunks(x):  # [B, S, H, d] -> [B, H, N, C, d]
        return jnp.moveaxis(x.reshape(b, n, chunk, h, d), 3, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    seg = segment_ids.reshape(b, 1, n, chunk)
    idx = jnp.arange(chunk)
    same = seg[..., :, None] == seg[..., None, :]  # [B, 1, N, C, C]
    tril = idx[:, None] >= idx[None, :]
    gh = g[:, None, None]  # [H, 1, 1]
    decay = jnp.where(
        tril, jnp.exp(gh * jnp.maximum(idx[:, None] - idx[None, :], 0)), 0.0
    )  # [H, C, C]
    prev_last = jnp.concatenate(
        [jnp.full((b, 1, 1), -1, seg.dtype), seg[:, :, :-1, -1]], axis=2)
    carry_ok = (seg == prev_last[..., None]).astype(jnp.float32)  # [B,1,N,C]
    same_as_last = (seg == seg[..., -1:]).astype(jnp.float32)

    scale = d**-0.5
    qk = jnp.einsum(
        "bhnid,bhnjd->bhnij", qc, kc, preferred_element_type=jnp.float32)
    a = qk * (scale * decay[None, :, None]) * same
    y_intra = jnp.einsum(
        "bhnij,bhnjd->bhnid", a.astype(v.dtype), vc,
        preferred_element_type=jnp.float32)
    # What the incoming state gives token i: lambda^(i+1) q_i S_prev.
    g_in = jnp.exp(g[:, None] * (idx + 1)[None, :])  # [H, C]
    q_in = qc.astype(jnp.float32) * (
        scale * g_in[None, :, None, :, None] * carry_ok[..., None])
    # What token j leaves in the outgoing state: lambda^(C-1-j) k_j^T v_j.
    g_out = jnp.exp(g[:, None] * (chunk - 1 - idx)[None, :])  # [H, C]
    # fp32 at HIGHEST: a decayed key rounded to the operands' type would
    # put a bf16 rounding into every term of the fp32 state.
    k_out = kc.astype(jnp.float32) * (
        g_out[None, :, None, :, None] * same_as_last[..., None])
    kv = jnp.einsum(
        "bhncd,bhnce->bhnde", k_out, vc.astype(jnp.float32),
        precision=_HIGHEST)
    keep = jnp.exp(g * chunk)[None, :, None] * carry_ok[..., -1]  # [B, H, N]

    def body(state, xs):
        q_i, kv_i, keep_i = xs
        y_i = jnp.einsum("bhcd,bhde->bhce", q_i, state, precision=_HIGHEST)
        return state * keep_i[..., None, None] + kv_i, y_i

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q_in, kv, keep))
    state, y_inter = jax.lax.scan(
        body, jnp.zeros((b, h, d, d), jnp.float32), xs)
    y = y_intra + jnp.moveaxis(y_inter, 0, 2)  # [B, H, N, C, d]
    y = jnp.moveaxis(y, 1, 3).reshape(b, s + pad, h, d)
    return y[:, pad:], state


@jax.named_scope("layer/lightning")
def lightning_forward(
    h: jax.Array,  # [B, S, D] normed block input
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    with_state: bool = False,
):
    """-> y [B, S, D]; `with_state` (prefill) adds the state after each
    row's last token [B, H, d, d] fp32."""
    q, k, v, gate = _in_proj(h, blk, cfg, cos, sin)
    with jax.named_scope("recurrence"):
        y, state = lightning_chunked(q, k, v, segment_ids)
    out = _out(y, gate, blk, cfg)
    return (out, state) if with_state else out


def lightning_step_jnp(state, q, k, v):
    """One token -> (state, y): state [B, H, d, d] fp32, q, k, v [B, H, d];
    fp32 multiply-and-sum, no matmul pass rounds the state."""
    h, d = q.shape[-2:]
    lam = jnp.exp(decay_log(h))[:, None, None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    state = state * lam + kf[..., :, None] * vf[..., None, :]
    y = jnp.sum(state * (q.astype(jnp.float32) * d**-0.5)[..., None], axis=-2)
    return state, y


@jax.named_scope("layer/lightning")
def lightning_step(
    h: jax.Array,  # [B, 1, D]
    blk: Params,
    cfg: ModelConfig,
    states: jax.Array,  # [n_lightning, B, H, d, d] fp32
    li,  # this layer's index into it
    cos: jax.Array,  # [B, 1, d/2...] the token's rotary table
    sin: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One decode token per row -> (y [B, 1, D], states), layer `li`
    stepped in place (XLA fuses the update into the dynamic-update-slice;
    the read of S for y is the same pass)."""
    q, k, v, gate = _in_proj(h, blk, cfg, cos, sin)
    with jax.named_scope("recurrence"):
        state = jax.lax.dynamic_index_in_dim(states, li, axis=0, keepdims=False)
        state, y = lightning_step_jnp(state, q[:, 0], k[:, 0], v[:, 0])
        states = jax.lax.dynamic_update_index_in_dim(states, state, li, axis=0)
    return _out(y[:, None], gate, blk, cfg), states


# The kind's record (`models/branches.py`).


def _packed(ctx, h, blk):
    if not ctx.with_state:
        return lightning_forward(
            h, blk, ctx.cfg, ctx.segment_ids, ctx.cos, ctx.sin), {}
    out, state = lightning_forward(
        h, blk, ctx.cfg, ctx.segment_ids, ctx.cos, ctx.sin, with_state=True)
    return out, {"state": state}


def _step(ctx, h, blk, cache, li):
    out, states = lightning_step(
        h, blk, ctx.cfg, cache.state, li, ctx.cos, ctx.sin)
    return out, dataclasses.replace(cache, state=states), {}


_CACHE = {  # a state and no tail
    "state": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.lightning_n_heads, cfg.lightning_head_dim,
         cfg.lightning_head_dim), jnp.float32),
}


def _matmul_params(cfg: ModelConfig) -> int:
    """ONE Lightning-attention layer's matmul parameters (q, k, v, the
    gate, the output projection), its recurrence counted as the 2 * d * d
    multiply-adds a head's state takes per token (add k^T v, read q S)."""
    w = cfg.lightning_dim
    return 5 * cfg.hidden_dim * w + 2 * w * cfg.lightning_head_dim


# minicpm_sala's two mixers refuse in the same words: the block-sparse
# kind's record (`transformer.py`) holds this one too.
SALA_REFUSAL = Refusal(
    HybridLayoutError,
    "block-sparse attention beside Lightning attention (minicpm_sala) runs "
    "under data and fsdp sharding only: the selection's compressed keys and "
    "the Lightning state are not split over `model`, neither the selection "
    "nor the chunked recurrence has a ring over a split sequence, and the "
    "pipeline's stage scans one kind of layer (PERF.md section 7)",
    "block-sparse attention beside Lightning attention (minicpm_sala) "
    "generates on the static decode program only: the serving plane's "
    "ragged paged attention has no selection (compressed keys beside the "
    "pages, chosen pages a lane), and a Lightning layer's state has no slot "
    "beside the pool yet (PERF.md section 7)",
)

# `remat_alone`: at rows of 13 k tokens a Lightning or block-sparse mixer's
# residuals (2.5 GB: the chunked recurrence's fp32 blocks) and the MLP's
# (1.5 GB at a width of 16,384) do not fit beside the state TOGETHER; a
# branch at a time the backward holds one of them, for one more saved
# [B, S, D] a layer and no more recompute.
BRANCH = Branch(
    leaves=LIGHTNING_LEAVES,
    init=init_lightning,
    cache=_CACHE,
    packed=_packed,
    step=_step,
    refusal=SALA_REFUSAL,
    remat_alone=True,
    matmul_params=_matmul_params,
    cache_stats=lambda cfg, cache, batch, s_max: {
        "lightning_state_bytes": nbytes(cache.state)},
)

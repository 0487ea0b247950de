"""The TPU-native transformer: pure-functional, scan-over-layers, packed rows.

Capability parity: realhf/impl/model/nn/real_llm_api.py (`ReaLModel`) +
real_llm_base.py (blocks, heads) — re-designed for XLA:

- Parameters are a plain pytree with per-layer tensors STACKED on a leading
  axis, so the forward pass is one `lax.scan` over layers: O(1) compile time
  in depth, and the natural substrate for pipeline stages.
- Batches are packed rows [B, S]: each row concatenates sequences, delimited
  by `segment_ids` (0 = pad).  Static shapes; attention is causal-within-
  segment (see areal_tpu/ops/attention.py).
- No device/layout logic here: sharding is applied by the engines via
  `jax.sharding` rules over this pytree (areal_tpu/parallel/sharding.py).
- `is_critic` swaps the LM head for a scalar value head
  (reference: real_llm_base.py:358-453).
- A layer is a mixer and an MLP, two residual branches — or, under
  `cfg.layer_pattern` (nemotron_h), ONE branch of one kind (a Mamba-2
  mixer `models/mamba.py`, the mixture of experts, or attention), each
  kind's leaves stacked over its own layers (`_pattern_blocks`).

Functions:
    init_params(cfg, key)                                  -> params
    forward(params, cfg, tokens, segment_ids[, positions]) -> logits/values
    init_kv_cache(cfg, b, s_max)                           -> cache
    prefill(params, cfg, tokens, segment_ids, cache)
    decode_step(params, cfg, tokens, positions, cache, slot, valid_from)
        — the static decode program's step over a dense window (per-head
          k/v, or one latent row a token: `cfg.is_latent`)
    init_paged_kv_cache(cfg, n_pages, page_size)           -> pool
    decode_step_ragged_paged(params, cfg, tokens, positions, pool,
                             page_table, row_of)
        — the serving plane's step over a packed token stream
"""

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from areal_tpu.models.config import DENSE_PREFIX, ModelConfig
from areal_tpu.models.linear_attention import (
    LINEAR_LEAVES,
    init_linear_attn,
    linear_attn_forward,
    linear_attn_step,
)
from areal_tpu.models.mamba import SSM_LEAVES, init_ssm, ssm_forward, ssm_step
from areal_tpu.ops.attention import (
    decode_attention,
    latent_decode_attention,
    packed_attention,
    ragged_paged_attention,
    repeat_kv,
)
from areal_tpu.ops.norms import apply_rotary, rms_norm, rope_cos_sin

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random init (truncated-normal fan-in scaling), layer-stacked."""
    dtype = cfg.dtype
    k_embed, k_blocks, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return (
            jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * (fan_in**-0.5)
        ).astype(dtype)

    # The SCANNED layers: all of them but `first_k_dense` leading ones.
    L, D, F = cfg.n_scan_layers, cfg.hidden_dim, cfg.intermediate_dim
    # A hybrid stack keeps ONE leading stack axis on every leaf (what the
    # sharding rules, the hand-back and the references read): the norms and
    # the MLP of all L layers, the attention leaves of its LA = n_periods
    # full layers, the `la_*` leaves of its L - LA linear layers.  A period
    # of one has LA = L.
    LA = cfg.n_periods
    # A (1 + w) norm starts at w = 0, a plain one at w = 1: scale one.
    norm_init = jnp.zeros if cfg.rms_norm_offset else jnp.ones
    ks = jax.random.split(k_blocks, 8)

    def attn_leaves(n, ks):
        """The attention leaves of `n` stacked layers."""
        if cfg.is_latent:
            H, rq, rkv = cfg.n_q_heads, cfg.q_lora_rank, cfg.kv_lora_rank
            kl = jax.random.split(ks[5], 3)
            return {
                "wq_a": dense(ks[0], (n, D, rq), D),
                "q_a_norm": norm_init((n, rq), dtype),
                "wq_b": dense(kl[0], (n, rq, cfg.q_dim), rq),
                "wkv_a": dense(ks[1], (n, D, cfg.latent_dim), D),
                "kv_a_norm": norm_init((n, rkv), dtype),
                "wk_b": dense(kl[1], (n, rkv, H * cfg.qk_nope_head_dim), rkv),
                "wv_b": dense(kl[2], (n, rkv, H * cfg.v_head_dim), rkv),
                "wo": dense(ks[3], (n, cfg.q_dim, D), cfg.q_dim),
            }
        out = {
            "wq": dense(ks[0], (n, D, cfg.q_dim), D),
            "wk": dense(ks[1], (n, D, cfg.kv_dim), D),
            "wv": dense(ks[2], (n, D, cfg.kv_dim), D),
            "wo": dense(ks[3], (n, cfg.q_dim, D), cfg.q_dim),
        }
        if cfg.qkv_bias:
            out["bq"] = jnp.zeros((n, cfg.q_dim), dtype)
            out["bk"] = jnp.zeros((n, cfg.kv_dim), dtype)
            out["bv"] = jnp.zeros((n, cfg.kv_dim), dtype)
        if cfg.qk_norm and cfg.qk_norm_per_head:
            out["q_norm"] = norm_init((n, cfg.head_dim), dtype)
            out["k_norm"] = norm_init((n, cfg.head_dim), dtype)
        elif cfg.qk_norm:
            out["q_norm"] = jnp.ones((n, cfg.q_dim), dtype)
            out["k_norm"] = jnp.ones((n, cfg.kv_dim), dtype)
        if cfg.attn_gate:
            out["wqg"] = dense(ks[5], (n, D, cfg.q_dim), D)
        return out

    # Layers with the mixture of experts: all the scanned ones, or a
    # pattern's 'E' layers.
    LM = cfg.n_moe_layers
    if cfg.is_pattern:
        # ONE norm a layer; each kind's leaves stacked over its own layers.
        blocks = {
            "ln1": norm_init((L, D), dtype),
            **attn_leaves(cfg.n_attn_layers, ks),
            **init_ssm(cfg, ks[6], cfg.n_ssm_layers, dense),
        }
    else:
        blocks = {
            "ln1": norm_init((L, D), dtype),
            **attn_leaves(LA, ks),
            "ln2": norm_init((L, D), dtype),
        }
    if cfg.first_k_dense:
        # The leading dense layers: their own leaves, stacked [K, ...]
        # under `dense_*`, the MLP at the dense width.
        K = cfg.first_k_dense
        kd = jax.random.split(jax.random.fold_in(k_blocks, 1), 9)
        lead = {
            "ln1": norm_init((K, D), dtype),
            **attn_leaves(K, kd),
            "ln2": norm_init((K, D), dtype),
            "wg": dense(kd[6], (K, D, F), D),
            "wu": dense(kd[7], (K, D, F), D),
            "wd": dense(kd[8], (K, F, D), F),
        }
        blocks.update({DENSE_PREFIX + n: w for n, w in lead.items()})
    if cfg.is_hybrid:
        blocks.update(init_linear_attn(cfg, ks[6], L - LA, dense))
    if cfg.norm_type == "layernorm":
        blocks["ln1_b"] = jnp.zeros((L, D), dtype)
        blocks["ln2_b"] = jnp.zeros((L, D), dtype)
    if cfg.proj_bias:
        blocks["bo"] = jnp.zeros((L, D), dtype)
        blocks["bproj"] = jnp.zeros((L, D), dtype)
        if not cfg.mlp_gated:
            blocks["bfc"] = jnp.zeros((L, F), dtype)
    if cfg.is_moe:
        E, FM = cfg.n_experts, cfg.moe_intermediate_dim
        km = jax.random.split(ks[4], 4)
        blocks["router"] = dense(km[0], (LM, D, cfg.router_width), D)
        if cfg.moe_score_func == "sigmoid":
            # Not trained; drawn as the configuration says (zeros unless it
            # states a draw: `router_bias_init_std`).
            blocks["router_bias"] = (
                cfg.router_bias_init_std * jax.random.normal(
                    jax.random.fold_in(km[0], 1), (LM, cfg.router_width)
                )
            ).astype(dtype)
        if cfg.mlp_gated:
            blocks["wg"] = dense(km[1], (LM, E, D, FM), D)
        blocks["wu"] = dense(km[2], (LM, E, D, FM), D)
        blocks["wd"] = dense(km[3], (LM, E, FM, D), FM)
        if cfg.shared_expert_dim:
            FS = cfg.shared_expert_dim
            kx = jax.random.split(ks[7], 4)
            if cfg.mlp_gated:
                blocks["ws_g"] = dense(kx[0], (LM, D, FS), D)
            blocks["ws_u"] = dense(kx[1], (LM, D, FS), D)
            blocks["ws_d"] = dense(kx[2], (LM, FS, D), FS)
            if cfg.shared_expert_gated:
                blocks["ws_gate"] = dense(kx[3], (LM, D, 1), D)
    elif not cfg.is_pattern:
        km = jax.random.split(ks[4], 3)
        blocks["wg"] = dense(km[0], (L, D, F), D)
        if cfg.mlp_gated:
            blocks["wu"] = dense(km[1], (L, D, F), D)
        blocks["wd"] = dense(km[2], (L, F, D), F)

    # The table of an untied MoE model is drawn at UNIT variance: a lookup
    # has fan-in one (a one-hot input).  A router reads the residual stream;
    # with rows of RMS D**-0.5 (0.022) beside attention outputs of 0.2-0.5
    # the stream of every position is its context's average, and a random
    # router sends every row of a batch to the same few experts (17 of 64 at
    # 8 rows on the chip, where a load-balanced router touches 42; PERF.md,
    # PR 26).  At unit variance the token dominates and random routing
    # spreads as trained routing does.  Dense models' work does not depend
    # on their data, and a tied table is also the head: both keep D**-0.5.
    embed_fan_in = 1 if cfg.is_moe and not cfg.tied_embeddings else D
    params: Params = {
        "embed": dense(k_embed, (cfg.vocab_size, D), embed_fan_in),
        "blocks": blocks,
        "final_ln": norm_init((D,), dtype),
    }
    if cfg.norm_type == "layernorm":
        params["final_ln_b"] = jnp.zeros((D,), dtype)
    if cfg.pos_emb == "learned":
        params["pos_embed"] = dense(
            jax.random.fold_in(k_embed, 1),
            (cfg.max_position_embeddings, D),
            D,
        )
    if cfg.is_critic:
        params["value_head"] = dense(k_head, (D, 1), D)
    elif not cfg.tied_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size), D)
    return params


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _act(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.hidden_act == "silu":
        return jax.nn.silu(x)
    if cfg.hidden_act == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if cfg.hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if cfg.hidden_act == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown hidden_act {cfg.hidden_act!r}")


def _norm(
    x: jax.Array, w: jax.Array, b: Optional[jax.Array], cfg: ModelConfig
) -> jax.Array:
    if cfg.norm_type == "rms":
        scale = w.astype(jnp.float32) + 1.0 if cfg.rms_norm_offset else w
        return rms_norm(x, scale, cfg.rms_norm_eps)
    # LayerNorm (gpt2): mean-centered, with bias, fp32 accumulation.
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
    out = out * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(dtype)


@jax.named_scope("embed")
def _embed(
    params: Params, cfg: ModelConfig, tokens: jax.Array, positions: jax.Array
) -> jax.Array:
    # mode="clip", not the jit default "fill": out-of-vocab ids (the pad /
    # eos sentinels sit past the table in some configs) must embed to
    # FINITE garbage.  A NaN here is not locally harmless — pad lanes write
    # their k/v into cache pages, and masked attention still reads them as
    # weight*NaN = NaN, poisoning every later query on the page.
    x = jnp.take(params["embed"], tokens, axis=0, mode="clip")
    if cfg.embed_scale:  # gemma normalizer, computed in fp32
        x = (x.astype(jnp.float32) * (cfg.hidden_dim**0.5)).astype(x.dtype)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0, mode="clip")
    return x


def positions_from_segments(segment_ids: jax.Array) -> jax.Array:
    """Within-segment positions for packed rows.

    Segments are contiguous runs in each row; position resets to 0 at every
    segment boundary.  [B, S] int32.
    """
    s = segment_ids.shape[-1]
    idx = jnp.arange(s, dtype=jnp.int32)
    prev = jnp.pad(segment_ids[..., :-1], ((0, 0), (1, 0)), constant_values=-1)
    is_start = segment_ids != prev
    start_idx = jnp.where(is_start, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, start_idx, axis=-1)
    return idx - seg_start


# Device-side names (PERF.md §3): one `jax.named_scope` per part of the
# model, trace-time metadata only.  Under the layer scan one scope serves
# all layers, so a profile's `op_name` paths read `.../layer/mlp/...`
# whatever the depth; forward, recomputed forward and backward of a scope
# are told apart by what JAX itself puts in the path (`jvp(...)`,
# `checkpoint` / `rematted_computation`, `transpose(jvp(...))`).


@jax.named_scope("layer/attn_out")
def _attn_out(
    a: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    gate: Optional[jax.Array] = None,
    absorbed: bool = False,
) -> jax.Array:
    if gate is not None:  # qwen3_next: o_proj(attn * sigmoid(gate))
        a = a * jax.nn.sigmoid(gate)
    if absorbed:
        # Latent attention's absorbed decode step: `a` is the weighted sum
        # of latent rows per head [.., H * kv_lora_rank]; the value
        # up-projection comes after it.
        with jax.named_scope("absorb_out"):
            h, c = cfg.n_q_heads, cfg.kv_lora_rank
            a = jnp.einsum(
                "...hc,chv->...hv",
                a.reshape(*a.shape[:-1], h, c),
                blk["wv_b"].reshape(c, h, cfg.v_head_dim),
            ).reshape(*a.shape[:-1], cfg.q_dim)
    y = a @ blk["wo"]
    if cfg.proj_bias:
        y = y + blk["bo"]
    return y


@jax.named_scope("final_norm")
def _final_norm(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return _norm(x, params["final_ln"], params.get("final_ln_b"), cfg)


@jax.named_scope("layer/mlp")
def _mlp_dense(h: jax.Array, blk: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp_gated:
        gate = _act(h @ blk["wg"], cfg)
        out = (gate * (h @ blk["wu"])) @ blk["wd"]
        if cfg.proj_bias:
            out = out + blk["bproj"]
        return out
    # Plain fc -> act -> proj (gpt2).
    hmid = h @ blk["wg"]
    if cfg.proj_bias:
        hmid = hmid + blk["bfc"]
    out = _act(hmid, cfg) @ blk["wd"]
    if cfg.proj_bias:
        out = out + blk["bproj"]
    return out


def _moe_route(x: jax.Array, blk: Params, cfg: ModelConfig):
    """Router: top-k weights/indices + switch-style load-balancing aux.

    fp32 throughout: softmax over ALL experts, then top-k.  The weights are
    renormalised to sum to one only where the architecture says so
    (`moe_norm_topk`: mixtral yes, olmoe no).

    One expert-parallel rank's share (`cfg.expert_share`): the router
    scores all `router_width` experts and the aux loss is over all of
    them, but `top_idx` and `one_hot` come back in LOCAL numbering over the
    `n_experts` held here — a choice that fell to an expert held elsewhere
    is index `n_experts` (sorts last, one-hot all zero), so every dispatch
    below computes this rank's part of the sum and nothing for the rest.
    What that costs is the dispatch's: `_experts_dense` and `_experts_topk`
    pass over every (row, choice) pair, and so does `_experts_grouped` in a
    decode step; over packed rows and in prefill it touches
    `expert_slab_rows` of them — twice a balanced router's — and the rest
    only where more than that many are held (its overflow, never a drop)."""
    router_logits = (x.astype(jnp.float32)) @ blk["router"].astype(jnp.float32)  # [T, E]
    if cfg.moe_score_func == "sigmoid":
        return _moe_route_sigmoid(router_logits, blk, cfg)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.n_experts_per_tok)  # [T, k]
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if cfg.expert_share:
        width = cfg.router_width
        load = jnp.zeros((width,), probs.dtype).at[top_idx.reshape(-1)].add(
            1.0 / x.shape[0]
        )
        aux = width * jnp.sum(load * jnp.mean(probs, axis=0))
        top_idx = _local_numbering(top_idx, cfg)
        one_hot = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=probs.dtype)
        return top_w, top_idx, one_hot, aux
    one_hot = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=probs.dtype)  # [T,k,E]
    # Load-balancing aux loss (switch-style): E * sum_e f_e * P_e.
    load = jnp.mean(one_hot.sum(axis=1), axis=0)  # fraction routed per expert
    importance = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(load * importance)
    return top_w, top_idx, one_hot, aux


def _local_numbering(top_idx: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A rank's share: the chosen experts numbered over the `n_experts`
    held here; a choice held elsewhere is `n_experts` (sorts last, one-hot
    all zero).  Sorting last is what the grouped dispatch's slab rests on:
    under its stable sort the held pairs are the first
    `sum(group_sizes)` of the order, in the positions they always had."""
    local = top_idx - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_experts)
    return jnp.where(held, local, cfg.n_experts)


def _moe_route_sigmoid(router_logits: jax.Array, blk: Params, cfg: ModelConfig):
    """`_moe_route` for sigmoid scores with a choice bias (deepseek_v3
    `noaux_tc`, one group): the top k are chosen by score + `router_bias`
    and weighted by their SCORE, renormalised where `moe_norm_topk` says
    so, then scaled by `moe_routed_scale`.  The bias takes no gradient (it
    reaches the indices only) and there is no auxiliary loss.  A rank's
    share numbers its choices as `_moe_route` does."""
    scores = jax.nn.sigmoid(router_logits)
    bias = jax.lax.stop_gradient(blk["router_bias"]).astype(jnp.float32)
    _, top_idx = jax.lax.top_k(scores + bias, cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    if cfg.moe_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.moe_routed_scale
    if cfg.expert_share:
        top_idx = _local_numbering(top_idx, cfg)
    one_hot = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=scores.dtype)
    return top_w, top_idx, one_hot, jnp.zeros((), jnp.float32)


def _experts_dense(x, top_w, top_idx, one_hot, blk: Params, cfg: ModelConfig):
    """Numerics-oracle MoE: full expert compute + weight masking.

    Every token runs through a dense einsum over ALL experts, then results
    are combined with the (sparse) router weights — E/k times the FLOPs of
    real dispatch, but perfectly static and exactly equal to un-dropped
    top-k routing.  Reference semantics: realhf/impl/model/modules/moe/.
    """
    with jax.named_scope("experts"):
        # All-expert compute: [E, T, F] einsums.
        if cfg.mlp_gated:
            gate = jax.nn.silu(jnp.einsum("td,edf->etf", x, blk["wg"]))
            hid = gate * jnp.einsum("td,edf->etf", x, blk["wu"])
        else:  # down(act(up(x))): two matrices an expert
            hid = _act(jnp.einsum("td,edf->etf", x, blk["wu"]), cfg)
        expert_out = jnp.einsum("etf,efd->etd", hid, blk["wd"])  # [E,T,D]
    with jax.named_scope("combine"):
        comb = jnp.einsum("tk,tke->te", top_w, one_hot)  # [T, E]
        return jnp.einsum(
            "te,etd->td", comb.astype(expert_out.dtype), expert_out
        )


def _experts_topk(x, top_w, top_idx, one_hot, blk: Params, cfg: ModelConfig):
    """Capacity-based top-k dispatch (GShard-style): expert matmuls run on
    [E, C, D] gathered slots, C = ceil(T*k/E * capacity_factor), so FLOPs
    scale with top-k rather than E.  First-choice assignments claim
    capacity before second choices; tokens over capacity are dropped
    (their combine weight is zero), matching the reference's token-choice
    router with capacity (realhf/impl/model/modules/moe/experts.py).  The
    expert axis of the dispatch einsums shards over the mesh (see
    parallel/sharding.py moe rules) — GSPMD inserts the all-to-alls.
    """
    import math

    T = x.shape[0]
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    cap = max(int(math.ceil(T * k / E * cfg.moe_capacity_factor)), 1)

    with jax.named_scope("dispatch"):
        # Queue position of each (choice slot, token) in its expert,
        # choice-slot-major so first choices win capacity.
        sel = one_hot.transpose(1, 0, 2).reshape(k * T, E)  # [k*T, E]
        pos = jnp.cumsum(sel, axis=0) - sel  # position BEFORE this entry
        keep = sel * (pos < cap)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype)  # [kT,E,C]
        disp_k = keep[..., None] * slot  # [k*T, E, C]
        disp = disp_k.reshape(k, T, E, cap)
        dispatch = disp.sum(axis=0)  # [T, E, C] 0/1
        combine = jnp.einsum(
            "tk,ktec->tec", top_w.astype(x.dtype), disp.astype(x.dtype)
        )  # [T, E, C]
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # [E, C, D]
    with jax.named_scope("experts"):
        if cfg.mlp_gated:
            gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, blk["wg"]))
            hid = gate * jnp.einsum("ecd,edf->ecf", xe, blk["wu"])
        else:
            hid = _act(jnp.einsum("ecd,edf->ecf", xe, blk["wu"]), cfg)
        ye = jnp.einsum("ecf,efd->ecd", hid, blk["wd"])  # [E, C, D]
    with jax.named_scope("combine"):
        return jnp.einsum("tec,ecd->td", combine, ye)


# A share's slab over the pairs a balanced router sends the rank.  Two: the
# slab's rows are paid for on every call and the overflow only when it is
# taken, and the fullest rank of a router trained under the load-balancing
# loss (or the choice bias) stays well under twice the mean, so the
# overflow is for the batch that is out of the ordinary, not for a tail of
# every batch.  A multiple of 512 rows keeps the ragged kernels' row tiles
# whole.
_SLAB_OVER_BALANCED = 2
_SLAB_ROW_MULTIPLE = 512


def expert_slab_rows(cfg: ModelConfig, pairs: int) -> int:
    """Of `pairs` (row, choice) pairs, how many the grouped dispatch
    gathers before it asks whether more are held here (`_experts_grouped`):
    all of them unless `cfg` is a rank's share of under half the router."""
    if not cfg.expert_share:
        return pairs
    rows = -(-_SLAB_OVER_BALANCED * pairs * cfg.n_experts // cfg.router_width)
    return min(pairs, -(-rows // _SLAB_ROW_MULTIPLE) * _SLAB_ROW_MULTIPLE)


def expert_slabs_run(slab: int, pairs: int, held: jax.Array) -> jax.Array:
    """How many slabs of `slab` rows the grouped dispatch runs over `pairs`
    sorted pairs of which the first `held` are held here: the first always,
    then as many as hold a held pair."""
    return jnp.clip(-(-held // slab), 1, -(-pairs // slab))


def _experts_grouped(
    x, top_w, top_idx, one_hot, blk: Params, cfg: ModelConfig, layer=None,
    kernel: bool = False,
):
    """Dropless grouped-GEMM dispatch (the default): tokens sorted by
    expert, expert matmuls ride `jax.lax.ragged_dot` — XLA:TPU's native
    megablox-style ragged kernel (a Mosaic custom call), which tiles each
    expert's contiguous row group onto the MXU; its time follows the
    groups that have rows.  No [E, C, D] token buffers are built.  What
    the kernel's WEIGHT operand costs depends on the caller: a custom
    call's operand must be a buffer of its own, so under a layer scan
    that slices `blk` out of the stacked [L, E, in, out] leaves (train,
    `forward`, `prefill`) XLA copies the layer's [E, in, out] slice before
    each kernel — noise beside thousands of rows, but 3 x 268 MB a
    layer-step at OLMoE's widths when a decode step has 8.  So the decode
    program passes `layer` (the scan's index) and `blk` = the STACKED
    leaves: they are viewed as [L*E, in, out] (leading contiguous axes: a
    bitcast) and the group sizes are zero outside [layer*E, (layer+1)*E),
    which hands the kernel the parameter's own buffer.  Same rows through
    the same experts either way (`expert_leaves_in_place`).  `kernel`
    (decode, stacked leaves): the Pallas kernel `grouped_decode_matmul` in
    `ragged_dot`'s place — XLA's kernel tiles by the divisors of the two
    weight dimensions and streams a [2,688, 1,856] expert at a ninth of
    the bandwidth (`ops/pallas/grouped_matmul.py`).

    Expert FLOPs are exactly 3·T·k·D·F — proportional to TOKENS, where
    the dense oracle pays E/k× that and capacity dispatch pays
    capacity_factor× plus GShard's one-hot dispatch einsums (T·E·C·D
    each, quadratic in T).  No token is ever dropped, so this matches
    the dense oracle bit-for-bit up to matmul rounding.  The TPU
    equivalent of the reference's grouped GEMM
    (realhf/impl/model/utils/moe.py, tests/cpp_extensions/
    test_grouped_gemm.py:149).

    Under expert-parallel meshes the stacked expert weights are sharded
    over fsdp (parallel/sharding.py moe rules); GSPMD resolves
    ragged_dot by gathering the expert dim — ZeRO-style weight
    gathering, the right trade below ~100B total expert bytes.  True
    token all-to-all EP stays on `moe_dispatch="topk"`.

    One expert-parallel rank's share (`cfg.expert_share`) holds an eighth
    of the pairs, say, and they are the FIRST `sum(group_sizes)` of the
    stable order (`_local_numbering`).  On the unstacked path (`layer` is
    None: the gradient program, `forward`, `prefill`) it therefore runs on
    a slab of the order's first `expert_slab_rows` pairs — twice what a
    balanced router sends here — and not on all T*k: same rows at the same
    offsets in the same groups, and what is left out of the scatter-add
    were exact zeros.  Pairs held past the slab go through the same path a
    slab at a time, every group's sizes cut to the slab's window, and their
    sum is added (`_grouped_slabs`): a router that sends this rank more
    than twice its share costs as many slabs as hold its pairs, never a
    dropped pair.  The first slab stays OUTSIDE the loop: the benchmark's
    readers find a program's ragged kernels by the row count of the scoped
    activation product under `layer/mlp/experts`
    (`benchmark/metrics/_moe.py`).  A decode step (stacked leaves, a few
    hundred pairs: latency, not bytes) keeps every pair on the one path.
    """
    with jax.named_scope("dispatch"):
        flat_e = top_idx.reshape(-1)  # [T*k], token-major
        order = jnp.argsort(flat_e, stable=True)
        group_sizes = jnp.sum(one_hot, axis=(0, 1)).astype(jnp.int32)  # [E]
    pairs = order.shape[0]
    slab = pairs if layer is not None else expert_slab_rows(cfg, pairs)
    if slab == pairs:  # every expert held, a decode step, a share of half
        return _grouped_rows(
            x, top_w, order, group_sizes, blk, cfg, layer, kernel
        )
    experts = {n: blk[n] for n in _expert_leaves(cfg)}
    return _grouped_slabs(cfg, slab, x, top_w, experts, order, group_sizes)


def _slabs(cfg: ModelConfig, slab: int, order, group_sizes):
    """The sorted pairs in slabs of `slab` -> (rows(i, x, top_w, experts,
    into): `_grouped_rows` over pairs [i*slab, (i+1)*slab) with every
    group's sizes cut to that window; later(body, first): `body(i, carry)`
    from `first` on over the slabs after the first that hold a held pair,
    a loop with a traced bound and, as a rule, no trip)."""
    with jax.named_scope("dispatch"):
        # Past the last pair: index 0, beyond every group, so zeroed.
        padded = jnp.pad(order, (0, -order.shape[0] % slab))
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes

    def rows(i, x, top_w, experts, into=None):
        with jax.named_scope("dispatch"):
            lo = i * slab
            mine = jax.lax.dynamic_slice(padded, (lo,), (slab,))
            sizes = jnp.clip(ends, lo, lo + slab) - jnp.clip(
                starts, lo, lo + slab
            )
        return _grouped_rows(x, top_w, mine, sizes, experts, cfg, into=into)

    def later(body, first):
        with jax.named_scope("overflow"):
            return jax.lax.fori_loop(
                1, expert_slabs_run(slab, order.shape[0], ends[-1]), body, first
            )

    return rows, later


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_slabs(cfg: ModelConfig, slab: int, x, top_w, experts, order, group_sizes):
    """A share's dispatch: `_grouped_rows` over the first `slab` pairs of
    the order, outside any control flow, plus the sum of the slabs after it
    for as long as they hold a held pair (`lax.fori_loop` with a traced
    bound: no trip unless more than `slab` pairs are held here).  One
    algorithm for every program, with or without a gradient.

    The later slabs add up from zero and NOT from the first slab's result:
    the first slab's scatter-add then meets what follows it — the shared
    expert's sum — as the full gather's did, XLA:TPU fuses the two as it
    did, and a batch that makes no trip gets the full gather's bits (with
    the first slab as the loop's carry it no longer did, and in the
    latent-attention cell one flipped top-4-of-64 choice put a token's
    log-prob 0.91 from the reference where the limit is 0.6 and the full
    gather reads 0.41: chip runs, PR 41).

    The gradient rule is its own because of what autodiff of control flow
    keeps and returns: a `cond` around the rest would make every residual
    of the taken branch an output of both, and hand back the experts'
    gradients as fresh [E, in, out] buffers of zeros from the branch not
    taken, on every call and live until the layer's gradients are stacked
    (chip run, PR 41: the hybrid cell's gradient program no longer fitted,
    8.77 GB of temporaries where 8.09 GB were free); a loop with a traced
    bound has no reverse mode at all.  Here the first slab's backward pass
    is autodiff's own (`jax.vjp`, its residuals kept as autodiff would), and
    the loop runs again on the way back, each later slab's forward
    recomputed inside it and its gradients added in place."""
    rows, later = _slabs(cfg, slab, order, group_sizes)
    return _slab_sum(rows, later, rows(0, x, top_w, experts), x, top_w, experts)


def _slab_sum(rows, later, first, x, top_w, experts):
    return first + later(
        lambda i, out: rows(i, x, top_w, experts, out), jnp.zeros_like(first)
    )


def _grouped_slabs_fwd(cfg, slab, x, top_w, experts, order, group_sizes):
    rows, later = _slabs(cfg, slab, order, group_sizes)
    first, first_vjp = jax.vjp(functools.partial(rows, 0), x, top_w, experts)
    out = _slab_sum(rows, later, first, x, top_w, experts)
    return out, (first_vjp, x, top_w, experts, order, group_sizes)


def _grouped_slabs_bwd(cfg, slab, res, ct):
    first_vjp, x, top_w, experts, order, group_sizes = res
    rows, later = _slabs(cfg, slab, order, group_sizes)

    def add(i, grads):
        more = jax.vjp(functools.partial(rows, i), x, top_w, experts)[1](ct)
        return jax.tree.map(jnp.add, grads, more)

    return (*later(add, first_vjp(ct)), None, None)


_grouped_slabs.defvjp(_grouped_slabs_fwd, _grouped_slabs_bwd)


def _grouped_rows(
    x, top_w, order, group_sizes, blk: Params, cfg: ModelConfig, layer=None,
    kernel: bool = False, into=None,
):
    """`_experts_grouped` from the sort on, over the (row, choice) pairs
    `order` names — indices into the token-major [T*k] pairs, sorted by
    expert — in groups of `group_sizes`: gather, the experts, weight,
    scatter-add -> [T, D], zero for a row none of whose pairs is here.
    `into`: a sum to add these pairs' to (the later slabs of a share add
    up in one buffer)."""
    k = cfg.n_experts_per_tok
    with jax.named_scope("dispatch"):
        tok_of = order // k
        xs = x[tok_of]  # [T*k, D] sorted by expert
        layer_sizes = group_sizes
        if layer is not None:  # stacked leaves: this layer's groups of L*E
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((blk["wd"].shape[0] * cfg.n_experts,), jnp.int32),
                group_sizes,
                (layer * cfg.n_experts,),
            )
            blk = {
                n: blk[n].reshape(-1, *blk[n].shape[2:])
                for n in _expert_leaves(cfg)
            }
    with jax.named_scope("experts"):
        # A rank's share: rows whose expert is held elsewhere sort past
        # every group.  The ragged kernels do no work for them, and what
        # they leave in those rows (forward, and as a cotangent on the way
        # back) is not a result: `held` zeroes it at every step, so nothing
        # of it reaches a sum or a gradient.
        held = None
        if cfg.expert_share:
            held = (jnp.arange(xs.shape[0]) < jnp.sum(group_sizes))[:, None]

        def ragged(lhs, w):
            if kernel and layer is not None:
                # Rows past every group come out zero: nothing to mask.
                from areal_tpu.ops.pallas.grouped_matmul import (
                    grouped_decode_matmul,
                )

                return grouped_decode_matmul(
                    lhs, w, layer_sizes, layer, max_rows=x.shape[0]
                )
            if held is None:
                return jax.lax.ragged_dot(lhs, w, group_sizes)
            out = jax.lax.ragged_dot(jnp.where(held, lhs, 0), w, group_sizes)
            return jnp.where(held, out, 0)

        if cfg.mlp_gated:
            gate = jax.nn.silu(ragged(xs, blk["wg"]))
            up = ragged(xs, blk["wu"])
            ys = ragged(gate * up, blk["wd"])  # [T*k, D]
        else:  # down(act(up(x))): two matrices an expert
            ys = ragged(_act(ragged(xs, blk["wu"]), cfg), blk["wd"])
    with jax.named_scope("combine"):
        w_sorted = top_w.reshape(-1)[order].astype(ys.dtype)
        out = jnp.zeros_like(x) if into is None else into
        return out.at[tok_of].add(ys * w_sorted[:, None])


def _expert_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    """The routed experts' matrices: three for a gated expert, two for
    down(act(up(x)))."""
    return ("wg", "wu", "wd") if cfg.mlp_gated else ("wu", "wd")


def expert_leaves_in_place(cfg: ModelConfig, blocks: Params) -> bool:
    """Whether a decode program can hand `ragged_dot` the stacked expert
    leaves themselves (`_experts_grouped(layer=...)`): grouped dispatch,
    and the leaves' layer and expert axes not split over devices — the
    flat [L*E] view would cross a sharded dimension (`moe_w*` rules with
    fsdp > 1 or pipe > 1), and GSPMD would gather every layer's experts
    to build it.  Read from the leaves' own shardings; a leaf that has
    none to show (a tracer, a numpy array) counts as unsharded, so a
    caller that jits over sharded params asks BEFORE tracing and passes
    the answer on (`decode_step(experts_in_place=...)`)."""
    if not (cfg.is_moe and cfg.moe_dispatch == "grouped"):
        return False
    for name in _expert_leaves(cfg):
        leaf = blocks[name]
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and (
            tuple(sharding.shard_shape(leaf.shape)[:2]) != tuple(leaf.shape[:2])
        ):
            return False
    return True


def _scan_blocks(
    cfg: ModelConfig, blocks: Params, experts_in_place: Optional[bool]
) -> Tuple[Params, Optional[Params]]:
    """A decode program's split of the stacked block leaves: what its layer
    scan slices (`xs`), and the expert leaves its body closes over whole
    (None where they stay in `xs`: dense models, other dispatches, a
    sharded layer or expert axis).  `experts_in_place` None asks
    `expert_leaves_in_place`."""
    if experts_in_place is None:
        experts_in_place = expert_leaves_in_place(cfg, blocks)
    if not experts_in_place:
        return blocks, None
    names = _expert_leaves(cfg)
    return (
        {n: w for n, w in blocks.items() if n not in names},
        {n: blocks[n] for n in names},
    )


_MOE_EXPERTS = {
    "dense": _experts_dense,
    "grouped": _experts_grouped,
    "topk": _experts_topk,
}


@jax.named_scope("layer/mlp")
def _mlp_moe(
    h: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    valid: Optional[jax.Array] = None,
    stacked: Optional[Params] = None,
    layer: Optional[jax.Array] = None,
    kernel: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MoE MLP -> (out [B,S,D], aux loss, rows per expert [E] int32).

    One routing (`_moe_route`) feeds whichever `moe_dispatch` computes the
    experts.  The nested scopes (`layer/mlp/router`, `/dispatch`,
    `/experts`, `/combine`) split the device time of `layer/mlp` into what
    sparsity costs and the matmuls themselves (PERF.md §3).  The third
    result counts the (token, choice) rows each expert received — over the
    rows `valid` marks ([B,S] bool; all rows when None) — for the
    generator's and the trainer's load counters.  `stacked` (decode,
    grouped dispatch): the expert weights come from these stacked
    [L, E, in, out] leaves at layer index `layer` instead of from `blk`
    (`_experts_grouped`, which `kernel` hands the Pallas grouped matmul);
    without it `layer` is not read.

    Where the grouped dispatch works on a slab (`expert_slab_rows`), the
    rows `valid` does not mark are left out of it: the slab is sized for
    what a router sends here, and pads are one vector many times over — all
    of them on the same experts.  A prompt batch's pads (up to half its
    rows) tripped the overflow in prefill (chip runs, PR 41); the rows that
    are read come out the same bit for bit, a pad's expert output is zero."""
    b, s, d = h.shape
    x = h.reshape(-1, d)  # [T, D]
    with jax.named_scope("router"):
        top_w, top_idx, one_hot, aux = _moe_route(x, blk, cfg)
        if (
            valid is not None
            and stacked is None
            and cfg.moe_dispatch == "grouped"
            and expert_slab_rows(cfg, top_idx.size) < top_idx.size
        ):
            real = valid.reshape(-1)
            top_idx = jnp.where(real[:, None], top_idx, cfg.n_experts)
            one_hot = one_hot * real[:, None, None].astype(one_hot.dtype)
            valid = None  # what is left IS the real rows'
        if valid is None:  # the grouped dispatch's group sizes: one reduction
            counts = jnp.sum(one_hot, axis=(0, 1))
        else:
            counts = jnp.einsum(
                "tke,t->e", one_hot, valid.reshape(-1).astype(one_hot.dtype)
            )
        counts = jax.lax.stop_gradient(counts).astype(jnp.int32)
    if stacked is None:
        out = _MOE_EXPERTS[cfg.moe_dispatch](x, top_w, top_idx, one_hot, blk, cfg)
    else:
        out = _experts_grouped(
            x, top_w, top_idx, one_hot, stacked, cfg, layer, kernel
        )
    if cfg.shared_expert_dim:
        with jax.named_scope("shared"):
            if cfg.mlp_gated:
                hid = jax.nn.silu(x @ blk["ws_g"]) * (x @ blk["ws_u"])
            else:
                hid = _act(x @ blk["ws_u"], cfg)
            if cfg.shared_expert_gated:
                out = out + jax.nn.sigmoid(x @ blk["ws_gate"]) * (hid @ blk["ws_d"])
            else:
                out = out + hid @ blk["ws_d"]
    return out.reshape(b, s, d), aux, counts


def _block_forward(
    x: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    cp_manual: "Optional[Tuple[str, int]]" = None,
    cp_zigzag: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One block over packed rows -> (y, MoE aux loss, rows per expert over
    the real tokens [E] int32; None for a dense MLP)."""
    b, s, d = x.shape
    h = _norm(x, blk["ln1"], blk.get("ln1_b"), cfg)
    if cfg.is_latent:
        q, k, v, _ = _latent_qkv(h, blk, cfg, cos, sin)
    else:
        q, k, v = _block_kv(h, blk, cfg, cos, sin)
    if cp_manual is None and cp_mesh is None:
        attn = packed_attention(
            q, k, v, segment_ids, causal=True, use_flash=use_flash
        )
    else:
        with jax.named_scope("layer/attn"):
            if cp_manual is not None:
                # Already inside a manual region that includes the seq
                # axis (the CP+PP pipeline): run the ring body DIRECTLY on
                # this shard's chunk — nesting another shard_map over auto
                # axes is not expressible once operands vary over the
                # outer manual axis.
                from areal_tpu.ops.ring_attention import _ring_shard

                axis_name, axis_size, *my_idx = cp_manual
                attn = _ring_shard(
                    q, k, v, segment_ids, axis_name, axis_size, causal=True,
                    my_index=my_idx[0] if my_idx else None,
                )
            elif cp_zigzag:
                # Inputs already zigzag-permuted by _backbone (ONCE per
                # forward, not per layer).
                from areal_tpu.ops.ring_attention import (
                    zigzag_ring_packed_attention_prepermuted,
                )

                attn = zigzag_ring_packed_attention_prepermuted(
                    q, k, v, segment_ids, cp_mesh, causal=True
                )
            else:
                from areal_tpu.ops.ring_attention import (
                    ring_packed_attention,
                )

                attn = ring_packed_attention(
                    q, k, v, segment_ids, cp_mesh, causal=True
                )
    attn_out = _attn_out(
        attn.reshape(b, s, cfg.q_dim), blk, cfg, _attn_gate(h, blk, cfg)
    )
    return _block_mlp(x, attn_out, blk, cfg, segment_ids)


def _linear_block_forward(
    x: jax.Array, blk: Params, cfg: ModelConfig, segment_ids: jax.Array
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """`_block_forward` with the Gated DeltaNet mixer in attention's place."""
    h = _norm(x, blk["ln1"], blk.get("ln1_b"), cfg)
    mixed = linear_attn_forward(h, blk, cfg, segment_ids)
    return _block_mlp(x, mixed, blk, cfg, segment_ids)


def _block_mlp(
    x: jax.Array,
    attn_out: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """A block from its mixer's output on: residual, norm, MLP, residual."""
    # Named checkpoints for remat="dots_small" (see _backbone): the
    # attention output and the MLP down-projection output are the SMALL
    # per-token dots ([*, D]) whose saving lets backward skip only the
    # fat gate/up recompute candidates' DOWNSTREAM — memory ~2x "full"
    # remat instead of the ~7x of "dots".
    attn_out = checkpoint_name(attn_out, "attn_out")
    x = x + attn_out
    h2 = _norm(x, blk["ln2"], blk.get("ln2_b"), cfg)
    if _is_sparse(cfg, blk):
        mlp_out, aux, counts = _mlp_moe(h2, blk, cfg, valid=segment_ids > 0)
    else:
        mlp_out, aux = _mlp_dense(h2, blk, cfg), jnp.zeros((), jnp.float32)
        counts = None
    mlp_out = checkpoint_name(mlp_out, "mlp_out")
    return x + mlp_out, aux, counts


def _is_sparse(cfg: ModelConfig, blk: Params) -> bool:
    """Whether this layer's MLP is the mixture of experts: every layer of
    a MoE model but its leading dense ones, which carry no router."""
    return cfg.is_moe and (not cfg.first_k_dense or "router" in blk)


def _lead_layers(cfg: ModelConfig, blocks: Params) -> list:
    """The leading dense layers' leaves, one dict a layer under the
    layer's own names; [] for a model without any."""
    return [
        {
            n[len(DENSE_PREFIX):]: w[i]
            for n, w in blocks.items() if n.startswith(DENSE_PREFIX)
        }
        for i in range(cfg.first_k_dense)
    ]


def _scanned(cfg: ModelConfig, blocks: Params) -> Params:
    """The leaves the layer scan slices: all but the leading dense layers'."""
    if not cfg.first_k_dense:
        return blocks
    return {n: w for n, w in blocks.items() if not n.startswith(DENSE_PREFIX)}


_ZIGZAG_SNAPSHOT: "Optional[bool]" = None


def _zigzag_enabled() -> bool:
    """AREAL_RING_ZIGZAG, read ONCE: the value is baked into traced
    programs, and jit caches do not key on it — honoring later toggles
    only sometimes (cache misses) would make layout comparisons silently
    measure the same variant twice.  Set the env var before first use."""
    global _ZIGZAG_SNAPSHOT
    if _ZIGZAG_SNAPSHOT is None:
        import os

        _ZIGZAG_SNAPSHOT = os.environ.get("AREAL_RING_ZIGZAG") == "1"
    return _ZIGZAG_SNAPSHOT


def _backbone(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    segment_ids: jax.Array,
    positions: jax.Array,
    remat: bool,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    pp_mesh=None,
    pp_microbatches: int = 4,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """-> (final-normed hidden states, summed MoE aux loss, per-layer rows
    per expert [L, E] int32 — None for dense models and under PP)."""
    x = _embed(params, cfg, tokens, positions)
    cos, sin = rope_cos_sin(positions, _rope_dim(cfg), cfg.rope_theta)

    if cfg.is_hybrid and (cp_mesh is not None or pp_mesh is not None):
        raise HybridLayoutError(
            "a hybrid layer pattern (full_attn_interval "
            f"{cfg.full_attn_interval}) runs under data and fsdp "
            "sharding only: the chunked delta rule has no ring over a "
            "split sequence, and the pipeline has no stage of periods"
        )

    if cfg.is_latent and (cp_mesh is not None or pp_mesh is not None):
        raise LatentLayoutError(_NO_LATENT_LAYOUT)

    if cfg.is_pattern and (cp_mesh is not None or pp_mesh is not None):
        raise HybridLayoutError(_NO_PATTERN_LAYOUT)

    if pp_mesh is not None:
        from areal_tpu.parallel.pipeline import pipelined_blocks

        if cp_mesh is not None and _zigzag_enabled():
            from areal_tpu.base import logging as _logging

            # The CP+PP schedule keeps the contiguous layout: zigzag
            # there needs the permutation threaded through the tick
            # schedule's position bookkeeping — not built yet.  Say so
            # instead of silently ignoring the knob.
            _logging.getLogger("transformer").warning(
                "AREAL_RING_ZIGZAG has no effect under combined CP+PP; "
                "running the contiguous ring"
            )
        # The pipeline checkpoints each stage tick internally.  CP + PP
        # compose by manualizing BOTH axes in the pipeline's shard_map
        # (see pipelined_blocks: nesting a fresh seq shard_map per stage
        # is rejected by jax once operands vary over the manual pipe
        # axis, and silently mistrains under check_vma=False).
        x, aux = pipelined_blocks(
            params["blocks"], cfg, x, segment_ids, cos, sin,
            pp_mesh, pp_microbatches, use_flash,
            cp=cp_mesh is not None,
        )
        x = _final_norm(params, cfg, x)
        return x, aux, None

    # Zigzag ring layout: permute the token order ONCE for the whole
    # layer stack (every other op is per-token; attention sees original
    # positions via cos/sin + segment ids traveling with the tokens) and
    # invert after the final norm.
    from areal_tpu.base.topology import SEQ_AXIS as _SEQ

    zz_inv = None
    if cp_mesh is not None and _zigzag_enabled():
        if x.shape[1] % (2 * cp_mesh.shape[_SEQ]) == 0:
            from areal_tpu.ops.ring_attention import zigzag_indices

            idx, zz_inv = zigzag_indices(x.shape[1], cp_mesh.shape[_SEQ])
            x = jnp.take(x, idx, axis=1)
            segment_ids = jnp.take(segment_ids, idx, axis=1)
            cos = jnp.take(cos, idx, axis=1)
            sin = jnp.take(sin, idx, axis=1)
        else:
            from areal_tpu.base import logging as _logging

            # Never let a benchmark believe it measured zigzag when the
            # shape quietly fell back to the contiguous ring.
            _logging.getLogger("transformer").warning(
                f"AREAL_RING_ZIGZAG ignored: row length {x.shape[1]} not "
                f"divisible by 2*seq={2 * cp_mesh.shape[_SEQ]}"
            )

    x, auxes, counts = _period_blocks(
        params["blocks"], cfg, x, segment_ids, cos, sin, remat, use_flash,
        cp_mesh, cp_zigzag=zz_inv is not None,
    )
    x = _final_norm(params, cfg, x)
    if zz_inv is not None:
        x = jnp.take(x, zz_inv, axis=1)
    return x, jnp.sum(auxes), counts


def _remat_layer(body, remat):
    """`body` (one layer) under the remat policy (HBM vs recompute FLOPs):
      "full"/True — save nothing, recompute the whole layer in backward
        (minimum activation memory; ~1/3 extra forward FLOPs);
      "dots" — save matmul outputs, recompute elementwise/norms only
        (more memory, near-zero recompute — the right default when the
        activations fit);
      "dots_small" — save only the per-layer residual-branch outputs
        (attn_out, mlp_out): ~1/8 the memory of "dots", recomputes
        most of the layer — for models where "dots" overflows HBM;
      "none"/False — plain autodiff residuals."""
    if remat is True or remat == "full":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    if remat == "dots_small":
        # Middle ground when "dots" (~46 KB/token/layer of saved matmul
        # outputs at 1.5B) overflows HBM but "full" recompute caps MFU:
        # save only the two [*, D] residual-branch outputs per layer
        # (~6 KB/token/layer) — backward recomputes qkv/attention and
        # the fat gate/up matmuls, but the residual stream itself is
        # never recomputed.
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out"
            ),
        )
    if remat not in (False, None, "none"):
        raise ValueError(f"unknown remat policy {remat!r}")
    return body


class HybridLayoutError(NotImplementedError):
    """A layout or plane a hybrid layer pattern (linear-attention layers
    with recurrent state beside softmax-attention layers) cannot run on
    yet, refused by name rather than run wrong."""


class LatentLayoutError(NotImplementedError):
    """A layout or plane latent attention (a cache of latent rows, an
    absorbed decode step, leading dense layers before the scan) cannot run
    on yet, refused by name rather than run wrong."""


_NO_LATENT_LAYOUT = (
    "latent attention and leading dense layers run under data and fsdp "
    "sharding only: the heads of the low-rank projections are not split "
    "over `model`, the ring over a split sequence and the pipeline's "
    "stages were not tested with them (PERF.md section 7)"
)


_NO_PATTERN_LAYOUT = (
    "a pattern of one-branch layers (Mamba-2, experts or attention alone) "
    "runs under data and fsdp sharding only: the Mamba heads, their conv "
    "channels and their state are not split over `model`, the chunked scan "
    "has no ring over a split sequence, and the pipeline has no stage of a "
    "pattern's layers (PERF.md section 7)"
)


# Leaves only a period's full-attention layer has (stacked [n_periods, ...]
# in a hybrid model); `LINEAR_LEAVES` are the linear layers' ([n_linear,
# ...]); every other block leaf is per layer ([n_layers, ...]).
_FULL_ATTN_LEAVES = (
    "wq", "wk", "wv", "wo", "wqg", "bq", "bk", "bv", "bo", "q_norm", "k_norm",
)


def _period_view(cfg: ModelConfig, blocks: Params) -> Params:
    """The block leaves with the stack axis split by period: per-layer
    leaves [P, n, ...], linear ones [P, n - 1, ...], the full layer's
    [P, ...] (n = full_attn_interval).  Leading-axis reshapes: no data
    moves.  What a scan over periods slices; a period of one is its layer
    and the leaves are what they were."""
    n, p = cfg.full_attn_interval, cfg.n_periods
    if n == 1:
        return blocks
    out = {}
    for name, w in blocks.items():
        if name in _FULL_ATTN_LEAVES:
            out[name] = w
        elif name in LINEAR_LEAVES:
            out[name] = w.reshape(p, n - 1, *w.shape[1:])
        else:
            out[name] = w.reshape(p, n, *w.shape[1:])
    return out


def _period_layer(cfg: ModelConfig, pblk: Params, j: int) -> Params:
    """Layer j's leaves out of one period's slice of `_period_view`:
    positions 0..n-2 are linear layers, n-1 the full-attention layer."""
    if cfg.full_attn_interval == 1:
        return pblk
    last = j == cfg.full_attn_interval - 1
    blk = {}
    for name, w in pblk.items():
        if name in _FULL_ATTN_LEAVES:
            if last:
                blk[name] = w
        elif name in LINEAR_LEAVES:
            if not last:
                blk[name] = w[j]
        else:
            blk[name] = w[j]
    return blk


# Leaves only a pattern's 'E' layers have; `SSM_LEAVES` are its 'M'
# layers', `_FULL_ATTN_LEAVES` its '*' layers'; `ln1` is every layer's.
_MOE_LEAVES = (
    "router", "router_bias", "wg", "wu", "wd", "ws_g", "ws_u", "ws_d",
    "ws_gate",
)


def _leaf_kind(name: str) -> Optional[str]:
    """The pattern character of the layers a block leaf belongs to; None
    for a leaf every layer has."""
    if name in SSM_LEAVES:
        return "M"
    if name in _MOE_LEAVES:
        return "E"
    return "*" if name in _FULL_ATTN_LEAVES else None


def _pattern_view(cfg: ModelConfig, blocks: Params) -> Params:
    """`_period_view` for a pattern of one-branch layers: each leaf's stack
    axis split [repeats, its layers in one unit, ...]."""
    unit, p = cfg.pattern_unit, cfg.n_periods
    return {
        name: w.reshape(
            p, unit.count(_leaf_kind(name)) if _leaf_kind(name) else len(unit),
            *w.shape[1:],
        )
        for name, w in blocks.items()
    }


def _pattern_layer(cfg: ModelConfig, pblk: Params, j: int) -> Tuple[str, int, Params]:
    """Layer j of one unit's slice of `_pattern_view` -> (its kind, its
    index among the unit's layers of that kind, its leaves)."""
    unit = cfg.pattern_unit
    kind, i = unit[j], unit[:j].count(unit[j])
    blk = {"ln1": pblk["ln1"][j]}
    blk.update(
        {n: w[i] for n, w in pblk.items() if _leaf_kind(n) == kind}
    )
    return kind, i, blk


def _pattern_blocks(
    blocks: Params, cfg: ModelConfig, x, segment_ids, cos, sin, remat, use_flash
):
    """`_period_blocks` for a pattern of one-branch layers: a scan over the
    repeats of the pattern's unit, every layer x += f(norm(x)) with f its
    ONE kind, under the remat policy on its own.
    -> (x, aux loss per repeat, rows per expert [n_moe_layers, E])."""

    def layer(kind, y, blk):
        h = _norm(y, blk["ln1"], None, cfg)
        aux, counts = jnp.zeros((), jnp.float32), None
        if kind == "M":
            out = checkpoint_name(
                ssm_forward(h, blk, cfg, segment_ids), "attn_out"
            )
        elif kind == "*":
            q, k, v = _block_kv(h, blk, cfg, cos, sin)
            attn = packed_attention(
                q, k, v, segment_ids, causal=True, use_flash=use_flash
            )
            out = checkpoint_name(
                _attn_out(attn.reshape(*y.shape[:2], cfg.q_dim), blk, cfg),
                "attn_out",
            )
        else:
            out, aux, counts = _mlp_moe(h, blk, cfg, valid=segment_ids > 0)
            out = checkpoint_name(out, "mlp_out")
        return y + out, aux, counts

    layers = {
        kind: _remat_layer(functools.partial(layer, kind), remat)
        for kind in set(cfg.pattern_unit)
    }

    def body(y, pblk):
        aux, counts = jnp.zeros((), jnp.float32), []
        for j in range(len(cfg.pattern_unit)):
            kind, _, blk = _pattern_layer(cfg, pblk, j)
            y, a, c = layers[kind](y, blk)
            aux = aux + a
            if c is not None:
                counts.append(c)
        return y, (aux, jnp.stack(counts) if counts else None)

    x, (auxes, counts) = jax.lax.scan(body, x, _pattern_view(cfg, blocks))
    if counts is not None:  # [repeats, 'E' layers a unit, E] -> [n_moe, E]
        counts = counts.reshape(-1, counts.shape[-1])
    return x, auxes, counts


def _period_stack(cfg: ModelConfig, per_layer: list):
    """One period's per-layer values (rows per expert; None for a dense
    MLP) as the scan's output: [n, ...], or the layer's own for a period of
    one.  `_all_layers` undoes it after the scan."""
    if per_layer[0] is None or cfg.full_attn_interval == 1:
        return per_layer[0]
    return jnp.stack(per_layer)


def _all_layers(cfg: ModelConfig, stacked):
    """[P, n, ...] off a scan over periods -> [L, ...]."""
    if stacked is None or cfg.full_attn_interval == 1:
        return stacked
    return stacked.reshape(cfg.n_scan_layers, *stacked.shape[2:])


def _period_blocks(
    blocks: Params, cfg: ModelConfig, x, segment_ids, cos, sin, remat,
    use_flash, cp_mesh=None, cp_zigzag: bool = False,
):
    """The block stack of every model: ONE `lax.scan` over periods, each
    period's layers unrolled inside it (n - 1 Gated DeltaNet blocks, then
    one softmax-attention block; a model of one kind of layer is a period
    of one), every layer under the remat policy on its own.
    -> (x, aux loss per period [P], rows per expert [L, E])."""
    if cfg.is_pattern:
        return _pattern_blocks(
            blocks, cfg, x, segment_ids, cos, sin, remat, use_flash
        )
    n = cfg.full_attn_interval

    def linear(y, blk):
        return _linear_block_forward(y, blk, cfg, segment_ids)

    def full(y, blk):
        return _block_forward(
            y, blk, cfg, segment_ids, cos, sin, use_flash, cp_mesh,
            cp_zigzag=cp_zigzag,
        )

    linear, full = _remat_layer(linear, remat), _remat_layer(full, remat)

    def body(y, pblk):
        aux, counts = None, []
        for j in range(n):
            layer = full if j == n - 1 else linear
            y, a, c = layer(y, _period_layer(cfg, pblk, j))
            aux = a if aux is None else aux + a
            counts.append(c)
        return y, (aux, _period_stack(cfg, counts))

    for blk in _lead_layers(cfg, blocks):  # dense MLP: no aux, no counts
        x, _, _ = full(x, blk)
    x, (auxes, counts) = jax.lax.scan(
        body, x, _period_view(cfg, _scanned(cfg, blocks))
    )
    return x, auxes, _all_layers(cfg, counts)


@jax.named_scope("head_logprob")
def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.is_critic:
        v = jnp.einsum(
            "bsd,dk->bsk", x, params["value_head"],
            preferred_element_type=jnp.float32,
        )
        return v[..., 0]  # [B, S] fp32 values
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    return jnp.einsum(
        "bsd,dv->bsv", x, head, preferred_element_type=jnp.float32
    )  # [B, S, V] fp32 logits


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S] int32
    segment_ids: jax.Array,  # [B, S] int32, 0 = pad
    positions: Optional[jax.Array] = None,
    remat: bool = False,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    pp_mesh=None,
    pp_microbatches: int = 4,
) -> jax.Array:
    """Full forward over packed rows -> fp32 logits [B,S,V] (or values [B,S]
    for critics).  Also returns MoE aux loss via `forward_with_aux`.

    `cp_mesh`: pass the engine's Mesh to route attention through ring
    context parallelism over its `seq` axis (areal_tpu/ops/ring_attention).
    `pp_mesh`: pass the Mesh to microbatch-pipeline the block stack over its
    `pipe` axis (areal_tpu/parallel/pipeline).
    """
    out, _ = forward_with_aux(
        params, cfg, tokens, segment_ids, positions, remat, use_flash,
        cp_mesh, pp_mesh, pp_microbatches,
    )
    return out


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    segment_ids: jax.Array,
    positions: Optional[jax.Array] = None,
    remat: bool = False,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    pp_mesh=None,
    pp_microbatches: int = 4,
    with_moe_counts: bool = False,
) -> Tuple[jax.Array, ...]:
    """Backbone only: final-layernormed hidden states [B, S, D] (+ MoE aux
    loss), WITHOUT the LM head.  Lets engines fuse the head into a chunked
    loss (ops/functional.fused_next_token_logprobs) instead of materializing
    [B, S, V] logits.  `with_moe_counts` adds a third result: the real
    tokens' rows per expert of every layer, [L, E] int32 (None for dense
    models and under PP) — the trainer's load counter."""
    if positions is None:
        positions = positions_from_segments(segment_ids)
    x, aux, counts = _backbone(
        params, cfg, tokens, segment_ids, positions, remat, use_flash,
        cp_mesh, pp_mesh, pp_microbatches,
    )
    return (x, aux, counts) if with_moe_counts else (x, aux)


def head_weights(params: Params, cfg: ModelConfig) -> jax.Array:
    """[D, V] LM-head matrix (transposed embedding when tied)."""
    return params["embed"].T if cfg.tied_embeddings else params["lm_head"]


def per_token_output(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D] from hidden_states()
    tokens: jax.Array,
    segment_ids: jax.Array,
    chunk_size: int = 512,
    mesh=None,
) -> jax.Array:
    """The engine-facing per-token model output [B, S] fp32: critic values
    (via the value head) or fused chunked next-token logprobs for LMs —
    never [B, S, V] logits.  `mesh`: the mesh the caller's program is
    partitioned over; the log-prob head splits the vocabulary over it."""
    if cfg.is_critic:
        return _head(params, cfg, x)
    from areal_tpu.ops.functional import fused_next_token_logprobs

    return fused_next_token_logprobs(
        x, head_weights(params, cfg), tokens, segment_ids, chunk_size, mesh
    )


def forward_with_aux(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    segment_ids: jax.Array,
    positions: Optional[jax.Array] = None,
    remat: bool = False,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    pp_mesh=None,
    pp_microbatches: int = 4,
) -> Tuple[jax.Array, jax.Array]:
    if positions is None:
        positions = positions_from_segments(segment_ids)
    x, aux, _ = _backbone(
        params, cfg, tokens, segment_ids, positions, remat, use_flash,
        cp_mesh, pp_mesh, pp_microbatches,
    )
    return _head(params, cfg, x), aux


# --------------------------------------------------------------------------
# KV-cache generation path
# --------------------------------------------------------------------------


@dataclasses.dataclass
class KVCache:
    """Dense per-layer KV cache of the static decode program: k/v
    [L, B, S_max, n_kv, head_dim], full precision (its windows are small;
    the int8 mode lives on the serving plane's `PagedKVCache`).

    A hybrid layer pattern keeps two kinds of state side by side: k/v for
    its softmax-attention layers alone (L = n_periods), and for each Gated
    DeltaNet layer a recurrent `state` [n_linear, B, hv, dk, dv] in fp32
    plus the causal conv's last inputs `conv` [n_linear, B, K-1, C].  Both
    are None for every other model.

    A pattern of one-branch layers keeps k/v for its attention layers
    alone (L = n_attn_layers), for each Mamba-2 layer a `state` [n_ssm, B,
    H, head_dim, N] in fp32 and the conv's last inputs `conv` [n_ssm, B,
    K-1, conv_dim], and nothing for an expert layer.

    Latent attention keeps neither k nor v: `latent` [L, B, S_max,
    kv_lora_rank + qk_rope_head_dim] holds ONE row a token and layer, the
    normed latent vector beside the roped key part all heads share, and
    the decode step attends over the rows themselves (`decode_step`)."""

    k: Optional[jax.Array]
    v: Optional[jax.Array]
    state: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None

    @property
    def s_max(self) -> int:
        return (self.latent if self.k is None else self.k).shape[2]


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "state", "conv", "latent"], meta_fields=[]
)


# Canonical implementations live in ops/quant.py (shared with the
# attention paths); re-exported here for the cache-facing API.
from areal_tpu.ops.quant import kv_dequant, kv_quant  # noqa: E402,F401


def _cache_update(kc, vc, ksc, vsc, k, v, rows, rows_s, quant: bool):
    """Pool write of the paged decode step: scatter the new K/V entries
    [T, n_kv, d] into the pool [L, P, ps, n_kv*d] at its flat token rows
    `rows` [T] (`_pool_rows`, the layer's offset added), quantizing when
    the pool is int8 (scales at `rows_s` [T, n_kv]).  The pool is written
    as the [L*P*ps, n_kv*d] rows it is in memory — a bitcast, and one
    index a token whatever `n_kv` is (PERF.md, PR 37).  There is no layer
    read: the attention op takes the stacked pool and the layer index and
    reads the live pages in place (`ops/attention.ragged_paged_attention`),
    dequantizing an int8 pool itself.

    Rows past the pool are DROPPED (dead lanes, and writes through a page
    table's unmapped entries)."""

    def put(pool, new, at, n_lead):
        flat = pool.reshape(-1, *pool.shape[n_lead:])
        return flat.at[at].set(new, mode="drop").reshape(pool.shape)

    t = k.shape[0]
    if quant:
        kq, ks = kv_quant(k)
        vq, vs = kv_quant(v)
        return (
            put(kc, kq.reshape(t, -1), rows, 3),
            put(vc, vq.reshape(t, -1), rows, 3),
            put(ksc, ks, rows_s, 4), put(vsc, vs, rows_s, 4),
        )
    return (
        put(kc, k.astype(kc.dtype).reshape(t, -1), rows, 3),
        put(vc, v.astype(vc.dtype).reshape(t, -1), rows, 3),
        ksc, vsc,
    )


def init_kv_cache(
    cfg: ModelConfig, batch: int, s_max: int, dtype=None
) -> KVCache:
    dtype = dtype or cfg.dtype
    if cfg.is_latent:
        return KVCache(k=None, v=None, latent=jnp.zeros(
            (cfg.n_layers, batch, s_max, cfg.latent_dim), dtype))
    shape = (cfg.n_attn_layers, batch, s_max, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if cfg.n_ssm_layers:
        nm = cfg.n_ssm_layers
        cache.state = jnp.zeros(
            (nm, batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state_dim),
            jnp.float32,
        )
        cache.conv = jnp.zeros(
            (nm, batch, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim), dtype
        )
    if cfg.is_hybrid:
        nl = cfg.n_linear_layers
        cache.state = jnp.zeros(
            (nl, batch, cfg.linear_n_v_heads, cfg.linear_k_head_dim,
             cfg.linear_v_head_dim),
            jnp.float32,
        )
        cache.conv = jnp.zeros(
            (nl, batch, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim), dtype
        )
    return cache


@jax.named_scope("layer/attn_qkv")
def _block_kv(
    h: jax.Array, blk: Params, cfg: ModelConfig, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = h.shape
    q = h @ blk["wq"]
    k = h @ blk["wk"]
    v = h @ blk["wv"]
    if cfg.qkv_bias:
        q, k, v = q + blk["bq"], k + blk["bk"], v + blk["bv"]
    per_head = cfg.qk_norm and cfg.qk_norm_per_head
    if cfg.qk_norm and not per_head:  # olmoe: over the WHOLE projection
        q = rms_norm(q, blk["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, blk["k_norm"], cfg.rms_norm_eps)
    q = q.reshape(b, s, cfg.n_q_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if per_head:  # qwen3_next: over each head's head_dim, scale per cfg
        q = _norm(q, blk["q_norm"], None, cfg)
        k = _norm(k, blk["k_norm"], None, cfg)
    if cfg.pos_emb == "rope":
        r = cfg.rotary_dim
        if r and r < cfg.head_dim:  # partial rotary: the first r dims only
            qr, kr = apply_rotary(q[..., :r], k[..., :r], cos, sin)
            q = jnp.concatenate([qr, q[..., r:]], axis=-1)
            k = jnp.concatenate([kr, k[..., r:]], axis=-1)
        else:
            q, k = apply_rotary(q, k, cos, sin)
    return q, k, v


def _attn_gate(h: jax.Array, blk: Params, cfg: ModelConfig):
    """The attention output gate's pre-activation [.., q_dim], or None."""
    if not cfg.attn_gate:
        return None
    with jax.named_scope("layer/attn_qkv"):
        return h @ blk["wqg"]


def _rope_dim(cfg: ModelConfig) -> int:
    if cfg.is_latent:
        return cfg.qk_rope_head_dim
    return cfg.rotary_dim or cfg.head_dim


def _latent_q_row(
    h: jax.Array, blk: Params, cfg: ModelConfig, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Latent attention's two low-rank projections of h [B, S, D] ->
    (q_nope [B, S, H, nope], roped q_pe [B, S, H, rope], the cache's row
    [B, S, kv_lora_rank + rope]: the normed latent vector beside the roped
    key part all heads share)."""
    b, s, _ = h.shape
    nope, c = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("q_lora"):
        c_q = rms_norm(h @ blk["wq_a"], blk["q_a_norm"], cfg.rms_norm_eps)
        q = (c_q @ blk["wq_b"]).reshape(b, s, cfg.n_q_heads, cfg.head_dim)
    with jax.named_scope("kv_lora"):
        kv = h @ blk["wkv_a"]
        c_kv = rms_norm(kv[..., :c], blk["kv_a_norm"], cfg.rms_norm_eps)
        q_pe, k_pe = apply_rotary(
            q[..., nope:], kv[..., None, c:], cos, sin
        )
        row = jnp.concatenate([c_kv, k_pe[..., 0, :]], axis=-1)
    return q[..., :nope], q_pe, row


@jax.named_scope("layer/attn_qkv")
def _latent_qkv(
    h: jax.Array, blk: Params, cfg: ModelConfig, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The MATERIALISED form (train, prefill): keys and values of every
    head up-projected from the latent vector -> (q, k, v [B, S, H,
    head_dim], the cache's row).  The kernels see plain multi-head
    attention of one width."""
    b, s, _ = h.shape
    hq, c = cfg.n_q_heads, cfg.kv_lora_rank
    q_nope, q_pe, row = _latent_q_row(h, blk, cfg, cos, sin)
    with jax.named_scope("up_kv"):
        c_kv, k_pe = row[..., :c], row[..., None, c:]
        k_nope = (c_kv @ blk["wk_b"]).reshape(b, s, hq, cfg.qk_nope_head_dim)
        v = (c_kv @ blk["wv_b"]).reshape(b, s, hq, cfg.v_head_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, s, hq, k_pe.shape[-1]))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return q, k, v, row


@jax.named_scope("layer/attn_qkv")
def _latent_q_absorbed(
    h: jax.Array, blk: Params, cfg: ModelConfig, cos: jax.Array, sin: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The ABSORBED form (decode): the query carried into the latent space,
    q~_h = q_nope,h W_k,h^T, beside its roped part -> (a query over latent
    rows [B, S, H, kv_lora_rank + rope], the new token's row).  Its scores
    against the rows are q_nope . k_nope + q_pe . k_pe in another order."""
    q_nope, q_pe, row = _latent_q_row(h, blk, cfg, cos, sin)
    with jax.named_scope("absorb_q"):
        c = cfg.kv_lora_rank
        q_lat = jnp.einsum(
            "bshn,chn->bshc", q_nope,
            blk["wk_b"].reshape(c, cfg.n_q_heads, cfg.qk_nope_head_dim),
        )
        return jnp.concatenate([q_lat, q_pe], axis=-1), row


@jax.named_scope("gen/prefill")
def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S] one sequence per row (left-aligned)
    segment_ids: jax.Array,  # [B, S] 1 where valid, 0 pad (single segment/row)
    cache: KVCache,
    use_flash: "bool | None" = None,
) -> Tuple[jax.Array, KVCache]:
    """Run the prompt through the model, filling cache[:, :, :S] and
    returning fp32 logits [B, V] at each row's LAST VALID position (the
    distribution over the first generated token).  Computing the head only
    there keeps prefill memory at [B, V] instead of [B, S, V] — at a 152k
    vocab that is the difference between 40 MB and 10 GB."""
    positions = positions_from_segments(segment_ids)
    x = _embed(params, cfg, tokens, positions)
    cos, sin = rope_cos_sin(positions, _rope_dim(cfg), cfg.rope_theta)

    def mlp(y, blk):
        h2 = _norm(y, blk["ln2"], blk.get("ln2_b"), cfg)
        if not _is_sparse(cfg, blk):
            return y + _mlp_dense(h2, blk, cfg)
        return y + _mlp_moe(h2, blk, cfg, valid=segment_ids > 0)[0]

    def body(carry, layer_in):
        """-> (y, what the layer leaves in the cache: (k, v), or the one
        latent row a token of latent attention)."""
        blk = layer_in
        h = _norm(carry, blk["ln1"], blk.get("ln1_b"), cfg)
        if cfg.is_latent:
            q, k, v, row = _latent_qkv(h, blk, cfg, cos, sin)
            left = (row,)
        else:
            q, k, v = _block_kv(h, blk, cfg, cos, sin)
            left = (k, v)
        attn = packed_attention(
            q, k, v, segment_ids, causal=True, use_flash=use_flash
        )
        y = _attn_out(
            attn.reshape(*carry.shape[:2], cfg.q_dim), blk, cfg,
            _attn_gate(h, blk, cfg),
        )
        return mlp(carry + y, blk), left

    def period_body(carry, pblk):
        """A period: its linear layers leave their final state and conv
        tail, its full layer its k/v.  A period of one is `body`."""
        n, y, states, tails = cfg.full_attn_interval, carry, [], []
        for j in range(n - 1):
            blk = _period_layer(cfg, pblk, j)
            h = _norm(y, blk["ln1"], blk.get("ln1_b"), cfg)
            mixed, state, tail = linear_attn_forward(
                h, blk, cfg, segment_ids, with_state=True
            )
            y = mlp(y + mixed, blk)
            states.append(state)
            tails.append(tail)
        y, kv = body(y, _period_layer(cfg, pblk, n - 1))
        left = (jnp.stack(states), jnp.stack(tails)) if states else ()
        return y, (*kv, *left)

    def pattern_body(carry, pblk):
        """A unit of one-branch layers: a Mamba layer leaves its state and
        conv tail at the row's last valid token, an attention layer its
        k/v, an expert layer nothing."""
        y, ks, vs, states, tails = carry, [], [], [], []
        for j in range(len(cfg.pattern_unit)):
            kind, _, blk = _pattern_layer(cfg, pblk, j)
            h = _norm(y, blk["ln1"], None, cfg)
            if kind == "M":
                out, state, tail = ssm_forward(
                    h, blk, cfg, segment_ids, with_state=True
                )
                states.append(state)
                tails.append(tail)
            elif kind == "*":
                q, k, v = _block_kv(h, blk, cfg, cos, sin)
                attn = packed_attention(
                    q, k, v, segment_ids, causal=True, use_flash=use_flash
                )
                out = _attn_out(attn.reshape(*y.shape[:2], cfg.q_dim), blk, cfg)
                ks.append(k)
                vs.append(v)
            else:
                out = _mlp_moe(h, blk, cfg, valid=segment_ids > 0)[0]
            y = y + out
        return y, tuple(
            jnp.stack(a) if a else None for a in (ks, vs, states, tails)
        )

    def fill(buf, new):
        """The cache buffer with the prompt's entries of every layer."""
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0,) * buf.ndim
        )

    def flat(a, buf):
        """[repeats, a kind's layers a unit, ...] -> the cache's [layers of
        that kind, ...]; the buffer as it is where no layer has the kind."""
        return buf if a is None else a.reshape(-1, *a.shape[2:])

    if cfg.is_pattern:
        x, by_kind = jax.lax.scan(
            pattern_body, x, _pattern_view(cfg, params["blocks"])
        )
        ks, vs, states, tails = (
            flat(a, buf) for a, buf in
            zip(by_kind, (cache.k, cache.v, cache.state, cache.conv))
        )
        new_cache = KVCache(
            k=fill(cache.k, ks), v=fill(cache.v, vs), state=states,
            conv=None if tails is None else tails.astype(cache.conv.dtype),
        )
        return _prefill_head(params, cfg, x, segment_ids), new_cache

    lead = []  # the leading dense layers' rows come first in the cache
    for blk in _lead_layers(cfg, params["blocks"]):
        x, (row,) = body(x, blk)
        lead.append(row)
    x, (ks, *left) = jax.lax.scan(
        period_body, x, _period_view(cfg, _scanned(cfg, params["blocks"]))
    )

    if cfg.is_latent:
        rows = jnp.concatenate([jnp.stack(lead), ks]) if lead else ks
        new_cache = KVCache(k=None, v=None, latent=fill(cache.latent, rows))
    else:
        vs, *left = left
        extra = {}
        if left:  # [P, n - 1, B, ...] -> [n_linear, B, ...]: the cache's layout
            extra = dict(
                state=left[0].reshape(cache.state.shape),
                conv=left[1].reshape(cache.conv.shape).astype(cache.conv.dtype),
            )
        new_cache = KVCache(k=fill(cache.k, ks), v=fill(cache.v, vs), **extra)
    return _prefill_head(params, cfg, x, segment_ids), new_cache


def _prefill_head(params: Params, cfg: ModelConfig, x, segment_ids):
    """fp32 logits [B, V] at each row's last valid position."""
    x = _final_norm(params, cfg, x)
    # Gather each row's last valid hidden state before the (huge) head matmul.
    # (index of the last nonzero segment: works for left- and right-aligned
    # prompt layouts alike)
    idx = jnp.arange(segment_ids.shape[-1])
    last = jnp.max(jnp.where(segment_ids > 0, idx, 0), axis=-1)  # [B]
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B,1,D]
    return _head(params, cfg, x_last)[:, 0]


@jax.named_scope("gen/decode_step")
def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B] int32 — current token per row
    positions: jax.Array,  # [B] int32 — its RoPE position per row
    cache: KVCache,
    slot: jax.Array,  # scalar int32 — cache slot written for ALL rows
    valid_from: jax.Array,  # [B] int32 — first valid cache slot per row
    with_moe_counts: bool = False,
    experts_in_place: Optional[bool] = None,
    row_kernel=None,  # None | bool | Mesh
    expert_kernel: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """One decode step: write the new token's k/v at cache slot `slot`
    (shared by every row — the right-aligned prompt layout makes the write a
    single `dynamic_update_slice`, not a per-row scatter), attend over the
    live window `[valid_from, slot]`, return fp32 logits [B, V] and the
    updated cache — and, `with_moe_counts`, the step's rows per expert of
    every layer ([L, E] int32; MoE models only), which the generator's
    counters reduce inside its decode loop.

    The cache rides the layer scan as CARRY (updated in place by XLA), so
    per-token HBM traffic is one (B, n_kv, d) write + one window read per
    layer instead of a full-cache rewrite (the fix for the one-hot scatter
    this replaces).  Reference semantics: the fused decode step replayed via
    CUDA graphs, realhf/impl/model/nn/real_llm_generate.py:336-368.

    The weights ride the scan as `xs`: the body gets its layer's slice of
    every stacked leaf, which XLA fuses into the dense matmuls.  A grouped
    MoE model's expert leaves `wg` / `wu` / `wd` are the exception
    (`experts_in_place`; None = `expert_leaves_in_place` of these params):
    the scan does not slice them, the body closes over the stacked
    [L, E, in, out] leaves and `_experts_grouped` picks the layer by group
    sizes, because a slice handed to the ragged kernel is a copy of all E
    experts' weights per kernel and step.  Where the leaves' layer or
    expert axis is sharded they stay in `xs`.  Dense models trace the
    program they always did.

    `expert_kernel`: whether the in-place expert matmuls are the Pallas
    kernel `grouped_decode_matmul` (None: on
    a TPU backend where XLA's ragged kernel tiles the expert's [in, out]
    badly, `grouped_matmul.ragged_tiles_badly`; a caller whose mesh
    spreads the rows over devices passes False, the kernel is one
    device's program).

    `row_kernel`: the form of the two per-row cache kernels, in which a
    row reads and writes nothing of another's — latent attention's
    `latent_decode` over the stacked rows and the Gated DeltaNet step's
    `gdn_delta_step` over the stacked recurrent state.  None = the Pallas
    kernel on a TPU backend, the XLA form elsewhere; a caller whose mesh
    spreads the rows over devices passes the MESH and the kernel runs per
    device on its rows (`shard_map` over the batch axes); a bool forces
    either form.

    Latent attention (`cfg.is_latent`) runs its ABSORBED form here: the
    cache holds one latent row a token (`KVCache.latent`), the query is
    carried into the latent space, scores and the weighted sum are taken
    against the rows themselves and the value up-projection comes after
    (`_latent_q_absorbed`, `latent_decode_attention`, `_attn_out`) — the
    numbers of the materialised form `prefill` and training run, in
    another order, and no per-head k/v is ever built.  Leading dense
    layers step before the scan, through the first layers of the cache.
    """
    b = tokens.shape[0]
    x = _embed(params, cfg, tokens, positions)[:, None, :]  # [B,1,D]
    cos, sin = rope_cos_sin(positions[:, None], _rope_dim(cfg), cfg.rope_theta)
    slot = jnp.asarray(slot, jnp.int32)
    blocks, stacked = _scan_blocks(cfg, params["blocks"], experts_in_place)
    if expert_kernel is None and stacked is not None:
        from areal_tpu.base.distributed import is_tpu_backend
        from areal_tpu.ops.pallas.grouped_matmul import ragged_tiles_badly

        expert_kernel = is_tpu_backend() and ragged_tiles_badly(
            cfg.hidden_dim, cfg.moe_intermediate_dim
        )

    def mlp(y, blk, layer):
        """-> (y + mlp, rows per expert); `layer` indexes the stacked
        expert leaves (all the scanned layers of them)."""
        h2 = _norm(y, blk["ln2"], blk.get("ln2_b"), cfg)
        if _is_sparse(cfg, blk):
            mlp_out, _, counts = _mlp_moe(
                h2, blk, cfg, stacked=stacked, layer=layer,
                kernel=bool(expert_kernel),
            )
        else:
            mlp_out, counts = _mlp_dense(h2, blk, cfg), None
        return y + mlp_out, counts

    def attend_latent(y, rows, blk, li):
        """Absorbed latent attention of one token per row through the
        latent rows of layer li."""
        h = _norm(y, blk["ln1"], blk.get("ln1_b"), cfg)
        q, row = _latent_q_absorbed(h, blk, cfg, cos, sin)
        rows = jax.lax.dynamic_update_slice(
            rows, row.astype(rows.dtype)[None], (li, 0, slot, 0)
        )
        attn = latent_decode_attention(
            q[:, 0], rows, li, valid_from, slot + 1, cfg.kv_lora_rank,
            cfg.head_dim**-0.5, use_kernel=row_kernel,
        )
        ao = _attn_out(attn.reshape(b, 1, -1), blk, cfg, absorbed=True)
        return y + ao, rows, None

    def attend(y, kc, vc, blk, li):
        """Softmax attention of one token per row through k/v layer li."""
        if cfg.is_latent:
            return attend_latent(y, kc, blk, li)
        h = _norm(y, blk["ln1"], blk.get("ln1_b"), cfg)
        q, k, v = _block_kv(h, blk, cfg, cos, sin)  # q/k/v [B,1,h,d]
        # k/v [B,1,h,d] -> [1,B,1,h,d] written at (layer, :, slot).
        kc = jax.lax.dynamic_update_slice(
            kc, k.astype(kc.dtype)[None], (li, 0, slot, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            vc, v.astype(vc.dtype)[None], (li, 0, slot, 0, 0)
        )
        k_layer = jax.lax.dynamic_index_in_dim(kc, li, axis=0, keepdims=False)
        v_layer = jax.lax.dynamic_index_in_dim(vc, li, axis=0, keepdims=False)
        attn = decode_attention(q, k_layer, v_layer, valid_from, slot + 1)
        ao = _attn_out(
            attn.reshape(b, 1, cfg.q_dim), blk, cfg, _attn_gate(h, blk, cfg)
        )
        return y + ao, kc, vc

    def period_body(carry, pblk):
        """A period: each linear layer steps its recurrent state and conv
        tail in place (carried like k/v; None where no layer is linear),
        the full layer attends through the period's k/v."""
        y, kc, vc, sc, cc, pi = carry
        n, counts = cfg.full_attn_interval, []

        def layer(j):  # the period's layer j among all the layers
            return pi if n == 1 else pi * n + j

        for j in range(n - 1):
            blk = _period_layer(cfg, pblk, j)
            li = pi * (n - 1) + j
            h = _norm(y, blk["ln1"], blk.get("ln1_b"), cfg)
            mixed, sc, cc = linear_attn_step(
                h, blk, cfg, sc, cc, li, row_kernel)
            y, c = mlp(y + mixed, blk, layer(j))
            counts.append(c)
        blk = _period_layer(cfg, pblk, n - 1)
        y, kc, vc = attend(y, kc, vc, blk, pi + n_lead if n_lead else pi)
        y, c = mlp(y, blk, layer(n - 1))
        counts.append(c)
        return (y, kc, vc, sc, cc, pi + 1), _period_stack(cfg, counts)

    def pattern_body(carry, pblk):
        """A unit of one-branch layers: a Mamba layer steps its state and
        conv tail in place, an attention layer attends through its k/v, an
        expert layer reads no cache; each indexes its own kind's stack."""
        y, kc, vc, sc, cc, pi = carry
        unit, counts = cfg.pattern_unit, []
        for j in range(len(unit)):
            kind, i, blk = _pattern_layer(cfg, pblk, j)
            li = pi * unit.count(kind) + i
            if kind == "*":
                y, kc, vc = attend(y, kc, vc, blk, li)
                continue
            h = _norm(y, blk["ln1"], None, cfg)
            if kind == "M":
                out, sc, cc = ssm_step(h, blk, cfg, sc, cc, li)
            else:
                out, _, c = _mlp_moe(
                    h, blk, cfg, stacked=stacked, layer=li,
                    kernel=bool(expert_kernel),
                )
                counts.append(c)
            y = y + out
        return (y, kc, vc, sc, cc, pi + 1), (
            jnp.stack(counts) if counts else None
        )

    if cfg.is_pattern:
        (x, kc, vc, sc, cc, _), counts = jax.lax.scan(
            pattern_body,
            (x, cache.k, cache.v, cache.state, cache.conv, jnp.int32(0)),
            _pattern_view(cfg, blocks),
        )
        if counts is not None:
            counts = counts.reshape(-1, counts.shape[-1])
        logits = _head(params, cfg, _final_norm(params, cfg, x))[:, 0]
        new_cache = KVCache(k=kc, v=vc, state=sc, conv=cc)
        if with_moe_counts:
            return logits, new_cache, counts
        return logits, new_cache

    n_lead = cfg.first_k_dense
    # k/v, or latent attention's one buffer of rows in k's place.
    kc, vc = (cache.latent, None) if cfg.is_latent else (cache.k, cache.v)
    for i, blk in enumerate(_lead_layers(cfg, blocks)):
        x, kc, vc = attend(x, kc, vc, blk, i)
        x, _ = mlp(x, blk, None)
    (x, kc, vc, sc, cc, _), counts = jax.lax.scan(
        period_body,
        (x, kc, vc, cache.state, cache.conv, jnp.int32(0)),
        _period_view(cfg, _scanned(cfg, blocks)),
    )
    counts = _all_layers(cfg, counts)
    if cfg.is_latent:
        new_cache = KVCache(k=None, v=None, latent=kc)
    else:
        new_cache = KVCache(k=kc, v=vc, state=sc, conv=cc)
    x = _final_norm(params, cfg, x)
    logits = _head(params, cfg, x)[:, 0]  # [B, V]
    if with_moe_counts:
        return logits, new_cache, counts
    return logits, new_cache


# --------------------------------------------------------------------------
# Paged KV-cache generation path
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PagedKVCache:
    """Block-paged KV pool: k/v [L, n_pages, page_size, n_kv * head_dim] —
    a token's heads side by side in one row, so that a page is whole
    (page_size, n_kv * head_dim) tiles the attention kernel copies as
    they lie, one head a lane-aligned slice of them, and a new token is
    one row to scatter (with (n_kv, head_dim) as the minor dims, `n_kv =
    2` pads to a 16-row tile or the pool is re-laid around the kernel).
    The shape is private to this module and the generator.

    A dense cache at [L, n_slots, s_max, ...] would couple every slot
    to the batch-max window: growth a full-cache copy plus a decode
    recompile per bucket, and a finished short row holding s_max worth
    of HBM until the batch drains.  Paging breaks the coupling: the pool
    is allocated ONCE per generate call, each slot owns an ordered list
    of pages (the host-side page table), growth appends a page index,
    and a retired slot's pages are recycled into new admits — fixed
    memory, fixed shapes, one decode compilation.  Reference: TPU ragged paged attention / vLLM
    PagedAttention block tables.

    Page index `n_pages` is the UNMAPPED sentinel: writes through it are
    dropped (`mode="drop"`), reads clamp and are masked by `valid_to`
    (pages are mapped contiguously from position 0, so any position
    beyond the mapped prefix is also beyond the live window).

    int8 mode (k/v int8 + bf16 per-(layer,page,pos,head) scales in
    k_scale/v_scale, quantizer `ops/quant.py`) HALVES the HBM bytes per
    cached token: at long context the decode batch × window product is
    capacity-bound — a 1.5B model's bf16 KV at batch 32 × 16k window is
    ~15 GB and does not fit a 16 GB chip at all; int8 does.  Scales add
    1/head_dim overhead.  Fresh K/V is quantized ONCE when written and
    every later read sees the stored codes, so chunk boundaries cannot
    move the numerics.  Reference role: KV-cache quantization knobs in
    serving engines (sglang).
    """

    k: jax.Array
    v: jax.Array
    k_scale: "jax.Array | None" = None  # [L, n_pages, n_kv, page_size] bf16
    v_scale: "jax.Array | None" = None
    page_size: int = 128  # static metadata (pytree aux)

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=["k", "v", "k_scale", "v_scale"],
    meta_fields=["page_size"],
)


def init_paged_kv_cache(
    cfg: ModelConfig, n_pages: int, page_size: int, dtype=None
) -> PagedKVCache:
    if cfg.is_latent:
        raise LatentLayoutError(_NO_SERVING_LATENT)
    if cfg.is_pattern:
        raise HybridLayoutError(_NO_SERVING_PATTERN)
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    dtype = dtype or cfg.dtype
    if dtype in (jnp.int8, "int8"):
        s_shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size)
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(s_shape, jnp.bfloat16),
            v_scale=jnp.zeros(s_shape, jnp.bfloat16),
            page_size=page_size,
        )
    return PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        page_size=page_size,
    )


def _page_of(page_table: jax.Array, pos: jax.Array, page_size: int):
    """Per-token (page, offset) write coordinates for flat positions
    `pos` [T] through the per-token `page_table` [T, max_pages]."""
    pos2 = pos[:, None]
    pages = jnp.take_along_axis(
        page_table, pos2 // page_size, axis=1, mode="clip"
    )
    # Positions addressing beyond the table width must DROP, not alias
    # the clipped last entry (2**30 is out of range of any pool axis).
    oob = pos2 // page_size >= page_table.shape[1]
    pages = jnp.where(oob, jnp.int32(2**30), pages)[:, 0]
    return pages.astype(jnp.int32), (pos % page_size).astype(jnp.int32)


def _pool_rows(cache: "PagedKVCache", n_kv: int, page, off):
    """Layer 0's flat rows of the entries (page, off): the pool's token
    rows [T] and the head-major scales' rows [T, n_kv].  Layer li's are
    `li * stride` (`li * stride * n_kv`) on.  A page that is not in the
    pool (the unmapped sentinel, `_page_of`'s drop, a dead lane) gives a
    row past the whole pool, in every layer."""
    n_layers, n_pool, ps = cache.k.shape[:3]
    stride = n_pool * ps
    gone = page >= n_pool
    rows = jnp.where(gone, n_layers * stride, page * ps + off)
    rows_s = (page[:, None] * n_kv + jnp.arange(n_kv)[None, :]) * ps + off[:, None]
    rows_s = jnp.where(gone[:, None], n_layers * stride * n_kv, rows_s)
    return rows.astype(jnp.int32), rows_s.astype(jnp.int32), stride


_NO_SERVING_STATE = (
    "recurrent state has no slot on the serving plane yet: a hybrid layer "
    "pattern (linear-attention layers) generates on the static decode "
    "program only (at most max_decode_batch requests, no stop sequences, "
    "no speculative decoding, max_new_tokens within static_path_max_new)"
)


_NO_SERVING_PATTERN = (
    "a Mamba-2 layer's recurrent state and conv tail have no slot on the "
    "serving plane yet, and its chunk has one kind of layer: a pattern of "
    "one-branch layers generates on the static decode program only (at "
    "most max_decode_batch requests, no stop sequences, no speculative "
    "decoding, max_new_tokens within static_path_max_new)"
)


_NO_SERVING_LATENT = (
    "latent rows have no pages on the serving plane yet, and its chunk has "
    "no layer before the scan: latent attention and leading dense layers "
    "generate on the static decode program only (at most max_decode_batch "
    "requests, no stop sequences, no speculative decoding, max_new_tokens "
    "within static_path_max_new)"
)


@jax.named_scope("gen/decode_step")
def decode_step_ragged_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [T] int32 — PACKED token stream
    positions: jax.Array,  # [T] int32 — flat cache position (== RoPE pos)
    cache: PagedKVCache,
    page_table: jax.Array,  # [B, max_pages] int32, sentinel = n_pages
    row_of: jax.Array,  # [T] int32 — owning slot per token; >= B = dead lane
    experts_in_place: Optional[bool] = None,
    paged_kernel: Optional[bool] = None,
) -> Tuple[jax.Array, PagedKVCache]:
    """The serving plane's forward: one packed [T] stream of query lanes
    with per-token windows, instead of a [B, Q] slab with per-row q_lens.

    A slab would pay B*Q query lanes of embed / QKV / MLP / head compute
    per step and MASK the dead ones; here the caller packs only live
    lanes (decode rows contribute 1, chunked-prefill / episode-
    observation / resume-replay rows their slice, spec-verify rows
    pending+drafts) so the whole transformer stack — not just attention
    — runs at ∝ T.  Token t writes its K/V at flat
    position `positions[t]` of slot `row_of[t]` and attends
    [0, positions[t]] through that slot's page-table row
    (`ragged_paged_attention`, which reads the STACKED pool at
    (layer, page): the Pallas kernel on a TPU backend, the XLA per-token
    gather elsewhere; a caller whose mesh spreads the pool or the lanes
    over more than one device passes `paged_kernel=False`, the kernel is
    one device's program).  Dead lanes (row_of >= B, the stream's slack) drop their
    cache writes, emit zero attention, and produce garbage logits the
    caller never reads.  The pool shape never changes during a generate
    call, so the enclosing program compiles exactly once.  A grouped MoE
    model's expert leaves
    reach the ragged kernels as in `decode_step` (`experts_in_place`)."""
    if cfg.is_hybrid:
        raise HybridLayoutError(_NO_SERVING_STATE)
    if cfg.is_latent:
        raise LatentLayoutError(_NO_SERVING_LATENT)
    if cfg.is_pattern:
        raise HybridLayoutError(_NO_SERVING_PATTERN)
    t = tokens.shape[0]
    b = page_table.shape[0]
    live = row_of < b
    rid = jnp.minimum(row_of.astype(jnp.int32), b - 1)
    pt_tok = jnp.take(page_table, rid, axis=0)  # [T, max_pages]
    positions = jnp.where(live, positions, 0).astype(jnp.int32)
    x = _embed(params, cfg, tokens, positions)[:, None, :]  # [T, 1, D]
    cos, sin = rope_cos_sin(positions[:, None], _rope_dim(cfg), cfg.rope_theta)
    wp_page, wp_off = _page_of(pt_tok, positions, cache.page_size)
    # Dead lanes must not scatter (2**30 = the `_page_of` OOB drop).
    wp_page = jnp.where(live, wp_page, jnp.int32(2**30))
    rows0, rows_s0, stride = _pool_rows(
        cache, cfg.n_kv_heads, wp_page, wp_off
    )
    valid_to = jnp.where(live, positions + 1, 0).astype(jnp.int32)
    quant = cache.quantized
    if paged_kernel is None:
        from areal_tpu.base.distributed import is_tpu_backend

        paged_kernel = is_tpu_backend()
    schedule = None
    if paged_kernel:  # the same live pages for every layer: list them once
        from areal_tpu.ops.pallas.paged_attention import live_page_schedule

        schedule = live_page_schedule(
            pt_tok, valid_to, cache.n_pages, cache.page_size,
            cfg.n_q_heads // cfg.n_kv_heads,
        )

    def body(carry, blk):
        y, kc, vc, ksc, vsc, li = carry
        h = _norm(y, blk["ln1"], blk.get("ln1_b"), cfg)
        q, k, v = _block_kv(h, blk, cfg, cos, sin)  # [T, 1, h, d]
        kc, vc, ksc, vsc = _cache_update(
            kc, vc, ksc, vsc, k[:, 0], v[:, 0], li * stride + rows0,
            li * stride * cfg.n_kv_heads + rows_s0, quant,
        )
        attn = ragged_paged_attention(
            q[:, 0], kc, vc, li, pt_tok, valid_to,
            k_scale=ksc if quant else None,
            v_scale=vsc if quant else None,
            use_kernel=paged_kernel, schedule=schedule,
        )
        ao = _attn_out(attn.reshape(t, 1, cfg.q_dim), blk, cfg)
        y = y + ao
        h2 = _norm(y, blk["ln2"], blk.get("ln2_b"), cfg)
        if cfg.is_moe:
            y = y + _mlp_moe(h2, blk, cfg, stacked=stacked, layer=li)[0]
        else:
            y = y + _mlp_dense(h2, blk, cfg)
        return (y, kc, vc, ksc, vsc, li + 1), None

    blocks, stacked = _scan_blocks(cfg, params["blocks"], experts_in_place)
    ksc0 = cache.k_scale if quant else jnp.zeros((0,), jnp.bfloat16)
    vsc0 = cache.v_scale if quant else jnp.zeros((0,), jnp.bfloat16)
    (x, kc, vc, ksc, vsc, _), _ = jax.lax.scan(
        body, (x, cache.k, cache.v, ksc0, vsc0, jnp.int32(0)), blocks
    )
    x = _final_norm(params, cfg, x)
    logits = _head(params, cfg, x)[:, 0]  # [T, V]
    return logits, PagedKVCache(
        k=kc, v=vc,
        k_scale=ksc if quant else None,
        v_scale=vsc if quant else None,
        page_size=cache.page_size,
    )


def copy_pages(
    cache: PagedKVCache,
    src_pages: jax.Array,  # [N] int32 pool page ids (sentinel = padding)
    dst_pages: jax.Array,  # [N] int32 pool page ids (sentinel = padding)
) -> PagedKVCache:
    """Copy whole KV pages src -> dst inside the pool in one gather +
    scatter per tensor — the device half of copy-on-write (the allocator
    hands out the (src, dst) pairs, `PageAllocator.ensure_writable`).
    Padding pairs use the sentinel (>= n_pages): their gather clamps to
    a legal page and the scatter DROPS, so one compiled shape serves any
    number of live copies up to N."""
    n = cache.n_pages
    src = jnp.minimum(src_pages.astype(jnp.int32), n - 1)
    dst = jnp.where(
        dst_pages.astype(jnp.int32) >= n,
        jnp.int32(2**30),
        dst_pages.astype(jnp.int32),
    )
    new = PagedKVCache(
        k=cache.k.at[:, dst].set(cache.k[:, src], mode="drop"),
        v=cache.v.at[:, dst].set(cache.v[:, src], mode="drop"),
        page_size=cache.page_size,
    )
    if cache.quantized:
        new = dataclasses.replace(
            new,
            k_scale=cache.k_scale.at[:, dst].set(
                cache.k_scale[:, src], mode="drop"
            ),
            v_scale=cache.v_scale.at[:, dst].set(
                cache.v_scale[:, src], mode="drop"
            ),
        )
    return new

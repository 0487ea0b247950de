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
- A layer is a tuple of residual branches x += f(norm(x)) and the model is
  `cfg.plan`: prefix + unit x repeats.  ONE function walks it (`_walk`: the
  prefix, then a scan of the repeats with a unit's layers unrolled) and
  every program (the train stack `_blocks`, `prefill`, `decode_step`, the
  serving chunk) is a caller of it that hands each branch to its kind's
  record (`models/branches.py`: `BRANCHES`, filled at the end of this
  module); each branch's leaves are stacked over the layers that have it
  (`_unit_view`, `_unit_layer`, `Branch.leaves`).

Functions:
    init_params(cfg, key)                                  -> params
    forward(params, cfg, tokens, segment_ids[, positions]) -> logits/values
    init_kv_cache(cfg, b, s_max)                           -> cache
    prefill(params, cfg, tokens, segment_ids, cache)
    decode_step(params, cfg, tokens, positions, cache, slot, valid_from)
        — the static decode program's step over a dense window (per-head
          k/v, or one latent row a token: `cfg.is_latent`; a sliding-
          window layer's k/v in a RING of `attn_window` slots)
    init_paged_kv_cache(cfg, n_pages, page_size)           -> pool
    decode_step_ragged_paged(params, cfg, tokens, positions, pool,
                             page_table, row_of)
        — the serving plane's step over a packed token stream
"""

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from areal_tpu.models import (
    latent_select,
    lightning,
    linear_attention,
    mamba,
    short_conv,
)
from areal_tpu.models.branches import (  # noqa: F401 - the errors' callers
    BRANCHES,
    Branch,
    Counter,
    Ctx,
    HybridLayoutError,
    LatentLayoutError,
    Refusal,
    WindowLayoutError,
    branches_of,
    nbytes,
)
from areal_tpu.models.config import (
    ATTENTION,
    DENSE_PREFIX,
    GDN,
    LATENT,
    LATENT_SELECT,
    LATENT_WINDOW,
    LIGHTNING,
    MLP,
    MOE,
    SCONV,
    SPARSE,
    SSM,
    WINDOW,
    LayerKind,
    ModelConfig,
)
from areal_tpu.models.mamba import slot_lanes_of
from areal_tpu.ops import block_sparse
from areal_tpu.ops.attention import (
    decode_attention,
    inner_scope,
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

    # The SCANNED layers (all but the prefix): every leaf keeps ONE leading
    # stack axis (what the sharding rules, the hand-back and the references
    # read), over the layers with the branch that owns it — `scanned` of
    # them; the norms over all L.
    plan = cfg.plan
    L, D, F = cfg.n_scan_layers, cfg.hidden_dim, cfg.intermediate_dim

    def scanned(*branches):
        return plan.repeats * plan.in_unit(*branches)

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

    LM = scanned(MOE)
    n_attn = scanned(ATTENTION, WINDOW, LATENT, SPARSE)
    blocks = {
        "ln1": norm_init((L, D), dtype),
        # latent attention by `window_pattern`: every mixer draws its own
        **(attn_leaves(n_attn, ks)
           if n_attn or not plan.count(LATENT_SELECT, LATENT_WINDOW) else {}),
    }
    if not cfg.is_pattern:  # a second branch a layer: a second norm
        blocks["ln2"] = norm_init((L, D), dtype)
    for name, branch in BRANCHES.items():  # a mixer's leaves, its module's
        if branch.init and scanned(name):
            blocks.update(branch.init(cfg, ks[6], scanned(name), dense))
    if plan.prefix:
        # The leading dense layers: their own leaves, stacked over the
        # leading layers that own them under `dense_*`, the mixers the
        # plan's, the MLP at the dense width.
        K, n_attn = len(plan.prefix), plan.in_prefix(ATTENTION, LATENT)
        kd = jax.random.split(jax.random.fold_in(k_blocks, 1), 9)
        lead = {
            "ln1": norm_init((K, D), dtype),
            **(attn_leaves(n_attn, kd) if n_attn else {}),
            "ln2": norm_init((K, D), dtype),
            "wg": dense(kd[6], (K, D, F), D),
            "wu": dense(kd[7], (K, D, F), D),
            "wd": dense(kd[8], (K, F, D), F),
        }
        for name, branch in BRANCHES.items():
            if branch.init and plan.in_prefix(name):
                lead.update(branch.init(
                    cfg, jax.random.fold_in(k_blocks, 2),
                    plan.in_prefix(name), dense,
                ))
        blocks.update({DENSE_PREFIX + n: w for n, w in lead.items()})
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
    elif scanned(MLP):
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
    if cfg.embedding_multiplier != 1.0:  # granitemoehybrid, in fp32
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
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


def _residual(x: jax.Array, out: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x + residual_multiplier * out: every program's residual add (the
    multiplier in fp32, rounded once to the stream's type; 1.0, every
    other family, is the plain add)."""
    if cfg.residual_multiplier == 1.0:
        return x + out
    return x + (out.astype(jnp.float32) * cfg.residual_multiplier).astype(x.dtype)


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
    scope: Optional[str] = None,
) -> jax.Array:
    with inner_scope(scope):
        return _attn_out_proj(a, blk, cfg, gate, absorbed)


def _attn_out_proj(a, blk, cfg, gate, absorbed):
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
    pass over every (row, choice) pair; `_experts_grouped` touches
    `expert_slab_rows` of them — twice a balanced router's — and the rest
    only where more than that many are held (its overflow, never a drop):
    over packed rows and in prefill wherever that is fewer than all, in a
    decode step where it is half of them or fewer (`decode_slab_rows`)."""
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
        top_w = top_w / (
            jnp.sum(top_w, axis=-1, keepdims=True) + cfg.moe_norm_topk_eps
        )
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


def decode_slab_rows(cfg: ModelConfig, pairs: int) -> int:
    """`expert_slab_rows` for a decode step's `pairs`: the slab where it
    leaves out at least half of them, else all — by the shapes alone.  Under
    that the saving is a fraction of a share of a step that is latency and
    not bytes (a token loop: 64 rows x 2 to 10 choices, one 512-row tile),
    and not worth a loop with a traced bound in the step; a block loop's
    forward routes 64 x 4 x 8 = 2,048 pairs for the 200 a rank of 16 in
    128 holds, and XLA's scatter-add walks its update rows one by one."""
    slab = expert_slab_rows(cfg, pairs)
    if cfg.moe_dispatch == "grouped" and 2 * slab <= pairs:
        return slab
    return pairs


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
    the same experts either way (`expert_leaves_in_place`).  `kernel`:
    this repo's Pallas kernels in `ragged_dot`'s place, where XLA's kernel
    — which tiles by the divisors of the two weight dimensions — is far
    off its roofline at the expert's widths (`expert_kernel_choice`: one
    TPU device and `ragged_tiles_badly`; [2304, 896], [2688, 1856] and
    [2048, 1792] of the benchmark's six).  In a decode step (stacked
    leaves) `grouped_decode_matmul`: XLA streams a [2,688, 1,856] expert at
    a ninth of the bandwidth, the kernel walks an expert in contiguous
    pieces of 0.6 to 8 MB whatever the divisors
    (`ops/pallas/grouped_matmul.py`, `tiles`).  Over packed rows (`layer`
    None) in the GRADIENT program `grouped_matmul`, which brings its own
    gradient rule — forward, dx and dw each walk (row tile, group) pairs in
    tiles of 256 rows against the expert's whole matrix, 2 to 5 times
    `ragged_dot`'s speed at mellum's and nemotron's widths and 1.2 to 1.5
    times at lfm2's (my chip runs, PR 50), under the caller's scope and
    phase where `ragged-dot-none.N` has neither — and zeroes the rows past
    every group itself, so a rank's share drops the `held` masks around
    every call (`_kernel_rows`).  Every other width, a mesh of more than
    one device, every other backend, the forward-only programs over
    packed rows (`forward`, `prefill`: `hidden_states`) and the later-slab
    loop (`_slabs`) keep `ragged_dot`.

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
    stable order (`_local_numbering`).  It therefore runs on a slab of the
    order's first `expert_slab_rows` pairs — twice what a balanced router
    sends here — and not on all T*k: same rows at the same offsets in the
    same groups, and what is left out of the scatter-add were exact zeros.
    Pairs held past the slab go through the same path a slab at a time,
    every group's sizes cut to the slab's window, and their sum is added
    (`_grouped_slabs`): a router that sends this rank more than twice its
    share costs as many slabs as hold its pairs, never a dropped pair.  The
    first slab stays OUTSIDE the loop: the benchmark's readers find a
    program's ragged kernels by the row count of the scoped activation
    product under `layer/mlp/experts` (`benchmark/metrics/_moe.py`).  One
    algorithm on both paths, the slab's size read from the input: the
    unstacked path (`layer` is None: the gradient program, `forward`,
    `prefill`) takes the slab wherever it is fewer than all the pairs, a
    decode step (stacked leaves) where it is half of them or fewer
    (`decode_slab_rows`: a block loop's 2,048 pairs -> 512; a token loop's
    few hundred stay on the one path), its first slab on the decode kernel
    where `kernel` says so.
    """
    with jax.named_scope("dispatch"):
        flat_e = top_idx.reshape(-1)  # [T*k], token-major
        order = jnp.argsort(flat_e, stable=True)
        group_sizes = jnp.sum(one_hot, axis=(0, 1)).astype(jnp.int32)  # [E]
    pairs = order.shape[0]
    slab = (expert_slab_rows if layer is None else decode_slab_rows)(cfg, pairs)
    if slab == pairs:  # every expert held, a share of half, a token loop
        return _grouped_rows(
            x, top_w, order, group_sizes, blk, cfg, layer, kernel
        )
    experts = {n: blk[n] for n in _expert_leaves(cfg)}
    return _grouped_slabs(
        cfg, slab, kernel, x, top_w, experts, order, group_sizes, layer
    )


def _slabs(
    cfg: ModelConfig, slab: int, kernel: bool, order, group_sizes, layer=None
):
    """The sorted pairs in slabs of `slab` -> (rows(i, x, top_w, experts,
    into): `_grouped_rows` over pairs [i*slab, (i+1)*slab) with every
    group's sizes cut to that window; later(body, first): `body(i, carry)`
    from `first` on over the slabs after the first that hold a held pair,
    a loop with a traced bound and, as a rule, no trip).  `layer`: the
    experts are stacked leaves (a decode step)."""
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
        # The kernel is the FIRST slab's (a Python 0): the loop's body, a
        # path that balanced routing never trips, keeps `ragged_dot`.  With
        # the kernels in the loop as well a gradient program took 5.4 s to
        # LOAD from the compile cache where the parent's takes 3.5 and this
        # one 4.3 — eight such programs a cell, + 14 s of a warm set-up of
        # 121 s where this reads + 10 (my chip runs, PR 50, `mellum2-coderl32-
        # 4k`).  A batch that overflows its slab runs XLA's kernel there.
        return _grouped_rows(
            x, top_w, mine, sizes, experts, cfg, layer,
            kernel=kernel and isinstance(i, int), into=into,
        )

    def later(body, first):
        with jax.named_scope("overflow"):
            return jax.lax.fori_loop(
                1, expert_slabs_run(slab, order.shape[0], ends[-1]), body, first
            )

    return rows, later


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _grouped_slabs(
    cfg: ModelConfig, slab: int, kernel: bool, x, top_w, experts, order,
    group_sizes, layer=None,
):
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
    recomputed inside it and its gradients added in place (`kernel`: the
    first slab's expert matmuls are `grouped_matmul`, whose own rule
    composes under `jax.vjp`; the loop's stay `ragged_dot`: `_slabs`).
    `layer` (a decode step's scan index, `experts` the stacked leaves)
    rides as an ordinary argument without a gradient."""
    rows, later = _slabs(cfg, slab, kernel, order, group_sizes, layer)
    return _slab_sum(rows, later, rows(0, x, top_w, experts), x, top_w, experts)


def _slab_sum(rows, later, first, x, top_w, experts):
    return first + later(
        lambda i, out: rows(i, x, top_w, experts, out), jnp.zeros_like(first)
    )


def _grouped_slabs_fwd(
    cfg, slab, kernel, x, top_w, experts, order, group_sizes, layer=None
):
    rows, later = _slabs(cfg, slab, kernel, order, group_sizes, layer)
    first, first_vjp = jax.vjp(functools.partial(rows, 0), x, top_w, experts)
    out = _slab_sum(rows, later, first, x, top_w, experts)
    return out, (first_vjp, x, top_w, experts, order, group_sizes, layer)


def _grouped_slabs_bwd(cfg, slab, kernel, res, ct):
    first_vjp, x, top_w, experts, order, group_sizes, layer = res
    rows, later = _slabs(cfg, slab, kernel, order, group_sizes, layer)

    def add(i, grads):
        more = jax.vjp(functools.partial(rows, i), x, top_w, experts)[1](ct)
        return jax.tree.map(jnp.add, grads, more)

    return (*later(add, first_vjp(ct)), None, None, None)


_grouped_slabs.defvjp(_grouped_slabs_fwd, _grouped_slabs_bwd)


# The expert matmuls traced so far on the unstacked grouped dispatch (the
# programs over packed rows), and those of them that were the Pallas kernel
# `grouped_matmul`: Python counts, taken while a program is traced
# (`expert_matmuls_traced`).
_EXPERT_MATMULS = {"calls": 0, "kernel": 0}


def expert_matmuls_traced() -> Tuple[int, int]:
    """(expert matmuls traced on the unstacked grouped dispatch since the
    process started, those handed to `grouped_matmul`): read before and
    after tracing a program, the difference says which kernel its experts
    run on (the train engine's `moe/expert_matmul_calls` and
    `moe/grouped_kernel_calls`)."""
    return _EXPERT_MATMULS["calls"], _EXPERT_MATMULS["kernel"]


def expert_kernel_choice(cfg: ModelConfig, choice: Optional[bool]) -> bool:
    """Whether a program's expert matmuls are this repo's Pallas kernels
    (`ops/pallas/grouped_matmul.py`) in `ragged_dot`'s place.  `choice`
    None: by what the code can see — a TPU backend and an expert matrix
    that XLA's ragged-dot kernel tiles badly (`ragged_tiles_badly`); a
    caller whose mesh has more than one device passes False (the kernels
    are one device's program); a bool forces either (interpreted off a
    TPU)."""
    if choice is not None:
        return bool(choice)
    from areal_tpu.base.distributed import is_tpu_backend
    from areal_tpu.ops.pallas.grouped_matmul import ragged_tiles_badly

    return is_tpu_backend() and ragged_tiles_badly(
        cfg.hidden_dim, cfg.moe_intermediate_dim
    )


def _grouped_rows(
    x, top_w, order, group_sizes, blk: Params, cfg: ModelConfig, layer=None,
    kernel: bool = False, into=None,
):
    """`_experts_grouped` from the sort on, over the (row, choice) pairs
    `order` names — indices into the token-major [T*k] pairs, sorted by
    expert — in groups of `group_sizes`: gather, the experts, weight,
    scatter-add -> [T, D], zero for a row none of whose pairs is here.
    `into`: a sum to add these pairs' to (the later slabs of a share add
    up in one buffer).  `kernel`: the expert matmuls are the Pallas kernels
    `grouped_matmul` (`layer` None; forward, dx and dw: `_kernel_rows`) or
    `grouped_decode_matmul` (stacked leaves) and not `ragged_dot`."""
    if kernel and layer is None:
        from areal_tpu.ops.pallas.flash_attention import _interpret

        experts = {n: blk[n] for n in _expert_leaves(cfg)}
        _EXPERT_MATMULS["calls"] += len(experts)
        _EXPERT_MATMULS["kernel"] += len(experts)
        # The mesh context spelled out: JAX keys a `jit`'s cached trace on
        # it, and reads "none" (a layer's first trace) and "the empty mesh"
        # (the same layer under autodiff of the traced scan) as two — the
        # block, its tables and its forward kernels traced twice a program.
        assert into is None  # the first slab's, or every pair's
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            return _kernel_rows(
                cfg, _interpret(), x, top_w, order, group_sizes, experts
            )
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
        if cfg.expert_share and not kernel:
            held = (jnp.arange(xs.shape[0]) < jnp.sum(group_sizes))[:, None]

        def ragged(lhs, w):
            if layer is None:
                _EXPERT_MATMULS["calls"] += 1
            if kernel:
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

        ys = _expert_mlp(cfg, ragged, xs, blk)  # [T*k, D]
    with jax.named_scope("combine"):
        w_sorted = top_w.reshape(-1)[order].astype(ys.dtype)
        out = jnp.zeros_like(x) if into is None else into
        return out.at[tok_of].add(ys * w_sorted[:, None])


def _expert_mlp(cfg: ModelConfig, ragged, xs, blk: Params):
    """An expert's matrices over its rows: `ragged(lhs, w)` the grouped
    matmul."""
    if cfg.mlp_gated:
        gate = jax.nn.silu(ragged(xs, blk["wg"]))
        return ragged(gate * ragged(xs, blk["wu"]), blk["wd"])
    # down(act(up(x))): two matrices an expert
    return ragged(_act(ragged(xs, blk["wu"]), cfg), blk["wd"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kernel_rows(
    cfg: ModelConfig, interpret: bool, x, top_w, order, group_sizes, experts
):
    """`_grouped_rows` on the Pallas kernel `grouped_matmul`, as ONE
    function of the program: the same block of the same shapes in each of
    a unit's unrolled layers and in the remat's forward is one traced
    jaxpr (and its linearisation and transpose one each, found in JAX's
    caches by it) and one private function of the lowered module, called
    from every site.  Traced at every site — 96
    expert matmuls a gradient program, each a gradient rule, its visit
    tables and a kernel body lowered to a Mosaic module — the host's work
    before the compile cache is asked took + 3.4 s a program and a warm
    set-up + 93% (PR 49's tree on a CPU host, and the driver's runs of it;
    PERF.md section 6, PR 50).  The visit tables are made once for the
    slab's two or three matrices and every pass over them.  Rows past
    every group come out of the kernels ZERO, forward and as a cotangent
    on the way back, so a rank's share needs no `held` mask here.
    `interpret`: the backend's answer, asked by the caller — outside this
    `jit`, whose cached trace must not be another backend's."""
    from areal_tpu.ops.pallas.grouped_matmul import grouped_matmul, visits

    with jax.named_scope("dispatch"):
        tok_of = order // cfg.n_experts_per_tok
        xs = x[tok_of]  # [T*k, D] sorted by expert
    with jax.named_scope("experts"):
        tables = visits(group_sizes, xs.shape[0])
        ys = _expert_mlp(
            cfg,
            lambda lhs, w: grouped_matmul(
                lhs, w, group_sizes, tables, interpret
            ),
            xs, experts,
        )
    with jax.named_scope("combine"):
        w_sorted = top_w.reshape(-1)[order].astype(ys.dtype)
        return jnp.zeros_like(x).at[tok_of].add(ys * w_sorted[:, None])


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


# The dispatches beside the grouped one (`_experts_grouped`, which `_mlp_moe`
# calls itself: it alone takes stacked leaves and a kernel).
_MOE_EXPERTS = {"dense": _experts_dense, "topk": _experts_topk}


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
    (`_experts_grouped`); without it `layer` is not read.  `kernel`: the
    grouped dispatch's matmuls are the Pallas kernels of
    `ops/pallas/grouped_matmul.py` and not `ragged_dot`.

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
    if stacked is None and cfg.moe_dispatch != "grouped":
        out = _MOE_EXPERTS[cfg.moe_dispatch](x, top_w, top_idx, one_hot, blk, cfg)
    else:
        out = _experts_grouped(
            x, top_w, top_idx, one_hot, blk if stacked is None else stacked,
            cfg, None if stacked is None else layer, kernel,
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


def _attention(
    h: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    use_flash: "bool | None" = None,
    cp_mesh=None,
    cp_manual: "Optional[Tuple[str, int]]" = None,
    cp_zigzag: bool = False,
    window: Optional[int] = None,
    scope: Optional[str] = None,
    blocks=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The attention branch (softmax over per-head k/v, or latent) over
    packed rows of normed `h` -> (its output, what it leaves in the cache:
    k and v, or the one latent row a token).  `window`: a query sees the
    last `window` keys of its sequence (a window layer; None, all of
    them); `scope`: the layer's inner name in a mixed plan (`inner_scope`);
    `blocks`: the block-causal mask's ids (`Ctx.block_mask`)."""
    b, s, _ = h.shape
    if cfg.is_latent:
        q, k, v, row = _latent_qkv(h, blk, cfg, cos, sin)
        left = {"latent": row}
    else:
        q, k, v = _block_kv(h, blk, cfg, cos, sin, scope)
        left = {"k": k, "v": v}
    if cp_manual is None and cp_mesh is None:
        attn = packed_attention(
            q, k, v, segment_ids, causal=True, use_flash=use_flash,
            window=window, scope=scope, blocks=blocks,
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
    out = _attn_out(
        attn.reshape(b, s, cfg.q_dim), blk, cfg, _attn_gate(h, blk, cfg),
        scope=scope,
    )
    return out, left


def _ring_tail(x: jax.Array, ring: int) -> jax.Array:
    """x [B, S, ...] by slot -> the ring [B, ring, ...] a window layer's
    cache keeps once slots [0, S) are written: slot s at entry s mod ring,
    the last `ring` slots where S is more."""
    s = x.shape[1]
    if s <= ring:
        return jnp.pad(x, ((0, 0), (0, ring - s)) + ((0, 0),) * (x.ndim - 2))
    return jnp.roll(x[:, s - ring:], (s - ring) % ring, axis=1)


def ring_valid(slot: jax.Array, valid_from: jax.Array, ring: int) -> jax.Array:
    """[B, ring] bool: the entries of a window layer's ring a decode step
    reads once slot `slot` is written.  Entry j holds the last slot at or
    before `slot` that is j modulo `ring`; it is live where that slot is
    one the row has written (not before `valid_from`, not before the
    cache's first).  Every such slot lies within `ring` <= attn_window of
    `slot`, so the ring's live entries ARE the window."""
    j = jnp.arange(ring, dtype=jnp.int32)
    held = slot - (slot - j) % ring
    return held[None, :] >= jnp.maximum(valid_from, 0)[:, None]


def _rope(cfg: ModelConfig, positions: jax.Array):
    """The rotary tables of one forward -> ((cos, sin) of the full layers
    and of every plan without window layers, (cos, sin) of the window
    layers or None without one).  YaRN (`cfg.rope_yarn_factor`) is the
    full layers'; a window layer takes plain rope."""
    yarn = None
    if cfg.rope_yarn_factor:
        yarn = (
            cfg.rope_yarn_factor, cfg.rope_yarn_original,
            cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow,
            cfg.rope_yarn_attention_factor
            or 0.1 * math.log(cfg.rope_yarn_factor) + 1.0,
        )
    dim, theta = _rope_dim(cfg), cfg.rope_theta
    with jax.named_scope("rope/yarn" if yarn else "rope/plain"):
        full = rope_cos_sin(positions, dim, theta, yarn)
    if not cfg.plan.count(WINDOW, LATENT_WINDOW):
        return full, None
    window_theta = cfg.window_rope_theta or theta
    if yarn is None and window_theta == theta:
        return full, full
    with jax.named_scope("rope/plain"):
        return full, rope_cos_sin(positions, dim, window_theta)


# The attention and MLP kinds over packed rows (`Branch.packed`): f(ctx, h,
# blk) -> (its output, what else it gives by name — `aux`, the MoE aux
# loss; `counts`, rows per expert over the real tokens [E] int32; and what
# it leaves in the cache, by `KVCache` field).


def _attention_packed(ctx: Ctx, h, blk):
    return _attention(
        h, blk, ctx.cfg, ctx.segment_ids, ctx.cos, ctx.sin, ctx.use_flash,
        ctx.cp_mesh, ctx.cp_manual, ctx.cp_zigzag,
        scope="full" if ctx.window_rope else None, blocks=ctx.block_mask,
    )


def _window_packed(ctx: Ctx, h, blk):
    """The window layers' own rotary table; where the caller keeps what
    they leave, the ring a row's last slots fill (`_ring_tail`)."""
    out, left = _attention(
        h, blk, ctx.cfg, ctx.segment_ids, *ctx.window_rope, ctx.use_flash,
        ctx.cp_mesh, ctx.cp_manual, ctx.cp_zigzag,
        window=ctx.cfg.attn_window, scope="window",
    )
    if ctx.ring is None:
        return out, {}
    return out, {
        "wk": _ring_tail(left["k"], ctx.ring),
        "wv": _ring_tail(left["v"], ctx.ring),
    }


def _sparse_packed(ctx: Ctx, h, blk):
    """Softmax attention by block selection (`ops/block_sparse.py`):
    no positions, the sigmoid output gate; it leaves k, v and, for a
    cache, the compressed keys of the row's sequence."""
    cfg, segment_ids = ctx.cfg, ctx.segment_ids
    b, s, _ = h.shape
    q, k, v = _block_kv(h, blk, cfg, ctx.cos, ctx.sin)
    sizes = block_sparse.Sizes.of(cfg)
    # By what the code can see (None) the kernels serve the GRADIENT
    # programs alone: there they replace three recomputations of
    # `attend` by two of a faster one.  A program with no backward
    # (`forward`, prefill) keeps the `jnp` form although the kernels
    # are 2.7 times faster there too (PERF.md section 6, PR 56): with
    # them in the programs the reference check runs at set-up, a warm
    # run that followed a parent's read `peak_hbm_gb` 214 MB higher
    # (section 7).  True forces them anywhere.
    use_flash = ctx.use_flash
    if use_flash is None and not ctx.backward:
        use_flash = False
    attn, kc, knum = block_sparse.packed_attention(
        q, k, v, segment_ids, sizes, use_flash=use_flash)
    out = _attn_out(
        attn.reshape(b, s, cfg.q_dim), blk, cfg, _attn_gate(h, blk, cfg))
    left = {"k": k, "v": v}
    if ctx.ck_slots is not None:
        left["ck"] = block_sparse.compressed_of_last(
            kc, knum, segment_ids, sizes, ctx.ck_slots)
    return out, left


def _mlp_packed(ctx: Ctx, h, blk):
    return _mlp_dense(h, blk, ctx.cfg), {"aux": jnp.zeros((), jnp.float32)}


def _moe_packed(ctx: Ctx, h, blk):
    out, aux, counts = _mlp_moe(
        h, blk, ctx.cfg, valid=ctx.segment_ids > 0, kernel=ctx.expert_kernel
    )
    return out, {"aux": aux, "counts": counts}


def _packed_ctx(
    cfg: ModelConfig, segment_ids, cos, sin, use_flash, remat=None,
    expert_kernel: Optional[bool] = False, **more,
) -> Ctx:
    """The context of a GRADIENT stack over packed rows (`_blocks`, a
    pipeline stage).  `expert_kernel`: whether the grouped dispatch's
    matmuls are the Pallas kernel `grouped_matmul`
    (`expert_kernel_choice`); `remat`: the policy the caller
    differentiates the stack under — where `use_flash` is None a
    block-sparse layer takes the flash kernels under one alone
    (`_sparse_packed`)."""
    return Ctx(
        cfg, cos, sin, segment_ids, use_flash=use_flash,
        backward=remat not in (False, None, "none"),
        expert_kernel=cfg.is_moe and expert_kernel_choice(cfg, expert_kernel),
        **more,
    )


def _packed_call(ctx: Ctx):
    """A walk's `call` over packed rows: no carry, no place in one."""

    def call(branch, h, blk, carry, li, at):
        out, left = BRANCHES[branch].packed(ctx, h, blk)
        return out, None, left

    return call


# The norm of a layer's first and second branch (in front of it, or over
# its output: `cfg.branch_norm`).
_BRANCH_NORMS = ("ln1", "ln2")


def _layer_of(
    cfg: ModelConfig, kind: LayerKind, call, gives, wrap=None, nth=None
):
    """ONE layer of `kind` as f(x, blk, carry, pi) -> (y, carry, what its
    branches gave of the names `gives`): x += f(norm(x)) a branch — or,
    with `cfg.branch_norm` "output", x += norm(f(x)), the branch fed the
    raw stream and the same `ln1` / `ln2` leaf over what it puts out
    (scope `layer/post_norm`) — the branch through `call(branch, h, blk,
    carry, li, at) -> (out, carry, what it gives by name)` — the one loop
    over a layer's branches every program runs.

    `nth`: for a walk with a carry, the layer's index, a branch, among
    the prefix's layers with the branch or, in scan step `pi`, among the
    unit's: `li` is then the layer's index among ALL the plan's layers
    with the branch — behind the prefix's and the earlier steps' — and
    `at` (nth, pi); both None without.
    `wrap`: a gradient stack's remat policy (`_remat_layer`'s, bound):
    the branches' outputs are the named checkpoints of `Branch.saved_as`
    — what is ADDED to the stream, so with the norm on the output the
    NORMED output: the backward of the add never recomputes the norm —
    every layer gives an `aux` (zero where no branch has one), and the
    layer goes under the policy whole or, with a `Branch.remat_alone`
    kind, a branch at a time."""
    plan = cfg.plan
    post_norm = cfg.branch_norm == "output"

    def branch_step(branch, ln):
        def step(x, blk, carry, pi):
            li = at = None
            if nth is not None:
                li, at = nth[branch], (nth[branch], pi)
                if pi is not None:
                    n, lead = plan.in_unit(branch), plan.in_prefix(branch)
                    li = pi if n == 1 else pi * n + li
                    li = li + lead if lead else li
            if post_norm:
                out, carry, gave = call(branch, x, blk, carry, li, at)
                with jax.named_scope("layer/post_norm"):
                    out = _norm(out, blk[ln], blk.get(ln + "_b"), cfg)
            else:
                h = _norm(x, blk[ln], blk.get(ln + "_b"), cfg)
                out, carry, gave = call(branch, h, blk, carry, li, at)
            if wrap is not None:
                out = checkpoint_name(out, BRANCHES[branch].saved_as)
            return _residual(x, out, cfg), carry, {
                n: gave[n] for n in gives if n in gave}

        return step

    alone = wrap is not None and any(BRANCHES[b].remat_alone for b in kind)
    steps = [branch_step(b, ln) for b, ln in zip(kind, _BRANCH_NORMS)]
    if alone:
        steps = [wrap(step) for step in steps]

    def layer(x, blk, carry, pi):
        gave = {}
        for step in steps:
            x, carry, more = step(x, blk, carry, pi)
            gave.update(more)
        if wrap is not None and "aux" not in gave:
            gave["aux"] = jnp.zeros((), jnp.float32)
        return x, carry, gave

    return layer if wrap is None or alone else wrap(layer)


def _block_forward(
    x: jax.Array,
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    use_flash: "bool | None" = None,
    cp_manual: "Optional[Tuple[str, int]]" = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """The (attention, MLP) layer a pipeline stage scans
    (`parallel/pipeline.py`), the ring's manual region handed through ->
    (y, MoE aux loss, rows per expert; None without experts)."""
    ctx = _packed_ctx(cfg, segment_ids, cos, sin, use_flash, cp_manual=cp_manual)
    y, _, gave = _layer_of(
        cfg, cfg.plan.unit[-1],
        _packed_call(ctx),
        ("aux", "counts"), wrap=lambda layer: layer,
    )(x, blk, None, None)
    return y, gave["aux"], gave.get("counts")


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
    expert_kernel: Optional[bool] = False,
    row_kernel=None,
    stream_ids=None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """-> (final-normed hidden states, summed MoE aux loss, per-layer rows
    per expert [L, E] int32 — None for dense models and under PP).
    `stream_ids` (`cfg.block_length` alone; None, every token clean): the
    rows' two streams, `engines/packing.py`."""
    x = _embed(params, cfg, tokens, positions)
    (cos, sin), window_rope = _rope(cfg, positions)

    refusal = plan_refusal(cfg, serving=False)
    if refusal and (cp_mesh is not None or pp_mesh is not None):
        raise refusal
    block_mask = _block_ids(cfg, positions, stream_ids)
    if block_mask is not None and (
        cp_mesh is not None or pp_mesh is not None
    ):
        raise BlockLayoutError(_BLOCK_REFUSAL.layout)

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

    x, auxes, counts = _blocks(
        params["blocks"], cfg, x, segment_ids, cos, sin, remat, use_flash,
        cp_mesh, cp_zigzag=zz_inv is not None, window_rope=window_rope,
        expert_kernel=expert_kernel, row_kernel=row_kernel,
        block_mask=block_mask,
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


# A plan of one-branch layers is refused for its SHAPE, whatever its kinds.
_PATTERN_REFUSAL = Refusal(
    HybridLayoutError,
    "a pattern of one-branch layers (Mamba-2, experts or attention alone) "
    "runs under data and fsdp sharding only: the Mamba heads, their conv "
    "channels and their state are not split over `model`, the chunked scan "
    "has no ring over a split sequence, and the pipeline has no stage of a "
    "pattern's layers (PERF.md section 7)",
    "a Mamba-2 layer's recurrent state and conv tail have no slot on the "
    "serving plane yet, and its chunk has one kind of layer: a pattern of "
    "one-branch layers generates on the static decode program only (at "
    "most max_decode_batch requests, no stop sequences, no speculative "
    "decoding, max_new_tokens within static_path_max_new)",
)


def plan_refusal(cfg: ModelConfig, serving: bool):
    """What of `cfg.plan` the serving plane (`serving`; its chunk
    `decode_step_ragged_paged` walks a plan of two-branch layers whose
    kinds have a `Branch.serve`) or else a mesh split over `model`, `seq`
    or `pipe` cannot run yet -> the error to raise, by name, or None for a
    plan both can: every refusal of a plane or a layout asks here.  The
    first kind of the table that refuses speaks; leading layers are
    refused in latent attention's words, which name them."""
    if cfg.block_length:
        return _BLOCK_REFUSAL.of(serving)
    if cfg.is_pattern:
        return _PATTERN_REFUSAL.of(serving)
    refusing = set(branches_of(cfg)) | ({LATENT} if cfg.plan.prefix else set())
    for name, branch in BRANCHES.items():
        if name in refusing and branch.refusal and branch.refusal.of(serving):
            return branch.refusal.of(serving)
    return None


# The attention and MLP kinds' block leaves (each mixer module's beside its
# own code): a leaf is stacked over the layers with a branch that owns it,
# in layer order (attention's and latent attention's share `wo`, a dense
# MLP's and the experts' `wg` / `wu` / `wd`: one of the two a model; a
# window layer has a full layer's leaves, stacked with them in layer
# order).  `ln1` is every layer's, `ln2` every layer's with a second branch
# (`_owns`).
_FULL_ATTN_LEAVES = (
    "wq", "wk", "wv", "wo", "wqg", "bq", "bk", "bv", "bo", "q_norm", "k_norm",
)
_LATENT_LEAVES = (
    "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wk_b", "wv_b",
)
_MOE_LEAVES = (
    "router", "router_bias", "wg", "wu", "wd", "ws_g", "ws_u", "ws_d",
    "ws_gate",
)


def _leaf_owners() -> Dict[str, list]:
    """Block leaf -> the kinds whose records list it."""
    owners = {}
    for name, branch in BRANCHES.items():
        for leaf in branch.leaves:
            owners.setdefault(leaf, []).append(name)
    return owners


def _owns(kind: LayerKind, leaf: str, owners=None) -> bool:
    """Whether a layer of this kind has the block leaf (`owners`:
    `_leaf_owners()`, for a caller that asks of many leaves)."""
    if leaf.startswith("ln2"):
        return len(kind) > 1
    kinds = (_leaf_owners() if owners is None else owners).get(leaf)
    return not kinds or any(b in kind for b in kinds)


def _unit_view(cfg: ModelConfig, blocks: Params) -> Params:
    """What the layer scan slices: the scanned block leaves with the stack
    axis split by scan step, [repeats, the unit's layers that own the
    leaf, ...] — a leaf ONE layer of the unit owns stays [repeats, ...].
    Leading-axis reshapes: no data moves, and a unit of one layer gets the
    leaves as they are.  The prefix's leaves are not in it."""
    plan, out, by_leaf = cfg.plan, {}, _leaf_owners()
    for name, w in blocks.items():
        if name.startswith(DENSE_PREFIX):
            continue
        n = sum(_owns(kind, name, by_leaf) for kind in plan.unit)
        out[name] = w if n == 1 else w.reshape(plan.repeats, n, *w.shape[1:])
    return out


def _unit_layer(cfg: ModelConfig, step: Params, j: int):
    """Layer j of one scan step's slice of `_unit_view` -> (its kind, for
    each of its branches the layer's index among the unit's layers with
    that branch, its leaves)."""
    unit, by_leaf = cfg.plan.unit, _leaf_owners()
    blk = {}
    for name, w in step.items():
        owners = [
            i for i, kind in enumerate(unit) if _owns(kind, name, by_leaf)]
        if j in owners:
            blk[name] = w if len(owners) == 1 else w[owners.index(j)]
    index = {b: sum(b in kind for kind in unit[:j]) for b in unit[j]}
    return unit[j], index, blk


def _prefix_layers(cfg: ModelConfig, blocks: Params) -> list:
    """The prefix's layers, each as `_unit_layer` gives a unit's — (kind,
    its index among the prefix's layers with each of its branches, its
    leaves under the layer's own names); [] without leading layers."""
    prefix, by_leaf = cfg.plan.prefix, _leaf_owners()
    lead = {
        n[len(DENSE_PREFIX):]: w
        for n, w in blocks.items() if n.startswith(DENSE_PREFIX)
    }
    return [
        (
            kind,
            {b: sum(b in k for k in prefix[:i]) for b in kind},
            {  # a leaf is stacked over the leading layers that own it
                n: w[sum(_owns(k, n, by_leaf) for k in prefix[:i])]
                for n, w in lead.items() if _owns(kind, n, by_leaf)
            },
        )
        for i, kind in enumerate(prefix)
    ]


def _unit_outputs(per_layer: list):
    """What the unit's layers put out in one scan step (rows per expert,
    what a branch leaves in the cache; None from a layer without) as the
    scan's output: stacked over the layers that have one, the layer's own
    where that is one layer of the unit, None where none.  `_layer_outputs`
    undoes it after the scan."""
    have = [x for x in per_layer if x is not None]
    if len(have) <= 1:
        return have[0] if have else None
    return jnp.stack(have)


def _layer_outputs(n_in_unit: int, stacked, lead=()):
    """A scan's `_unit_outputs` [repeats, layers of the unit with one, ...]
    -> [layers with one, ...], the prefix's `lead` first."""
    if stacked is not None and n_in_unit > 1:
        stacked = stacked.reshape(-1, *stacked.shape[2:])
    if not lead:
        return stacked
    return jnp.concatenate([jnp.stack(lead), stacked])


def _walk(
    cfg: ModelConfig, blocks: Params, x, carry, call, gives=(), summed=(),
    wrap=None, unroll: bool = False,
):
    """THE walk of `cfg.plan`, every program's: the prefix's layers one by
    one, then ONE `lax.scan` over the repeats of the plan's unit, a unit's
    layers unrolled inside it, each layer `_layer_of(call, wrap)` — the
    train stack `_blocks`, `prefill`, `decode_step` and the serving chunk
    `decode_step_ragged_paged` differ in `call`, in what they carry and in
    what they read of what the layers give.

    `carry`: what the layers hand on beside x (a cache; None over packed
    rows), a scan carry.  `gives`: the names read of what the branches
    give, in the order the scan puts them out -> out[name], stacked over
    the layers that gave one, the prefix's first (None where none did) —
    a name of `summed` instead the unit's sum, a scan step.  `unroll`: a
    plan of ONE repeat steps its unit without a scan, at the static step 0.
    -> (x, carry, out)."""
    plan = cfg.plan
    indexed = carry is not None
    layers, in_unit = {}, {}

    def layer(kind, nth):
        """`_layer_of`, made once a kind where no layer asks its place."""
        key = (kind, tuple(nth.items()) if indexed else None)
        if key not in layers:
            layers[key] = _layer_of(
                cfg, kind, call, gives, wrap, nth if indexed else None)
        return layers[key]

    def body(state, step):
        y, carry, pi = state
        gave = {name: [] for name in gives}
        for j in range(len(plan.unit)):
            kind, nth, blk = _unit_layer(cfg, step, j)
            y, carry, more = layer(kind, nth)(y, blk, carry, pi)
            for name in gives:
                if name in summed and gave[name]:
                    gave[name] = [gave[name][0] + more[name]]
                else:
                    gave[name].append(more.get(name))
        for name in gives:
            in_unit[name] = sum(g is not None for g in gave[name])
        return (y, carry, pi if pi is None else pi + 1), tuple(
            _unit_outputs(gave[name]) for name in gives)

    lead = []  # the prefix's layers come first in their populations
    for kind, nth, blk in _prefix_layers(cfg, blocks):
        x, carry, more = layer(kind, nth)(x, blk, carry, None)
        lead.append(more)
    view = _unit_view(cfg, blocks)
    if unroll:
        (x, carry, _), stacked = body(
            (x, carry, 0), jax.tree.map(lambda w: w[0], view))
    else:
        (x, carry, _), stacked = jax.lax.scan(
            body, (x, carry, jnp.int32(0) if indexed else None), view)
    return x, carry, {
        name: ys if name in summed else _layer_outputs(
            in_unit[name], ys, [g[name] for g in lead if name in g])
        for name, ys in zip(gives, stacked)
    }


def _blocks(
    blocks: Params, cfg: ModelConfig, x, segment_ids, cos, sin, remat,
    use_flash, cp_mesh=None, cp_zigzag: bool = False, window_rope=None,
    expert_kernel: Optional[bool] = False, row_kernel=None, block_mask=None,
):
    """The block stack of every model over packed rows, every layer (all
    its branches, or each on its own: `_layer_of`) under the remat policy.
    `row_kernel`: the form of the Gated DeltaNet's chunked rule
    (`linear_attn_forward`'s `kernel`: None, by what the code can see; the
    caller's MESH where it has more than one device).
    -> (x, aux loss per repeat, rows per expert [n_moe_layers, E])."""
    ctx = _packed_ctx(
        cfg, segment_ids, cos, sin, use_flash, remat, expert_kernel,
        cp_mesh=cp_mesh, cp_zigzag=cp_zigzag, window_rope=window_rope,
        row_kernel=row_kernel, block_mask=block_mask,
    )
    x, _, gave = _walk(
        cfg, blocks, x, None,
        _packed_call(ctx),
        gives=("aux", "counts"), summed=("aux",),
        wrap=functools.partial(_remat_layer, remat=remat),
    )
    return x, gave["aux"], gave["counts"]


@jax.named_scope("head_logprob")
def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.is_critic:
        v = jnp.einsum(
            "bsd,dk->bsk", x, params["value_head"],
            preferred_element_type=jnp.float32,
        )
        return v[..., 0]  # [B, S] fp32 values
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, head, preferred_element_type=jnp.float32
    )  # [B, S, V] fp32 logits
    if cfg.logits_scaling != 1.0:  # before any softmax, temperature, top-k/p
        logits = logits / cfg.logits_scaling
    return logits


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
    expert_kernel: Optional[bool] = False,
    row_kernel=None,
    stream_ids=None,
) -> Tuple[jax.Array, ...]:
    """Backbone only: final-layernormed hidden states [B, S, D] (+ MoE aux
    loss), WITHOUT the LM head.  Lets engines fuse the head into a chunked
    loss (ops/functional.fused_next_token_logprobs) instead of materializing
    [B, S, V] logits.  `with_moe_counts` adds a third result: the real
    tokens' rows per expert of every layer, [L, E] int32 (None for dense
    models and under PP) — the trainer's load counter.  `expert_kernel`:
    whether a grouped MoE model's expert matmuls are the Pallas kernel
    `grouped_matmul` and not `ragged_dot` — the GRADIENT program's, which
    passes None on one device (`expert_kernel_choice`); every forward-only
    program (`forward`, `prefill`, this function's other callers) keeps
    `ragged_dot`: it holds a quarter of the kernels' seconds, it is most of
    the programs that would carry them at set-up, and its text stays the
    parent's, so what a generator samples does not move (PERF.md section
    6, PR 50).  `row_kernel`: the form of a Gated DeltaNet layer's chunked
    rule (`linear_attention.linear_attn_forward`): None, the backend's, on
    one device; a caller whose mesh has more passes the MESH."""
    if positions is None:
        positions = positions_from_segments(segment_ids)
    x, aux, counts = _backbone(
        params, cfg, tokens, segment_ids, positions, remat, use_flash,
        cp_mesh, pp_mesh, pp_microbatches, expert_kernel, row_kernel,
        stream_ids,
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

    if cfg.logits_scaling != 1.0:
        # The fused head never holds the logits: the hidden states take
        # the division (granite's 8 is a power of two: exact in bf16).
        x = (x.astype(jnp.float32) / cfg.logits_scaling).astype(x.dtype)
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
    logits = _head(params, cfg, x)
    if cfg.block_length:  # in place, the mask token's logit left out
        logits = mask_logit_out(cfg, logits)
    return logits, aux


# --------------------------------------------------------------------------
# KV-cache generation path
# --------------------------------------------------------------------------


@dataclasses.dataclass
class KVCache:
    """The static decode program's cache, one population a kind of branch
    (`cfg.plan`), each stacked over the layers that have the branch and
    None where none does — the rows the kinds' records say a layer keeps
    (`Branch.cache`, [layers, B, ...] each; an MLP or expert branch keeps
    nothing):

    - `k` / `v`: softmax and block-sparse attention's per-head keys and
      values by slot, full precision (the int8 mode lives on the serving
      plane's `PagedKVCache`);
    - `latent`, in their place: latent attention's ONE row a token — the
      normed latent vector beside the roped key part all heads share;
    - `state` (fp32) and `conv`: a recurrent branch's state and its causal
      conv's last inputs (Gated DeltaNet, Mamba-2; Lightning attention a
      state alone, the gated short convolution a tail alone);
    - `wk` / `wv`: a sliding-window layer's ring of min(attn_window, S_max)
      slots — slot s at entry s mod ring, so the ring holds the last `ring`
      slots written and nothing older (`ring_valid`);
    - `ck`: a block-sparse layer's COMPRESSED keys, one row a
      `sparse_kernel_stride` tokens by kernel number within the row's
      sequence, appended as kernels complete
      (`block_sparse.compressed_step`);
    - `ikeys`: beside a selected latent layer's rows, the token indexer's
      key a slot; `wlatent`: a latent window layer's ring of latent rows
      (`models/latent_select.py`)."""

    k: Optional[jax.Array]
    v: Optional[jax.Array]
    state: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None
    ck: Optional[jax.Array] = None
    ikeys: Optional[jax.Array] = None
    wlatent: Optional[jax.Array] = None

    @property
    def s_max(self) -> int:
        return (self.latent if self.k is None else self.k).shape[2]

    @property
    def ring(self) -> Optional[int]:
        """Entries of the window layers' rings; None without one."""
        rings = self.wlatent if self.wk is None else self.wk
        return None if rings is None else rings.shape[2]


jax.tree_util.register_dataclass(
    KVCache, data_fields=[f.name for f in dataclasses.fields(KVCache)],
    meta_fields=[],
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
    """What the plan's kinds keep (`Branch.cache`), each population over
    the layers of every kind that keeps it."""
    dtype, plan, kinds = dtype or cfg.dtype, cfg.plan, branches_of(cfg)
    keep = {}
    for branch in kinds.values():
        keep.update({f: row for f, row in branch.cache.items() if f not in keep})
    if "latent" not in keep:
        # Without an attention layer: no layers of k/v, the window's length
        # (`KVCache.s_max`).
        keep = {**_KV, **keep}
    kept = {"k": None, "v": None}
    for field, row in keep.items():
        shape, kind = row(cfg, batch, s_max, dtype)
        layers = plan.count(*(n for n, b in kinds.items() if field in b.cache))
        kept[field] = jnp.zeros((layers, *shape), kind)
    return KVCache(**kept)


@jax.named_scope("layer/attn_qkv")
def _block_kv(
    h: jax.Array, blk: Params, cfg: ModelConfig, cos: jax.Array,
    sin: jax.Array, scope: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    with inner_scope(scope):
        return _qkv(h, blk, cfg, cos, sin)


def _qkv(h, blk, cfg, cos, sin):
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
    if cfg.attention_multiplier:
        # Every attention kernel scales q k^T by head_dim ** -0.5: the
        # stated multiplier is FOLDED INTO q as multiplier * sqrt(head_dim)
        # (granite: 2 ** -6 * 8 = 2 ** -3, exact in bf16), so no kernel
        # takes a scale of its own.
        q = q * jnp.asarray(
            cfg.attention_multiplier * cfg.head_dim**0.5, q.dtype)
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
    head: bool = True,
) -> Tuple[jax.Array, KVCache]:
    """Run the prompt through the model, filling cache[:, :, :S] and
    returning fp32 logits [B, V] at each row's LAST VALID position (the
    distribution over the first generated token).  Computing the head only
    there keeps prefill memory at [B, V] instead of [B, S, V] — at a 152k
    vocab that is the difference between 40 MB and 10 GB."""
    positions = positions_from_segments(segment_ids)
    x = _embed(params, cfg, tokens, positions)
    (cos, sin), window_rope = _rope(cfg, positions)
    ctx = Ctx(
        cfg, cos, sin, segment_ids, window_rope, use_flash, with_state=True,
        ring=cache.ring,
        ck_slots=None if cache.ck is None else cache.ck.shape[2],
        block_mask=_block_ids(cfg, positions, None),
    )
    # Each population's new entries [its layers, ...], the prefix's layers
    # first ...
    x, _, entries = _walk(
        cfg, params["blocks"], x, None,
        _packed_call(ctx),
        gives=tuple(_CACHE_FIELDS),
    )

    def place(buf, new):
        """... in its buffer: a state and a ring are all new, a window
        gets the prompt's entries of every layer."""
        if buf is None or new is None:
            return buf
        if new.shape == buf.shape:
            return new.astype(buf.dtype)
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0,) * buf.ndim
        )

    new_cache = {f: place(getattr(cache, f), entries[f]) for f in _CACHE_FIELDS}
    if not head:  # the block loop reads no logits of the prompt's blocks
        return None, KVCache(**new_cache)
    return _prefill_head(params, cfg, x, segment_ids), KVCache(**new_cache)


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


# The attention and MLP kinds, one token per row (`Branch.step`): f(ctx, h,
# blk, cache, li) -> (its output, the cache, what its decode counter reads);
# li: the layer's index among all the layers with the branch, which is its
# place in the branch's population of the cache and in the stacked expert
# leaves.


def _put_token(buf: jax.Array, new: jax.Array, li, slot) -> jax.Array:
    """k or v [B,1,h,d] -> [1,B,1,h,d] written at (layer, :, slot)."""
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype)[None], (li, 0, slot, 0, 0))


def _layer_of_cache(buf: jax.Array, li) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(buf, li, axis=0, keepdims=False)


@functools.lru_cache(maxsize=None)
def kv_cache_alone(cfg: ModelConfig) -> bool:
    """Whether the static cache of `cfg.plan` is k/v alone: of the fields
    its kinds' records keep (`Branch.cache`), `k` and `v` and no other — no
    state, ring, latent row or compressed key.  From the records, once a
    config; nothing is traced."""
    keep = set()
    for branch in branches_of(cfg).values():
        keep.update(branch.cache)
    return keep == {"k", "v"}


def kv_kernel_form(cfg: ModelConfig, row_kernel, s_max: int) -> bool:
    """Whether the static program's softmax attention is the Pallas kernel
    `kv_decode` (`ops/pallas/kv_decode.py`: the stacked cache read in
    place, a row's live keys alone) in `decode_attention`'s place, by what
    the code can see: the plan's cache is k/v alone (`kv_cache_alone`;
    every other plan keeps the program it has) and `row_kernel` says so
    (`flash_attention.row_kernel_form`) — None: a TPU backend and shapes
    the kernel can cut (`kv_decode.fits`); a bool forces either form
    (interpreted off a TPU); a MESH keeps the XLA form (the kernel is one
    device's program; `shard_map` over rows and KV heads is not built)."""
    if not kv_cache_alone(cfg):
        return False
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form
    from areal_tpu.ops.pallas.kv_decode import fits

    use_kernel, mesh = row_kernel_form(row_kernel, fits(s_max, cfg.head_dim))
    return use_kernel and mesh is None


def _kv_attention(q, kc, vc, li, valid_from, valid_to):
    """`decode_attention` of q [B, T, n_q, d] (a row's T tokens see ONE
    window) over layer li of the STACKED caches [L, B, S, n_kv, d], on the
    kernel `kv_decode`: no layer sliced out.  Imported here, in the branch
    that takes it."""
    from areal_tpu.ops.pallas.kv_decode import kv_decode

    return kv_decode(q, kc, vc, li, valid_from, valid_to)


def _attention_step(ctx: Ctx, h, blk, cache, li):
    """Softmax attention of one token per row through k/v layer li."""
    cfg, slot = ctx.cfg, ctx.slot
    full = "full" if ctx.window_rope else None  # the inner scope, a mixed plan
    q, k, v = _block_kv(h, blk, cfg, ctx.cos, ctx.sin, full)  # [B,1,h,d]
    kc = _put_token(cache.k, k, li, slot)
    vc = _put_token(cache.v, v, li, slot)
    if kv_kernel_form(cfg, ctx.row_kernel, kc.shape[2]):
        attn = _kv_attention(q, kc, vc, li, ctx.valid_from, slot + 1)
    else:
        attn = decode_attention(
            q, _layer_of_cache(kc, li), _layer_of_cache(vc, li),
            ctx.valid_from, slot + 1, scope=full,
        )
    ao = _attn_out(
        attn.reshape(h.shape[0], 1, cfg.q_dim), blk, cfg,
        _attn_gate(h, blk, cfg), scope=full,
    )
    return ao, dataclasses.replace(cache, k=kc, v=vc), {}


def _latent_step(ctx: Ctx, h, blk, cache, li):
    """ABSORBED latent attention of one token per row through the latent
    rows of layer li (`KVCache.latent`, one row a token): the query is
    carried into the latent space, scores and the weighted sum are taken
    against the rows themselves and the value up-projection comes after —
    the numbers of the materialised form `prefill` and training run, in
    another order."""
    cfg, slot = ctx.cfg, ctx.slot
    q, row = _latent_q_absorbed(h, blk, cfg, ctx.cos, ctx.sin)
    rows = jax.lax.dynamic_update_slice(
        cache.latent, row.astype(cache.latent.dtype)[None],
        (li, 0, slot, 0),
    )
    attn = latent_decode_attention(
        q[:, 0], rows, li, ctx.valid_from, slot + 1, cfg.kv_lora_rank,
        cfg.head_dim**-0.5, use_kernel=ctx.row_kernel,
    )
    ao = _attn_out(attn.reshape(h.shape[0], 1, -1), blk, cfg, absorbed=True)
    return ao, dataclasses.replace(cache, latent=rows), {}


def _window_step(ctx: Ctx, h, blk, cache, li):
    """Sliding-window attention of one token per row through ring li
    (`KVCache.wk` / `wv`, min(attn_window, S_max) slots): the token's k/v
    go to entry `slot` mod ring, over the slot that left the window, and
    the live entries (`ring_valid`: the same for every window layer of
    the step) are read where they lie — softmax does not ask for their
    order — with the window layers' own rotary table (`_rope`)."""
    cfg, slot = ctx.cfg, ctx.slot
    q, k, v = _block_kv(h, blk, cfg, *ctx.window_rope, "window")
    at = slot % cache.wk.shape[2]
    kc = _put_token(cache.wk, k, li, at)
    vc = _put_token(cache.wv, v, li, at)
    attn = decode_attention(
        q, _layer_of_cache(kc, li), _layer_of_cache(vc, li),
        ctx.valid_from, slot + 1, valid=ctx.live, scope="window",
    )
    ao = _attn_out(
        attn.reshape(h.shape[0], 1, cfg.q_dim), blk, cfg, scope="window")
    return ao, dataclasses.replace(cache, wk=kc, wv=vc), {}


def _sparse_step(ctx: Ctx, h, blk, cache, li):
    """Block-sparse attention of one token per row: the token's k/v
    and, where it completes a kernel, the row's next compressed key go
    into layer li; the selection reads the compressed keys and the
    attention the chosen blocks' rows alone.  What it read ([3] fp32:
    keys read, keys cached, rows still under `sparse_dense_len`) rides out
    for the kind's counter."""
    cfg, slot = ctx.cfg, ctx.slot
    sizes = block_sparse.Sizes.of(cfg)
    q, k, v = _block_kv(h, blk, cfg, ctx.cos, ctx.sin)
    kc = _put_token(cache.k, k, li, slot)
    vc = _put_token(cache.v, v, li, slot)
    with jax.named_scope("layer/sparse_attn/compress"):
        ck = block_sparse.compressed_step(
            cache.ck, kc, li, slot, ctx.valid_from, sizes)
    attn, reads = block_sparse.decode_attention(
        q, _layer_of_cache(kc, li), _layer_of_cache(vc, li),
        _layer_of_cache(ck, li), ctx.valid_from, slot, sizes,
    )
    ao = _attn_out(
        attn.reshape(h.shape[0], 1, cfg.q_dim), blk, cfg,
        _attn_gate(h, blk, cfg))
    return ao, dataclasses.replace(cache, k=kc, v=vc, ck=ck), {SPARSE: reads}


def _mlp_step(ctx: Ctx, h, blk, cache, li):
    return _mlp_dense(h, blk, ctx.cfg), cache, {}


def _moe_step(ctx: Ctx, h, blk, cache, li):
    out, _, counts = _mlp_moe(
        h, blk, ctx.cfg, stacked=ctx.stacked, layer=li,
        kernel=ctx.expert_kernel,
    )
    return out, cache, {MOE: counts}


def decode_counters(cfg: ModelConfig) -> Dict[str, Counter]:
    """The decode counters of `cfg.plan`'s kinds, by name, in the order
    the static loop carries their sums."""
    found = [b.counter for b in branches_of(cfg).values() if b.counter]
    return {c.name: c for c in sorted(found, key=lambda c: c.name)}


@jax.named_scope("gen/decode_step")
def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B] int32 — current token per row
    positions: jax.Array,  # [B] int32 — its RoPE position per row
    cache: KVCache,
    slot: jax.Array,  # scalar int32 — cache slot written for ALL rows
    valid_from: jax.Array,  # [B] int32 — first valid cache slot per row
    with_counts: bool = False,
    experts_in_place: Optional[bool] = None,
    row_kernel=None,  # None | bool | Mesh
    expert_kernel: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """One decode step: write the new token's k/v at cache slot `slot`
    (shared by every row — the right-aligned prompt layout makes the write a
    single `dynamic_update_slice`, not a per-row scatter), attend over the
    live window `[valid_from, slot]`, return fp32 logits [B, V] and the
    updated cache — and, `with_counts`, a dict of what the plan's kinds
    give their decode counters (`decode_counters`, by `Counter.name`, each
    stacked over the kind's layers: a mixture's rows per expert [L, E]
    int32, what a block-sparse layer read [layers, 3] fp32), which the
    generator's counters reduce inside its decode loop.

    The layers are `cfg.plan`'s, walked by `_walk`, each branch through
    its record's `step`.  The cache rides the layer scan as CARRY (updated
    in place by XLA), so
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

    `row_kernel`: the form of the per-row cache kernels, in which a
    row reads and writes nothing of another's — latent attention's
    `latent_decode` over the stacked rows and the Gated DeltaNet step's
    `gdn_delta_step` over the stacked recurrent state.  None = the Pallas
    kernel on a TPU backend, the XLA form elsewhere; a caller whose mesh
    spreads the rows over devices passes the MESH and the kernel runs per
    device on its rows (`shard_map` over the batch axes); a bool forces
    either form.  Where the plan's cache is k/v alone it also picks softmax
    attention's `kv_decode` over the stacked k/v (`kv_kernel_form`: one
    device; a mesh keeps `decode_attention`).

    What a kind does with its population of the cache is its `step`'s to
    say (`_window_step`: the ring; `_latent_step`: the absorbed form, no
    per-head k/v ever built).  Leading dense layers step before the scan,
    through the first layers of the cache."""
    x = _embed(params, cfg, tokens, positions)[:, None, :]  # [B,1,D]
    (cos, sin), window_rope = _rope(cfg, positions[:, None])
    slot = jnp.asarray(slot, jnp.int32)
    blocks, stacked = _scan_blocks(cfg, params["blocks"], experts_in_place)
    ctx = Ctx(
        cfg, cos, sin, window_rope=window_rope, row_kernel=row_kernel,
        stacked=stacked, slot=slot, valid_from=valid_from,
        expert_kernel=stacked is not None and expert_kernel_choice(
            cfg, expert_kernel),
        # the same entries of every window layer's ring
        live=ring_valid(slot, valid_from, cache.ring)
        if window_rope else None,
    )
    x, new_cache, counts = _walk(
        cfg, blocks, x, cache,
        lambda branch, h, blk, cache, li, at: BRANCHES[branch].step(
            ctx, h, blk, cache, li),
        gives=tuple(decode_counters(cfg)),
    )
    x = _final_norm(params, cfg, x)
    logits = _head(params, cfg, x)[:, 0]  # [B, V]
    if with_counts:
        return logits, new_cache, {
            n: c for n, c in counts.items() if c is not None}
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

    Pages exist for the plan's ATTENTION layers alone (`L` above is their
    count, not `n_layers`).  A plan with Mamba-2 layers keeps, beside the
    pool and for the same generate call, one SLOT of recurrent state a
    request: `state`, fp32 [n_slots, H, head_dim, N] a Mamba layer, and the
    conv's last inputs `conv`, [n_slots, K-1, conv_dim] a Mamba layer
    (`init_paged_kv_cache(n_slots=)`; None for every other plan).  Both are
    TUPLES over the Mamba layers of the plan's unit, each array stacked
    [repeats, n_slots, ...] over the scan's steps — one buffer a layer of
    the unit, not one stack of all layers: the matmul that reads a layer's
    state takes a whole buffer as it lies, where XLA copies a 134 MB slice
    out of a stack of layers in front of it (compiled for a described
    v5e, PR 53; with repeats > 1 the step's slice of a buffer is copied
    the same way until the recurrence is a kernel of its own).
    `slot_state(s)` stacks a slot's in layer order.  A slot's state is not
    paged, shared or copied: it restarts from zero at the slot's lane of
    position 0 (`mamba.ssm_ragged`) and is dead with the request.

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
    state: "tuple | None" = None  # per unit layer [repeats, n_slots, H, P, N] fp32
    conv: "tuple | None" = None  # per unit layer [repeats, n_slots, K-1, conv_dim]
    page_size: int = 128  # static metadata (pytree aux)

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def slot_state(self, slot: int) -> Tuple[jax.Array, jax.Array]:
        """Slot `slot`'s (state [L_ssm, H, P, N], conv tail [L_ssm, K-1,
        conv_dim]) in layer order, for a check of what the chunk left."""
        def stacked(per_layer):
            return jnp.stack([
                a[p, slot] for p in range(per_layer[0].shape[0])
                for a in per_layer
            ])

        return stacked(self.state), stacked(self.conv)


jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=["k", "v", "k_scale", "v_scale", "state", "conv"],
    meta_fields=["page_size"],
)


def init_paged_kv_cache(
    cfg: ModelConfig, n_pages: int, page_size: int, dtype=None,
    n_slots: int = 0,
) -> PagedKVCache:
    """`n_slots`: the generator's slots, for a plan whose kinds keep a
    `state` or a `conv` (`Branch.cache`: a slot of state and a conv tail a
    request; the tail in the compute type, as `init_kv_cache` keeps it)."""
    refusal = plan_refusal(cfg, serving=True)
    if refusal:
        raise refusal
    plan = cfg.plan
    n_attn = plan.count(ATTENTION)
    shape = (n_attn, n_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    dtype = dtype or cfg.dtype
    quant = dtype in (jnp.int8, "int8")
    slots = {}
    for name, branch in branches_of(cfg).items():
        for field in ("state", "conv"):
            if field not in branch.cache:
                continue
            if n_slots < 1:
                raise ValueError(
                    "a plan with Mamba-2 layers keeps a slot of state a "
                    "request beside the page pool: init_paged_kv_cache "
                    "needs n_slots"
                )
            row, kind = branch.cache[field](
                cfg, n_slots, 0, cfg.dtype if quant else dtype)
            slots[field] = tuple(
                jnp.zeros((plan.repeats, *row), kind)
                for _ in range(plan.in_unit(name)))
    if quant:
        s_shape = (n_attn, n_pages, cfg.n_kv_heads, page_size)
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(s_shape, jnp.bfloat16),
            v_scale=jnp.zeros(s_shape, jnp.bfloat16),
            page_size=page_size, **slots,
        )
    return PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        page_size=page_size, **slots,
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


class _Pages(NamedTuple):
    """What one serving chunk step hands its attention layers: the lanes'
    page-table rows and windows, layer 0's flat rows of their writes
    (`_pool_rows`), whether the pool is int8, the live pages' schedule."""

    table: jax.Array  # [T, max_pages]
    valid_to: jax.Array  # [T]
    rows: jax.Array
    rows_s: jax.Array
    stride: int
    quant: bool
    schedule: Any


def _attention_serve(ctx: Ctx, h, blk, pools, li, at):
    """A lane's k/v into its page of layer li among the attention layers,
    and ragged paged attention through the slot's page-table row."""
    cfg, pg = ctx.cfg, ctx.pages
    q, k, v = _block_kv(h, blk, cfg, ctx.cos, ctx.sin)  # [T, 1, h, d]
    kc, vc, ksc, vsc = _cache_update(
        pools.k, pools.v, pools.k_scale, pools.v_scale, k[:, 0], v[:, 0],
        li * pg.stride + pg.rows,
        li * pg.stride * cfg.n_kv_heads + pg.rows_s, pg.quant,
    )
    attn = ragged_paged_attention(
        q[:, 0], kc, vc, li, pg.table, pg.valid_to,
        k_scale=ksc, v_scale=vsc,
        use_kernel=ctx.paged_kernel, schedule=pg.schedule,
    )
    return _attn_out(attn.reshape(h.shape[0], 1, cfg.q_dim), blk, cfg), (
        dataclasses.replace(pools, k=kc, v=vc, k_scale=ksc, v_scale=vsc))


def _mlp_serve(ctx: Ctx, h, blk, pools, li, at):
    return _mlp_dense(h, blk, ctx.cfg), pools


def _moe_serve(ctx: Ctx, h, blk, pools, li, at):
    return _mlp_moe(h, blk, ctx.cfg, stacked=ctx.stacked, layer=li)[0], pools


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
    slot_lanes: Optional[int] = None,
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
    one device's program, as is the Mamba-2 state kernel `ssm_slab_step`,
    which takes the same choice).  Dead lanes (row_of >= B, the stream's slack) drop their
    cache writes, emit zero attention, and produce garbage logits the
    caller never reads.  The pool shape never changes during a generate
    call, so the enclosing program compiles exactly once.  A grouped MoE
    model's expert leaves
    reach the ragged kernels as in `decode_step` (`experts_in_place`).

    The layers are `cfg.plan`'s, walked as `decode_step` walks them
    (`_walk`), each branch through its record's `serve`, the pool the
    walk's carry.  An attention branch reads and writes the pages of its
    own layer among the attention layers; a branch with a slot of state
    (`mamba.ssm_ragged`) steps its layer of the slots' state and conv
    tails over the slot's lanes of this step — `slot_lanes`, the most
    lanes one slot may hold in a stream (the caller's W), sizes its slab
    and is required for a plan with state; a slot's lanes are contiguous
    in the stream and in position order."""
    refusal = plan_refusal(cfg, serving=True)
    if refusal:
        raise refusal
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
    lanes = None
    if cache.state is not None:  # the same slab of lanes for every layer
        if not slot_lanes:
            raise ValueError(
                "a plan with Mamba-2 layers needs slot_lanes: the most "
                "lanes one slot holds in a stream"
            )
        lanes = slot_lanes_of(row_of, positions, b, slot_lanes)

    blocks, stacked = _scan_blocks(cfg, params["blocks"], experts_in_place)
    ctx = Ctx(
        cfg, cos, sin, stacked=stacked, paged_kernel=paged_kernel,
        lanes=lanes, pages=_Pages(
            pt_tok, valid_to, rows0, rows_s0, stride, cache.quantized,
            schedule),
    )
    # One step of the unit with state: no scan, so a layer's place in its
    # buffers is STATIC, step 0, and the buffer is read as it lies
    # (`PagedKVCache`).
    x, cache, _ = _walk(
        cfg, blocks, x, cache,
        lambda branch, *args: (*BRANCHES[branch].serve(ctx, *args), {}),
        unroll=lanes is not None and cfg.plan.repeats == 1,
    )
    x = _final_norm(params, cfg, x)
    return _head(params, cfg, x)[:, 0], cache  # logits [T, V]


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
    new = dataclasses.replace(
        cache,
        k=cache.k.at[:, dst].set(cache.k[:, src], mode="drop"),
        v=cache.v.at[:, dst].set(cache.v[:, src], mode="drop"),
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


# --------------------------------------------------------------------------
# The attention and MLP kinds' records, and the table
# --------------------------------------------------------------------------


def _kv_row(cfg: ModelConfig, batch, s_max, dtype):
    return (batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype


def _ring_row(cfg: ModelConfig, batch, s_max, dtype):
    return (batch, min(cfg.attn_window, s_max), cfg.n_kv_heads,
            cfg.head_dim), dtype


def _attn_matmul_params(cfg: ModelConfig) -> int:
    """ONE softmax-attention layer's projections (the query's twice where
    it also gives the output gate)."""
    h, d = cfg.hidden_dim, cfg.head_dim
    q_mats = 2 if cfg.attn_gate else 1
    return (
        h * (q_mats * cfg.n_q_heads * d + 2 * cfg.n_kv_heads * d)
        + cfg.n_q_heads * d * h
    )


def _latent_matmul_params(cfg: ModelConfig) -> int:
    """Latent attention: the two low-rank query projections, the latent
    and shared-rope projection, the key and value up-projections and the
    output projection (the materialised form training and prefill run)."""
    h, d, hq, c = cfg.hidden_dim, cfg.head_dim, cfg.n_q_heads, cfg.kv_lora_rank
    return (
        h * cfg.q_lora_rank + cfg.q_lora_rank * hq * d
        + h * cfg.latent_dim
        + c * hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        + hq * cfg.v_head_dim * h
    )


def _softmax_flops(cfg: ModelConfig, n_tokens, sum_sq_seqlens) -> float:
    """QK^T and attn @ V over every key (a window layer is counted as a
    full one: this bounds the program's own estimate, the benchmark's
    `peaks_swa.py` counts the band), the causal factor folded into the
    constant as the reference counts it (flops_counter.py)."""
    return 2.0 * 2.0 * cfg.n_q_heads * cfg.head_dim * sum_sq_seqlens


def _sparse_flops(cfg: ModelConfig, n_tokens, sum_sq_seqlens) -> float:
    """A token's SELECTED keys (topk blocks, never more than its sequence
    has), not all of them, and its scores against one compressed key per
    stride."""
    hd = cfg.n_q_heads * cfg.head_dim
    chosen = min(
        sum_sq_seqlens, float(n_tokens) * cfg.sparse_topk * cfg.sparse_block_size)
    return 4.0 * hd * chosen + 2.0 * hd * sum_sq_seqlens / cfg.sparse_kernel_stride


def _mlp_matmul_params(cfg: ModelConfig) -> int:
    return (3 if cfg.mlp_gated else 2) * cfg.hidden_dim * cfg.intermediate_dim


def _moe_matmul_params(cfg: ModelConfig):
    """Of the routed experts the ones HELD here: a rank's share computes
    n_experts / router_width of a token's k choices in expectation, beside
    the whole router and the shared expert (its gate where it has one)."""
    h, n_mats = cfg.hidden_dim, 3 if cfg.mlp_gated else 2
    inter = cfg.moe_intermediate_dim or cfg.intermediate_dim
    held = cfg.n_experts_per_tok * cfg.n_experts / cfg.router_width
    out = n_mats * h * inter * held + h * cfg.router_width
    if cfg.shared_expert_dim:
        out += n_mats * h * cfg.shared_expert_dim + (
            h if cfg.shared_expert_gated else 0)
    return out


def _moe_slab(cfg: ModelConfig, rows: int) -> Tuple[int, int]:
    """(slab, pairs) of a decode step of `rows` tokens: slab < pairs in the
    programs whose grouped dispatch takes one (`decode_slab_rows`)."""
    pairs = rows * cfg.n_experts_per_tok
    return decode_slab_rows(cfg, pairs), pairs


def _moe_counter_width(cfg: ModelConfig, rows: int) -> int:
    slab, pairs = _moe_slab(cfg, rows)
    return 3 + (2 if cfg.expert_share else 0) + (3 if slab < pairs else 0)


def _moe_step_counters(counts: jax.Array, cfg: ModelConfig, at) -> jax.Array:
    """One decode step's rows per expert [L, E] -> f32 [3]: experts with at
    least one row and the fullest expert's rows (both means over layers),
    and 1 for the step.  A rank's share of the experts
    (`cfg.expert_share`) adds two: the rows that reached experts held
    here, and the rows the router sent anywhere (`at.rows` tokens x k
    choices x L layers).  A program whose dispatch takes a slab
    (`decode_slab_rows` of the step's pairs) adds three, and no other
    program carries them: the rows its slabs left out (the pairs less the
    dispatch's own loop bound, `expert_slabs_run`, times the slab; summed
    over layers), the fullest layer's held pairs over the slab (past 1.0
    the overflow ran), and 1 for the step."""
    out = [
        jnp.mean(jnp.sum(counts > 0, axis=-1).astype(jnp.float32)),
        jnp.mean(jnp.max(counts, axis=-1).astype(jnp.float32)),
        jnp.float32(1.0),
    ]
    if cfg.expert_share:
        out += [
            jnp.sum(counts).astype(jnp.float32),
            jnp.float32(at.rows * cfg.n_experts_per_tok * counts.shape[0]),
        ]
    slab, pairs = _moe_slab(cfg, at.rows)
    if slab < pairs:
        held = jnp.sum(counts, axis=-1)  # [L]: the dispatch's group sizes
        gathered = jnp.minimum(expert_slabs_run(slab, pairs, held) * slab, pairs)
        out += [
            jnp.sum(pairs - gathered).astype(jnp.float32),
            jnp.max(held).astype(jnp.float32) / slab,
            jnp.float32(1.0),
        ]
    return jnp.stack(out)


def _moe_report(sums, cfg: ModelConfig, params: Params) -> Dict[str, Any]:
    """Per decode step and MoE layer, the experts with at least one row
    and the rows on the fullest expert (means over every step of a
    generate call); and which way the expert weights reached the ragged
    kernels (1: the parameters' own buffers, 0: the layer scan's slices).
    Where a program of the call took a slab, the generator-side twins of
    the trainer's `moe/rows_gathered_share` and `moe/slab_fill_max`: the
    rows the dispatch gathered (of `moe_rows_routed`; a step without a slab
    gathers all of its own) and the fullest layer's held pairs over the
    slab, mean over the steps that took one."""
    touched, rows_max, steps, *share = sums
    if not steps:
        return {}
    out = dict(
        moe_experts_touched=touched / steps,
        moe_rows_per_expert_max=rows_max / steps,
        moe_decode_steps=int(steps),
        moe_expert_leaves_in_place=int(
            expert_leaves_in_place(cfg, params["blocks"])),
    )
    if share:
        # One expert-parallel rank's share: (row, choice) pairs that fell
        # to experts held here, of all the router made, over every decode
        # step and layer.  Balanced routing reads n_experts / router_width.
        out.update(
            moe_rows_local=float(share[0]), moe_rows_routed=float(share[1]))
    if len(share) > 2 and share[4]:
        out.update(
            moe_rows_gathered=float(share[1] - share[2]),
            moe_slab_fill_max=float(share[3] / share[4]))
    return out


def _window_step_counters(given, cfg: ModelConfig, at) -> jax.Array:
    """(row, ring entry) pairs this step's window layers each read, and 1
    for the step."""
    live = ring_valid(at.slot, at.valid_from, at.cache.wk.shape[2])
    return jnp.stack([jnp.sum(live).astype(jnp.float32), jnp.float32(1.0)])


def _window_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """Rings beside the full layers' windows, and every attention layer at
    s_max, as a cache without rings."""
    return {
        "window_cache_bytes": nbytes(cache.wk, cache.wv),
        "kv_cache_bytes": nbytes(cache.k, cache.v),
        "kv_cache_bytes_unwindowed": 2 * cache.wk.dtype.itemsize * (
            cfg.plan.count(ATTENTION, WINDOW) * batch * s_max * cfg.kv_dim
        ),
        "window_slots": batch * cache.wk.shape[2],
    }


def _latent_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """The latent rows beside what per-head k/v would take."""
    return {
        "latent_cache_bytes": nbytes(cache.latent),
        "kv_cache_bytes_as_heads": cache.latent.dtype.itemsize * (
            cfg.n_layers * batch * s_max * cfg.n_kv_heads
            * (cfg.head_dim + cfg.v_head_dim)
        ),
    }


def _sparse_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    return {
        "kv_cache_bytes": nbytes(cache.k, cache.v),
        "compressed_cache_bytes": nbytes(cache.ck),
    }


_KV = {"k": _kv_row, "v": _kv_row}
# In the order the first kind of a plan that refuses a plane or a layout
# speaks (`plan_refusal`) and a cache's populations are made
# (`init_kv_cache`).
BRANCHES.update({
    SPARSE: Branch(
        leaves=_FULL_ATTN_LEAVES,
        cache={**_KV, "ck": lambda cfg, batch, s_max, dtype: (
            (batch, -(-s_max // cfg.sparse_kernel_stride), cfg.n_kv_heads,
             cfg.head_dim), dtype)},
        packed=_sparse_packed,
        step=_sparse_step,
        refusal=lightning.SALA_REFUSAL,
        remat_alone=True,  # as a Lightning layer's: `lightning.BRANCH`
        matmul_params=_attn_matmul_params,
        attn_flops=_sparse_flops,
        counter=Counter(
            SPARSE, lambda cfg, rows: 3,
            lambda given, cfg, at: jnp.sum(given.reshape(-1, 3), axis=0),
            lambda sums, cfg, params: dict(
                sparse_keys_read=float(sums[0]),
                sparse_keys_cached=float(sums[1]),
                sparse_dense_rows=float(sums[2]),
            ),
        ),
        cache_stats=_sparse_cache_stats,
    ),
    LIGHTNING: lightning.BRANCH,
    ATTENTION: Branch(
        leaves=_FULL_ATTN_LEAVES,
        cache=_KV,
        packed=_attention_packed,
        step=_attention_step,
        serve=_attention_serve,
        matmul_params=_attn_matmul_params,
        attn_flops=_softmax_flops,
    ),
    GDN: linear_attention.BRANCH,
    SCONV: short_conv.BRANCH,
    LATENT: Branch(
        leaves=_LATENT_LEAVES + ("wo", "bo"),
        cache={"latent": lambda cfg, batch, s_max, dtype: (
            (batch, s_max, cfg.latent_dim), dtype)},
        packed=_attention_packed,
        step=_latent_step,
        refusal=Refusal(
            LatentLayoutError,
            "latent attention and leading dense layers run under data and "
            "fsdp sharding only: the heads of the low-rank projections are "
            "not split over `model`, the ring over a split sequence and the "
            "pipeline's stages were not tested with them (PERF.md section 7)",
            "latent rows have no pages on the serving plane yet, and its "
            "chunk has no layer before the scan: latent attention and "
            "leading dense layers generate on the static decode program "
            "only (at most max_decode_batch requests, no stop sequences, no "
            "speculative decoding, max_new_tokens within "
            "static_path_max_new)",
        ),
        matmul_params=_latent_matmul_params,
        attn_flops=_softmax_flops,
        cache_stats=_latent_cache_stats,
    ),
    LATENT_SELECT: latent_select.SELECT_BRANCH,
    LATENT_WINDOW: latent_select.WINDOW_BRANCH,
    SSM: mamba.BRANCH,
    WINDOW: Branch(
        leaves=_FULL_ATTN_LEAVES,
        cache={"wk": _ring_row, "wv": _ring_row},
        packed=_window_packed,
        step=_window_step,
        refusal=Refusal(
            WindowLayoutError,
            "sliding-window layers beside full-attention layers run under "
            "data and fsdp sharding only: the ring attention over a split "
            "sequence has no window, the pipeline's stage scans one kind of "
            "layer with one rotary table, and the window layers' heads were "
            "not tested split over `model` (PERF.md section 7)",
            "the serving plane's chunk scans one kind of attention over "
            "pages of every slot, with one rotary table: it would run the "
            "sliding-window layers as full ones.  A mix of window and full "
            "attention layers generates on the static decode program only "
            "(at most max_decode_batch requests, no stop sequences, no "
            "speculative decoding, max_new_tokens within "
            "static_path_max_new)",
        ),
        matmul_params=_attn_matmul_params,
        attn_flops=_softmax_flops,
        flash_window=lambda cfg: cfg.attn_window,
        counter=Counter(
            WINDOW, lambda cfg, rows: 2, _window_step_counters,
            lambda sums, cfg, params: dict(
                window_slots_live=float(sums[0] / max(sums[1], 1.0))),
        ),
        cache_stats=_window_cache_stats,
    ),
    # A mixer's output and an MLP's are the SMALL per-token dots ([*, D])
    # whose saving (remat="dots_small", `_remat_layer`) lets backward skip
    # only the fat gate/up recompute candidates' DOWNSTREAM — memory ~2x
    # "full" remat instead of the ~7x of "dots".
    MLP: Branch(
        leaves=("wg", "wu", "wd", "bproj", "bfc"),
        packed=_mlp_packed,
        step=_mlp_step,
        serve=_mlp_serve,
        saved_as="mlp_out",
        matmul_params=_mlp_matmul_params,
    ),
    MOE: Branch(
        leaves=_MOE_LEAVES + ("bproj", "bfc"),
        packed=_moe_packed,
        step=_moe_step,
        serve=_moe_serve,
        saved_as="mlp_out",
        matmul_params=_moe_matmul_params,
        counter=Counter(
            MOE, _moe_counter_width, _moe_step_counters, _moe_report),
    ),
})

# The cache's populations, in the order every program puts them out:
# `KVCache` field -> the kinds that keep it.
_CACHE_FIELDS = {
    f.name: tuple(n for n, b in BRANCHES.items() if f.name in b.cache)
    for f in dataclasses.fields(KVCache)
}


# --------------------------------------------------------------------------
# Generation by diffusion over blocks (`cfg.block_length`): the block-causal
# mask's ids over packed rows, and the static program's BLOCK step
# --------------------------------------------------------------------------


class BlockLayoutError(NotImplementedError):
    """A layout or plane generation by diffusion over blocks (a decode
    step of a block of tokens, the block-causal mask, the two-stream train
    forward) cannot run on yet, refused by name rather than run as an
    autoregressive model."""


_BLOCK_REFUSAL = Refusal(
    BlockLayoutError,
    "generation by diffusion over blocks (block_length > 0) shards over "
    "data and fsdp alone: the block-causal mask runs on one device's flash "
    "kernel or the dense form, so a mesh split over model, seq or pipe is "
    "refused",
    "generation by diffusion over blocks (block_length > 0) runs on the "
    "STATIC decode program alone: the serving chunk yields one token a "
    "lane and step, and its pages, prefix sharing and speculation assume "
    "it",
)


def _block_ids(cfg: ModelConfig, positions, stream_ids):
    """(block ids, stream ids) of the block-causal mask — a token's block
    is floor(position / block_length), by its ABSOLUTE position in its
    sequence — or None for every autoregressive model."""
    if not cfg.block_length:
        return None
    if stream_ids is None:
        stream_ids = jnp.zeros_like(positions)
    return positions // cfg.block_length, stream_ids


def mask_logit_out(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Logits with the mask token's set to -inf: no position may hold it,
    so sampler, trainer and reference leave it out of every softmax."""
    return jnp.where(
        jnp.arange(logits.shape[-1]) == cfg.mask_token_id, -jnp.inf, logits)


def _attention_block_step(ctx: Ctx, h, blk, cache, li):
    """`_attention_step` of a BLOCK of tokens a row: the block's k/v go
    to its own slots [slot, slot + B) and every one of its B queries
    attends [valid_from, slot + B) — the committed blocks and the whole of
    its own."""
    cfg, slot = ctx.cfg, ctx.slot
    b, n = h.shape[:2]
    q, k, v = _block_kv(h, blk, cfg, ctx.cos, ctx.sin)  # [B, Q, h, d]
    kc = _put_token(cache.k, k, li, slot)
    vc = _put_token(cache.v, v, li, slot)
    if kv_kernel_form(cfg, ctx.row_kernel, kc.shape[2]):
        # The block's Q queries see ONE window: the kernel's tokens a row.
        attn = _kv_attention(q, kc, vc, li, ctx.valid_from, slot + n)
    else:
        # One window, so the Q queries are `decode_attention`'s one token a
        # row with Q times the query heads a key head: [B, Q, g, r, d] ->
        # [B, 1, g (Q r), d].  As an op of its own over [B, Q, ...] XLA
        # copied the layer's k and v out of the stacked cache in front of
        # every forward (2 x 67 MB a layer; my chip run, PR 68).
        g, d = cfg.n_kv_heads, cfg.head_dim
        q = q.reshape(b, n, g, -1, d).transpose(0, 2, 1, 3, 4)
        attn = decode_attention(
            q.reshape(b, 1, -1, d), _layer_of_cache(kc, li),
            _layer_of_cache(vc, li), ctx.valid_from, slot + n,
        )
        attn = attn.reshape(b, g, n, -1, d).transpose(0, 2, 1, 3, 4)
    ao = _attn_out(
        attn.reshape(b, n, cfg.q_dim), blk, cfg, _attn_gate(h, blk, cfg))
    return ao, dataclasses.replace(cache, k=kc, v=vc), {}


def block_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, Q] int32 — a block a row (mask tokens or not)
    positions: jax.Array,  # [B, Q] int32 — their RoPE positions
    cache: KVCache,
    slot: jax.Array,  # scalar int32 — the block's FIRST cache slot, all rows
    valid_from: jax.Array,  # [B] int32 — first valid cache slot per row
    head: bool = True,
    experts_in_place: Optional[bool] = None,
    expert_kernel: Optional[bool] = None,
    row_kernel=None,  # None | bool | Mesh: `kv_kernel_form`
) -> Tuple[jax.Array, ...]:
    """`decode_step` of a block of Q = `cfg.block_length` tokens a row:
    one forward of the block against the cache -> (fp32 logits [B, Q, V] IN
    PLACE — row i the distribution of the token AT i, the mask token's
    logit -inf — or None without `head`, the cache, the experts' rows
    [L, E]).  The block's k/v land in its own slots [slot, slot + Q): a
    denoising forward leaves there what its part-masked block gave, read
    by no later step — the COMMIT forward of the clean block overwrites it,
    so what the cache keeps of a block is the commit's.  `row_kernel`:
    `decode_step`'s, for the block's attention (`kv_kernel_form`)."""
    x = _embed(params, cfg, tokens, positions)  # [B, Q, D]
    (cos, sin), _ = _rope(cfg, positions)
    slot = jnp.asarray(slot, jnp.int32)
    blocks, stacked = _scan_blocks(cfg, params["blocks"], experts_in_place)
    ctx = Ctx(
        cfg, cos, sin, stacked=stacked, slot=slot, valid_from=valid_from,
        expert_kernel=stacked is not None and expert_kernel_choice(
            cfg, expert_kernel),
        row_kernel=row_kernel,
    )

    def call(branch, h, blk, cache, li, at):
        step = (_attention_block_step if branch == ATTENTION
                else BRANCHES[branch].step)
        return step(ctx, h, blk, cache, li)

    x, new_cache, counts = _walk(
        cfg, blocks, x, cache, call, gives=tuple(decode_counters(cfg)))
    logits = None
    if head:
        logits = mask_logit_out(cfg, _head(params, cfg, _final_norm(params, cfg, x)))
    return logits, new_cache, {n: c for n, c in counts.items() if c is not None}


def block_token_output(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D] from hidden_states() over two-stream rows
    labels: jax.Array,  # [B, S] int32 — x_j at the masked stream's place of j
    label_mask: jax.Array,  # [B, S] — where a log-prob is wanted
    head_index: jax.Array,  # [B, K] int32 — those slots, padded with S
    chunk_size: int = 512,
    mesh=None,
) -> jax.Array:
    """`per_token_output` of a model with `block_length`: [B, S] fp32, at
    the masked stream's place of position j the log-probability of token j
    IN PLACE (`ops/functional.fused_label_logprobs`, the mask token left
    out), 0 elsewhere.  The head runs over the K gathered rows alone."""
    from areal_tpu.ops.functional import fused_label_logprobs

    b, s, _ = x.shape
    at = jnp.minimum(head_index, s - 1)
    real = head_index < s
    lp = fused_label_logprobs(
        jnp.take_along_axis(x, at[..., None], axis=1),
        head_weights(params, cfg),
        jnp.take_along_axis(labels, at, axis=1),
        jnp.take_along_axis(label_mask, at, axis=1) * real,
        chunk_size, mesh, exclude=cfg.mask_token_id,
    )
    return jnp.zeros((b, s), jnp.float32).at[
        jnp.arange(b)[:, None], head_index].set(lp, mode="drop")

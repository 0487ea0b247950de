"""FLOP and byte arithmetic of LATENT attention (MLA) with leading dense
layers and a rank's share of sigmoid-routed experts beside an ungated
shared expert (glm4_moe_lite, the deepseek_v3 block).  `benchmark/peaks.py`
counts a GQA layer everywhere and all of a token's chosen experts; it stays
as it is for the configurations it was written for, and the metrics of a
latent configuration divide by what this file counts.

Per layer kind, forward, a multiply-add as 2 FLOPs:
  * latent attention, as training and prefill run it (materialised): the
    two low-rank query projections, the latent + shared-rope projection,
    the key and value up-projections, the output projection, and the
    causal half of the score matrix at head width nope + rope (= v).  The
    absorbed decode step multiplies by the same up-projection weights in
    another order; its scores and sums run over latent rows (c + rope and
    c wide), which `flops_generate` counts.
  * dense MLP (the first `first_k_dense` layers): three matrices.
  * sparse MLP: the router over its whole width, the ungated shared
    expert, and of a token's k choices the EXPECTED share that falls to
    experts held here, k E_held / E_router (0.5 of 4 at 8 of 64).

Bytes of a decode step: every weight the step multiplies by once (bf16),
of the routed experts the ones the program COUNTED as touched; and of the
latent cache each row's live context read ONCE (the rows are keys and
values at the same time) plus the new row written.
"""

BF16, FP32 = 2, 4


def n_sparse(cfg):
    return cfg.n_layers - cfg.first_k_dense


def attn_params(cfg):
    """Matmul parameters of one layer's latent attention."""
    h, hq = cfg.hidden_dim, cfg.n_q_heads
    return (
        h * cfg.q_lora_rank + cfg.q_lora_rank * hq * cfg.head_dim
        + h * cfg.latent_dim
        + cfg.kv_lora_rank * hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        + hq * cfg.v_head_dim * h
    )


def dense_mlp_params(cfg):
    return 3 * cfg.hidden_dim * cfg.intermediate_dim


def experts_per_token_held(cfg):
    return cfg.n_experts_per_tok * cfg.n_experts / cfg.router_width


def sparse_mlp_params(cfg):
    """Matmul parameters one token's forward uses in one sparse MLP."""
    h = cfg.hidden_dim
    return (experts_per_token_held(cfg) * 3 * h * cfg.moe_intermediate_dim
            + h * cfg.router_width + 3 * h * cfg.shared_expert_dim)


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_layers * attn_params(cfg)
            + cfg.first_k_dense * dense_mlp_params(cfg)
            + n_sparse(cfg) * sparse_mlp_params(cfg)
            + cfg.hidden_dim * cfg.vocab_size)


def _causal_attn_flops(cfg, seqlens):
    """QK^T and PV of ONE layer over these sequences, the half the causal
    mask keeps (as `peaks.flops_forward`)."""
    return 2.0 * cfg.n_q_heads * cfg.head_dim * float(
        sum(s * s for s in seqlens))


def flops_forward(cfg, seqlens):
    n = float(sum(seqlens))
    return (2.0 * matmul_params(cfg) * n
            + cfg.n_layers * _causal_attn_flops(cfg, seqlens))


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def mla_train_flops(cfg, seqlens):
    """Forward + backward FLOPs of ALL the layers' attention (scopes
    `layer/attn_qkv`, `layer/attn`, `layer/attn_out`): the projections and
    the causal per-segment scores and sums."""
    n = float(sum(seqlens))
    return 3.0 * cfg.n_layers * (
        2.0 * attn_params(cfg) * n + _causal_attn_flops(cfg, seqlens))


def latent_row_bytes(cfg):
    """One token's row of ONE layer's latent cache."""
    return cfg.latent_dim * BF16


def mla_decode_bytes(cfg, context_lens):
    """HBM bytes ALL the layers' attention of one decode step over these
    rows has to move: each layer's attention weights once, every row's
    live context of latent rows read once, the new row written."""
    rows = len(context_lens)
    cache = latent_row_bytes(cfg) * (float(sum(context_lens)) + rows)
    return cfg.n_layers * (attn_params(cfg) * BF16 + cache)


# --------------------------------------------------------------------------
# The MLPs of a rank's share, a whole decode step, a generate request
# --------------------------------------------------------------------------


def experts_expected(cfg, rows):
    """HELD experts of one layer that `rows` tokens touch under uniform
    routing over the router's whole width (7.87 of 8 at 64 rows of
    4-of-64)."""
    miss = 1.0 - cfg.n_experts_per_tok / cfg.router_width
    return cfg.n_experts * (1.0 - miss ** rows)


def moe_layer_parts(cfg, tokens, experts_touched=None, local_rows=None,
                    bytes_per_el=BF16):
    """{part: (FLOPs, HBM bytes)} of ONE sparse layer's MLP over `tokens`
    tokens, forward only, as `peaks_hybrid.moe_layer_parts` counts a
    rank's share: the router scores its whole width; of the tokens' k
    choices only the `local_rows` that fall to experts held here (counted
    by the program, else the expectation) are gathered, multiplied and
    scattered; the `experts_touched` held experts' weights are read; the
    ungated shared expert sees every token."""
    t, h = float(tokens), cfg.hidden_dim
    f, b = cfg.moe_intermediate_dim, bytes_per_el
    e_router = cfg.router_width
    if experts_touched is None:
        experts_touched = experts_expected(cfg, tokens)
    r = t * experts_per_token_held(cfg) if local_rows is None else float(
        local_rows)
    fs = cfg.shared_expert_dim
    return {
        "router": (2 * t * h * e_router,
                   (t * h + h * e_router) * b + t * e_router * FP32),
        "gather": (0.0, (t * h + r * h) * b),
        "gate_up": (2 * 2 * r * h * f,
                    (r * h + 2 * experts_touched * h * f + r * f) * b),
        "down": (2 * r * f * h,
                 (r * f + experts_touched * f * h + r * h) * b),
        "scatter": (2 * r * h, (r * h + t * h) * b),
        "shared": (2 * t * h * 3 * fs,
                   (3 * fs * h + 2 * t * h + 2 * t * fs) * b),
    }


def dense_mlp_parts(cfg, tokens, bytes_per_el=BF16):
    """(FLOPs, HBM bytes) of ONE leading dense layer's MLP."""
    t, h, f = float(tokens), cfg.hidden_dim, cfg.intermediate_dim
    return 2 * t * 3 * h * f, (3 * h * f + 2 * t * h + 2 * t * f) * bytes_per_el


def mlp_decode_bytes(cfg, rows, experts_touched=None, local_rows=None):
    """HBM bytes ALL the layers' MLPs of one decode step over `rows` rows
    have to move — what runs under the scope `layer/mlp`: the sparse
    layers' (`moe_layer_parts`) and the leading dense layers'."""
    sparse = sum(by for _, by in moe_layer_parts(
        cfg, rows, experts_touched, local_rows).values())
    return (n_sparse(cfg) * sparse
            + cfg.first_k_dense * dense_mlp_parts(cfg, rows)[1])


def mlps_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the layers' MLPs over `tokens`
    trained tokens — what runs under the scope `layer/mlp`: the sparse
    layers' (the local rows at their expectation) and the leading dense
    layers'."""
    sparse = sum(fl for fl, _ in moe_layer_parts(cfg, tokens).values())
    return 3.0 * (n_sparse(cfg) * sparse
                  + cfg.first_k_dense * dense_mlp_parts(cfg, tokens)[0])


def decode_bytes(cfg, context_lens, experts_touched=None, local_rows=None):
    """HBM bytes one decode step over these rows has to move: attention
    (`mla_decode_bytes`), the MLPs (`mlp_decode_bytes`) and the head."""
    return (mla_decode_bytes(cfg, context_lens)
            + mlp_decode_bytes(cfg, len(context_lens), experts_touched,
                               local_rows)
            + cfg.hidden_dim * cfg.vocab_size * BF16)


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts (materialised) + one token at a time over
    the rest (absorbed: a head's score and sum against a latent row cost
    c + rope and c multiply-adds where per-head k/v would cost 2 d)."""
    total = flops_forward(cfg, prompt_lens)
    per_token = 2.0 * matmul_params(cfg)
    attn_c = 2.0 * cfg.n_q_heads * (
        cfg.latent_dim + cfg.kv_lora_rank) * cfg.n_layers
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + attn_c * (g * p + g * g / 2.0)
    return total

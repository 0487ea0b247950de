"""FLOP and byte arithmetic of a HYBRID layer pattern (qwen3_next: n - 1
Gated DeltaNet layers to one gated softmax-attention layer a period, a MoE
MLP with a shared expert in every layer, and of the routed experts only
the ones this rank holds).  `benchmark/peaks.py` counts a softmax-
attention layer everywhere and all of a token's chosen experts; it stays
as it is for the configurations it was written for, and the metrics of a
hybrid configuration divide by what this file counts.

Per layer kind, forward, a multiply-add as 2 FLOPs:
  * gated attention: q (query and gate), k, v, o projections, and the
    causal half of the score matrix (as `peaks.flops_forward`).
  * Gated DeltaNet: the projections to q | k | v, z and b | a and the
    output projection, plus the delta rule as the RECURRENCE needs it per
    token and value head — decay S (1), S^T k (2), k d^T (2), S^T q (2):
    7 d_k d_v.  The chunked form the program runs for whole sequences
    spends more (pairwise products inside a chunk, a triangular solve);
    that surplus is not counted as work.  The conv (2 K a channel) and the
    norms are left out.
  * MoE MLP: the router over its whole width, the shared expert and its
    gate, and of a token's k choices the EXPECTED share that falls to
    experts held here, k E_held / E_router (1.25 of 10 at 64 of 512).
"""

FP32, BF16 = 4, 2


def _n_linear(cfg):
    return cfg.n_layers - cfg.n_periods


def full_attn_params(cfg):
    h, q = cfg.hidden_dim, cfg.n_q_heads * cfg.head_dim
    q_mats = 2 if cfg.attn_gate else 1
    return h * (q_mats * q + 2 * cfg.n_kv_heads * cfg.head_dim) + q * h


def linear_attn_params(cfg):
    """Matmul parameters of one Gated DeltaNet layer's projections."""
    h = cfg.hidden_dim
    return (h * (cfg.linear_conv_dim + cfg.linear_value_dim
                 + 2 * cfg.linear_n_v_heads) + cfg.linear_value_dim * h)


def delta_rule_flops_per_token(cfg):
    """Forward FLOPs of one layer's recurrence for one token."""
    return (7 * cfg.linear_n_v_heads * cfg.linear_k_head_dim
            * cfg.linear_v_head_dim)


def experts_per_token_held(cfg):
    return cfg.n_experts_per_tok * cfg.n_experts / cfg.router_width


def mlp_params(cfg):
    """Matmul parameters one token's forward uses in one layer's MLP."""
    h = cfg.hidden_dim
    out = (experts_per_token_held(cfg) * 3 * h * cfg.moe_intermediate_dim
           + h * cfg.router_width)
    if cfg.shared_expert_dim:
        out += 3 * h * cfg.shared_expert_dim + h
    return out


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_periods * full_attn_params(cfg)
            + _n_linear(cfg) * linear_attn_params(cfg)
            + cfg.n_layers * mlp_params(cfg)
            + cfg.hidden_dim * cfg.vocab_size)


def flops_forward(cfg, seqlens):
    n = float(sum(seqlens))
    sq = float(sum(s * s for s in seqlens))
    attn = 2.0 * cfg.n_q_heads * cfg.head_dim * sq * cfg.n_periods
    delta = _n_linear(cfg) * delta_rule_flops_per_token(cfg) * n
    return 2.0 * matmul_params(cfg) * n + attn + delta


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def gdn_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the Gated DeltaNet mixers (scope
    `layer/linear_attn`) over `tokens` trained tokens."""
    per_token = 2.0 * linear_attn_params(cfg) + delta_rule_flops_per_token(cfg)
    return 3.0 * _n_linear(cfg) * per_token * float(tokens)


def gdn_decode_bytes(cfg, rows):
    """HBM bytes ALL the Gated DeltaNet mixers of one decode step over
    `rows` rows have to move: each layer's projection weights once (bf16),
    its recurrent state read and written once (fp32, [rows, h_v, d_k,
    d_v]) and its conv tail read and written ([rows, K - 1, C], bf16)."""
    state = rows * cfg.linear_n_v_heads * cfg.linear_k_head_dim \
        * cfg.linear_v_head_dim * FP32
    tail = rows * (cfg.linear_conv_kernel - 1) * cfg.linear_conv_dim * BF16
    weights = (linear_attn_params(cfg)
               + cfg.linear_conv_kernel * cfg.linear_conv_dim) * BF16
    return _n_linear(cfg) * (weights + 2 * state + 2 * tail)


# --------------------------------------------------------------------------
# The MoE MLP of a rank's share, a whole decode step, a generate request
# --------------------------------------------------------------------------


def experts_expected(cfg, rows):
    """HELD experts of one layer that `rows` tokens touch under uniform
    routing over the router's whole width: a row misses an expert with
    probability 1 - k / E_router (45.9 of 64 at 64 rows of 10-of-512)."""
    miss = 1.0 - cfg.n_experts_per_tok / cfg.router_width
    return cfg.n_experts * (1.0 - miss ** rows)


def moe_layer_parts(cfg, tokens, experts_touched=None, local_rows=None,
                    bytes_per_el=BF16):
    """{part: (FLOPs, HBM bytes)} of ONE layer's MLP over `tokens` tokens,
    forward only, as `peaks.moe_layer_parts` counts a whole layer, for a
    rank's share: the router scores its whole width; of the tokens' k
    choices only the `local_rows` that fall to experts held here (counted
    by the program, else the expectation k E_held / E_router a token) are
    gathered, multiplied and scattered — the rows the program gathers and
    then leaves unmultiplied are not work; the `experts_touched` held
    experts' weights are read; the shared expert and its gate see every
    token."""
    t, h = float(tokens), cfg.hidden_dim
    f, b = cfg.moe_intermediate_dim, bytes_per_el
    e_router = cfg.router_width
    if experts_touched is None:
        experts_touched = experts_expected(cfg, tokens)
    r = t * experts_per_token_held(cfg) if local_rows is None else float(
        local_rows)
    parts = {
        "router": (2 * t * h * e_router,
                   (t * h + h * e_router) * b + t * e_router * FP32),
        "gather": (0.0, (t * h + r * h) * b),
        "gate_up": (2 * 2 * r * h * f,
                    (r * h + 2 * experts_touched * h * f + r * f) * b),
        "down": (2 * r * f * h,
                 (r * f + experts_touched * f * h + r * h) * b),
        "scatter": (2 * r * h, (r * h + t * h) * b),
    }
    fs = cfg.shared_expert_dim
    if fs:
        parts["shared"] = (2 * t * h * (3 * fs + 1),
                           ((3 * fs + 1) * h + 2 * t * h + 2 * t * fs) * b)
    return parts


def experts_decode_bytes(cfg, rows, experts_touched=None, local_rows=None):
    """HBM bytes ALL the layers' MLPs of one decode step over `rows` rows
    have to move (`moe_layer_parts`)."""
    return cfg.n_layers * sum(by for _, by in moe_layer_parts(
        cfg, rows, experts_touched, local_rows).values())


def experts_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the layers' MLPs over `tokens`
    trained tokens, the local rows at their expectation."""
    return 3.0 * cfg.n_layers * sum(
        fl for fl, _ in moe_layer_parts(cfg, tokens).values())


def decode_bytes(cfg, context_lens, experts_touched=None, local_rows=None):
    """HBM bytes one decode step over these rows has to move: the Gated
    DeltaNet layers' weights, states and conv tails (`gdn_decode_bytes`),
    the attention layers' weights and every row's K/V at its context, the
    MLPs (`experts_decode_bytes`) and the head."""
    rows = len(context_lens)
    kv = (2 * cfg.n_periods * cfg.n_kv_heads * cfg.head_dim * BF16
          * float(sum(context_lens)))
    weights = (cfg.n_periods * full_attn_params(cfg)
               + cfg.hidden_dim * cfg.vocab_size) * BF16
    return (gdn_decode_bytes(cfg, rows) + weights + kv
            + experts_decode_bytes(cfg, rows, experts_touched, local_rows))


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest (as
    `peaks.flops_generate`, with this file's per-kind counts)."""
    total = flops_forward(cfg, prompt_lens)
    per_token = (2.0 * matmul_params(cfg)
                 + _n_linear(cfg) * delta_rule_flops_per_token(cfg))
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_periods
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + attn_c * (g * p + g * g / 2.0)
    return total

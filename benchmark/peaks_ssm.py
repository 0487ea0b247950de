"""FLOP and byte arithmetic of a PATTERN OF ONE-BRANCH LAYERS (nemotron_h:
every layer is a Mamba-2 mixer, a mixture of ungated experts or softmax
attention ALONE, in the order `cfg.layer_pattern` gives, and of the routed
experts only the ones this rank holds).  `benchmark/peaks.py` counts a
mixer and an MLP in every layer, `peaks_hybrid.py` a period of delta-rule
layers with SwiGLU experts; they stay as they are for the configurations
they were written for, and the metrics of this pattern divide by what this
file counts, each kind over its OWN layers.

Per layer kind, forward, a multiply-add as 2 FLOPs:
  * "M", Mamba-2: in_proj ([z | x B C | dt]) and out_proj, plus the
    recurrence as it is DEFINED per token and state element — decay S (1),
    add dt x (x) B (2), read y = S C (2): 5 d_inner N.  The chunked form
    the program runs for whole sequences spends more (the [C, C] blocks
    inside a chunk); that surplus is not counted as work.  The conv (2 K a
    channel), the gate and the norms are left out.
  * "*", attention: q, k, v, o projections and the causal half of the
    score matrix (as `peaks.flops_forward`).
  * "E", experts: the router over its whole width, the shared expert, and
    of a token's k choices the EXPECTED share that falls to experts held
    here, k E_held / E_router (0.75 of 6 at 16 of 128) — TWO matrices an
    expert, down(relu(up(x))^2).
"""

from benchmark.peaks_hybrid import (  # noqa: F401 - the share's counts
    BF16,
    FP32,
    experts_expected,
    experts_per_token_held,
)


def attn_params(cfg):
    h, q = cfg.hidden_dim, cfg.n_q_heads * cfg.head_dim
    return h * (q + 2 * cfg.n_kv_heads * cfg.head_dim) + q * h


def ssm_params(cfg):
    """Matmul parameters of one Mamba-2 layer's two projections."""
    return cfg.hidden_dim * cfg.ssm_in_dim + cfg.ssm_inner_dim * cfg.hidden_dim


def ssm_flops_per_token(cfg):
    """Forward FLOPs of one layer's recurrence for one token."""
    return 5 * cfg.ssm_inner_dim * cfg.ssm_state_dim


def mlp_params(cfg):
    """Matmul parameters one token's forward uses in one expert layer."""
    h = cfg.hidden_dim
    return (experts_per_token_held(cfg) * 2 * h * cfg.moe_intermediate_dim
            + h * cfg.router_width + 2 * h * cfg.shared_expert_dim)


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_attn_layers * attn_params(cfg)
            + cfg.n_ssm_layers * ssm_params(cfg)
            + cfg.n_moe_layers * mlp_params(cfg)
            + cfg.hidden_dim * cfg.vocab_size)


def flops_forward(cfg, seqlens):
    n = float(sum(seqlens))
    sq = float(sum(s * s for s in seqlens))
    attn = 2.0 * cfg.n_q_heads * cfg.head_dim * sq * cfg.n_attn_layers
    ssm = cfg.n_ssm_layers * ssm_flops_per_token(cfg) * n
    return 2.0 * matmul_params(cfg) * n + attn + ssm


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def ssm_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the Mamba-2 mixers (scope
    `layer/ssm`) over `tokens` trained tokens."""
    per_token = 2.0 * ssm_params(cfg) + ssm_flops_per_token(cfg)
    return 3.0 * cfg.n_ssm_layers * per_token * float(tokens)


def ssm_decode_bytes(cfg, rows):
    """HBM bytes ALL the Mamba-2 mixers of one decode step over `rows`
    rows have to move: each layer's projection weights, conv taps and
    per-channel vectors once (bf16), its state read and written once
    (fp32, [rows, H, P, N]) and its conv tail read and written ([rows,
    K - 1, conv_dim], bf16)."""
    state = rows * cfg.ssm_inner_dim * cfg.ssm_state_dim * FP32
    tail = rows * (cfg.ssm_conv_kernel - 1) * cfg.ssm_conv_dim * BF16
    weights = (ssm_params(cfg)
               + (cfg.ssm_conv_kernel + 1) * cfg.ssm_conv_dim
               + cfg.ssm_inner_dim + 3 * cfg.ssm_n_heads) * BF16
    return cfg.n_ssm_layers * (weights + 2 * state + 2 * tail)


# --------------------------------------------------------------------------
# The expert layers of a rank's share, a whole decode step, a generate call
# --------------------------------------------------------------------------


def moe_layer_parts(cfg, tokens, experts_touched=None, local_rows=None,
                    bytes_per_el=BF16):
    """{part: (FLOPs, HBM bytes)} of ONE expert layer over `tokens`
    tokens, forward only, as `peaks_hybrid.moe_layer_parts` counts a rank's
    share, for experts of TWO matrices: the router scores its whole width;
    of the tokens' k choices only the `local_rows` that fall to experts
    held here (counted by the program, else the expectation) are gathered,
    multiplied and scattered; the `experts_touched` held experts' weights
    are read; the ungated shared expert sees every token."""
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
        "up": (2 * r * h * f, (r * h + experts_touched * h * f + r * f) * b),
        "down": (2 * r * f * h,
                 (r * f + experts_touched * f * h + r * h) * b),
        "scatter": (2 * r * h, (r * h + t * h) * b),
        "shared": (2 * t * h * 2 * fs,
                   (2 * fs * h + 2 * t * h + 2 * t * fs) * b),
    }


def experts_decode_bytes(cfg, rows, experts_touched=None, local_rows=None):
    """HBM bytes ALL the expert layers of one decode step over `rows` rows
    have to move (`moe_layer_parts`)."""
    return cfg.n_moe_layers * sum(by for _, by in moe_layer_parts(
        cfg, rows, experts_touched, local_rows).values())


def experts_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the expert layers over `tokens`
    trained tokens, the local rows at their expectation."""
    return 3.0 * cfg.n_moe_layers * sum(
        fl for fl, _ in moe_layer_parts(cfg, tokens).values())


def decode_bytes(cfg, context_lens, experts_touched=None, local_rows=None):
    """HBM bytes one decode step over these rows has to move: the Mamba
    layers' weights, states and conv tails (`ssm_decode_bytes`), the
    attention layers' weights and every row's K/V at its context, the
    expert layers (`experts_decode_bytes`) and the head."""
    rows = len(context_lens)
    kv = (2 * cfg.n_attn_layers * cfg.n_kv_heads * cfg.head_dim * BF16
          * float(sum(context_lens)))
    weights = (cfg.n_attn_layers * attn_params(cfg)
               + cfg.hidden_dim * cfg.vocab_size) * BF16
    return (ssm_decode_bytes(cfg, rows) + weights + kv
            + experts_decode_bytes(cfg, rows, experts_touched, local_rows))


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest (as
    `peaks.flops_generate`, with this file's per-kind counts)."""
    total = flops_forward(cfg, prompt_lens)
    per_token = (2.0 * matmul_params(cfg)
                 + cfg.n_ssm_layers * ssm_flops_per_token(cfg))
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_attn_layers
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + attn_c * (g * p + g * g / 2.0)
    return total

"""FLOP and byte arithmetic of Mamba-2 mixers in TWO-BRANCH layers with a
DENSE MLP (granitemoehybrid: every layer is a mixer — Mamba-2 or softmax
attention, by `cfg.window_pattern` — AND a SwiGLU MLP; the head is tied).
`peaks_ssm.py` counts a pattern of one-branch layers with experts; it
stays as it is for the configuration it was written for, and this file
takes from it what a Mamba-2 mixer and an attention layer are.

Per layer, forward, a multiply-add as 2 FLOPs:
  * the mixer: Mamba-2 (in_proj, out_proj, and the recurrence as it is
    DEFINED per token and state element, 5 d_inner N: `peaks_ssm`) or
    attention (q, k, v, o and the causal half of the score matrix);
  * the MLP: three matrices [h, f], every token.
The serving plane's inner step moves, at least: every weight once (bf16;
the tied table once, as the head), each LIVE slot's fp32 state read once
and written once, its conv tail likewise, the live pages of the attention
layers, and the lanes' fp32 logits written and read once.  What the
program's form of the recurrence reads beyond that (the state a second
time, for the lanes' read of it) is not counted as work.
"""

from benchmark.peaks_ssm import (  # noqa: F401 - a mixer's own counts
    BF16,
    FP32,
    attn_params,
    ssm_flops_per_token,
    ssm_params,
    ssm_train_flops,
)


def mlp_params(cfg):
    return 3 * cfg.hidden_dim * cfg.intermediate_dim


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_attn_layers * attn_params(cfg)
            + cfg.n_ssm_layers * ssm_params(cfg)
            + cfg.n_layers * mlp_params(cfg)
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


def flops_generate(cfg, prompt_lens, gen_lens):
    """Every prompt token and every new token forwarded once: on the
    serving plane prefill slices and decode lanes ride one program."""
    total = flops_forward(cfg, prompt_lens)
    per_token = (2.0 * matmul_params(cfg)
                 + cfg.n_ssm_layers * ssm_flops_per_token(cfg))
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_attn_layers
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + attn_c * (g * p + g * g / 2.0)
    return total


def ssm_serving_bytes(cfg, live_slots):
    """HBM bytes ALL the Mamba-2 mixers of one inner step of the serving
    chunk have to move with `live_slots` slots holding a lane: each
    layer's projection weights, conv taps and per-channel vectors once
    (bf16), a live slot's state read and written once (fp32 [H, P, N]) and
    its conv tail read and written ([K - 1, conv_dim], bf16)."""
    state = live_slots * cfg.ssm_inner_dim * cfg.ssm_state_dim * FP32
    tail = live_slots * (cfg.ssm_conv_kernel - 1) * cfg.ssm_conv_dim * BF16
    weights = (ssm_params(cfg)
               + (cfg.ssm_conv_kernel + 1) * cfg.ssm_conv_dim
               + cfg.ssm_inner_dim + 3 * cfg.ssm_n_heads) * BF16
    return cfg.n_ssm_layers * (weights + 2 * state + 2 * tail)


def serving_step_bytes(cfg, live_slots, lanes, live_page_tokens):
    """HBM bytes one inner step of the serving chunk has to move: the
    mixers (`ssm_serving_bytes`), the attention layers' weights and the
    `live_page_tokens` cached tokens under the lanes' windows (k and v),
    every layer's MLP, the tied table once as the head, and the `lanes`
    lanes' fp32 logits written and read once."""
    kv = (2 * cfg.n_attn_layers * cfg.n_kv_heads * cfg.head_dim * BF16
          * float(live_page_tokens))
    weights = (cfg.n_attn_layers * attn_params(cfg)
               + cfg.n_layers * mlp_params(cfg)
               + cfg.hidden_dim * cfg.vocab_size) * BF16
    logits = 2 * float(lanes) * cfg.vocab_size * FP32
    return ssm_serving_bytes(cfg, live_slots) + weights + kv + logits

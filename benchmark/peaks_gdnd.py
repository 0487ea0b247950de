"""FLOP and byte arithmetic of Gated DeltaNet mixers in TWO-BRANCH layers
with a DENSE MLP (olmo_hybrid: n - 1 Gated DeltaNet layers to one
position-free softmax-attention layer a period, as many K/V heads as query
heads, a SwiGLU MLP in every layer, an untied head).  `peaks_hybrid.py`
counts the same mixers before a mixture of experts (its `mlp_params`
divides by a router's width); it stays as it is for the configuration it
was written for, and this file takes from it what a Gated DeltaNet mixer
and an attention layer are.

Per layer, forward, a multiply-add as 2 FLOPs:
  * the mixer: Gated DeltaNet (the projections and the delta rule as the
    RECURRENCE needs it a token and value head, 7 d_k d_v:
    `peaks_hybrid`) or attention (q, k, v, o and the causal half of the
    score matrix);
  * the MLP: three matrices [h, f], every token.
A decode step moves, at least: every weight once (bf16), each row's fp32
state read once and written once, its conv tail likewise, and every row's
K/V at its context in the attention layers.
"""

from benchmark.peaks_hybrid import (  # noqa: F401 - a mixer's own counts
    BF16,
    FP32,
    _n_linear,
    delta_rule_flops_per_token,
    full_attn_params,
    gdn_decode_bytes,
    gdn_train_flops,
    linear_attn_params,
)


def mlp_params(cfg):
    return 3 * cfg.hidden_dim * cfg.intermediate_dim


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


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest (as
    `peaks_hybrid.flops_generate`, with this file's per-kind counts)."""
    total = flops_forward(cfg, prompt_lens)
    per_token = (2.0 * matmul_params(cfg)
                 + _n_linear(cfg) * delta_rule_flops_per_token(cfg))
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_periods
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + attn_c * (g * p + g * g / 2.0)
    return total


def decode_bytes(cfg, context_lens):
    """HBM bytes one decode step over these rows has to move: the Gated
    DeltaNet layers' weights, states and conv tails (`gdn_decode_bytes`),
    the attention layers' weights and every row's K/V at its context, the
    MLPs and the head."""
    kv = (2 * cfg.n_periods * cfg.n_kv_heads * cfg.head_dim * BF16
          * float(sum(context_lens)))
    weights = (cfg.n_periods * full_attn_params(cfg)
               + cfg.n_layers * mlp_params(cfg)
               + cfg.hidden_dim * cfg.vocab_size) * BF16
    return gdn_decode_bytes(cfg, len(context_lens)) + weights + kv

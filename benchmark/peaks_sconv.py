"""FLOP and byte arithmetic of a SHORT-CONVOLUTION / ATTENTION MIX
(lfm2_moe: gated short-convolution layers beside softmax-attention layers
in the order `cfg.window_pattern` gives, `first_k_dense` leading layers
with a dense MLP, gated experts behind every other mixer, and of the
routed experts only the ones this rank holds).  `benchmark/peaks.py`
counts a softmax-attention layer everywhere; it stays as it is for the
configurations it was written for, and the metrics of this mix divide by
what this file counts.

Per layer kind, forward, a multiply-add as 2 FLOPs:
  * gated short convolution: in_proj [D, 3 D] and out_proj [D, D],
    `sconv_params` = 4 D^2 = 16,777,216 at D = 2,048.  The depthwise conv
    (2 K a channel), the two gates and the norms are left out.
  * attention: q, k, v, o projections (`peaks_ssm.attn_params`), then QK^T
    and PV over the causal half of a sequence's pairs, 4 h_q d a pair.
  * dense MLP of a leading layer: `peaks_mla.dense_mlp_params` /
    `dense_mlp_parts`.
  * MoE MLP: `peaks_hybrid.mlp_params` / `moe_layer_parts` as they count a
    rank's share of gated experts (no shared expert here).

A decode step reads a conv layer's weights and its tail [rows, K - 1, D]
(and writes the tail back), and an attention layer's K/V at the row's
context.
"""

from benchmark.peaks_hybrid import (  # noqa: F401 - the share's counts
    BF16,
    experts_expected,
    mlp_params,
    moe_layer_parts,
)
from benchmark.peaks_mla import dense_mlp_params, dense_mlp_parts
from benchmark.peaks_ssm import attn_params  # noqa: F401
from benchmark.peaks_swa import TILE, kv_token_bytes  # noqa: F401


def n_sconv(cfg):
    return cfg.window_pattern.count("C")


def n_attn(cfg):
    return cfg.n_layers - n_sconv(cfg)


def n_sparse(cfg):
    return cfg.n_layers - cfg.first_k_dense


def sconv_params(cfg):
    """Matmul parameters of one short-convolution layer's projections."""
    return 4 * cfg.hidden_dim * cfg.hidden_dim


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, the tied head
    included."""
    return (n_sconv(cfg) * sconv_params(cfg) + n_attn(cfg) * attn_params(cfg)
            + cfg.first_k_dense * dense_mlp_params(cfg)
            + n_sparse(cfg) * mlp_params(cfg)
            + cfg.hidden_dim * cfg.vocab_size)


def attn_pair_flops(cfg, seqlens):
    """Forward FLOPs of QK^T and PV over the attention layers' causal
    pairs."""
    return 4.0 * cfg.n_q_heads * cfg.head_dim * n_attn(cfg) * sum(
        s * s / 2.0 for s in seqlens)


def flops_forward(cfg, seqlens):
    return (2.0 * matmul_params(cfg) * float(sum(seqlens))
            + attn_pair_flops(cfg, seqlens))


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest: a new
    token at context c scores c keys in an attention layer and none in a
    conv layer."""
    total = flops_forward(cfg, prompt_lens)
    per_token = 2.0 * matmul_params(cfg)
    pair = 4.0 * cfg.n_q_heads * cfg.head_dim * n_attn(cfg)
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g + pair * (g * p + g * g / 2.0)
    return total


def sconv_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the conv mixers' projections (scope
    `layer/sconv`) over `tokens` trained tokens: 2 x 16,777,216 a token
    and layer forward at D = 2,048, twice that backward."""
    return 3.0 * n_sconv(cfg) * 2.0 * sconv_params(cfg) * float(tokens)


def sconv_decode_bytes(cfg, rows):
    """HBM bytes ALL the conv mixers of one decode step over `rows` rows
    have to move: each layer's projection weights and taps once, its tail
    [rows, K - 1, D] read and written, the rows' activations in and out."""
    d, k = cfg.hidden_dim, cfg.sconv_kernel
    weights = sconv_params(cfg) + k * d
    tail = rows * (k - 1) * d
    return n_sconv(cfg) * (weights + 2 * tail + 2 * rows * d) * BF16


def flash_tile_flops(cfg, tiles, fwd_runs=2):
    """FLOPs the three flash kernels spend on the live tiles of a train
    step: per 128 x 128 tile and query head 128 x 128 x head_dim
    multiply-adds a product — the forward kernel 2 products (QK^T, PV)
    each time it runs (`fwd_runs`: 2 where the backward pass recomputes
    it), `flash_dq` 3 (S, dP, dQ), `flash_dkv` 4 (S, dV, dP, dK) — over
    the attention layers."""
    per_tile = (2 * fwd_runs + 3 + 4) * 2.0 * TILE * TILE * cfg.head_dim
    return per_tile * cfg.n_q_heads * n_attn(cfg) * tiles


def cache_share(cfg, s_max):
    """(tails + K/V of the attention layers) over K/V at every layer, from
    shapes: a row's bytes at `s_max` slots."""
    tails = n_sconv(cfg) * (cfg.sconv_kernel - 1) * cfg.hidden_dim * BF16
    kv = s_max * kv_token_bytes(cfg)
    return (tails + n_attn(cfg) * kv) / (cfg.n_layers * kv)


def mlps_decode_bytes(cfg, rows, experts_touched=None, local_rows=None):
    """HBM bytes ALL the layers' MLPs of one decode step over `rows` rows
    have to move: the leading dense MLPs and the expert layers
    (`peaks_hybrid.moe_layer_parts`)."""
    return (cfg.first_k_dense * dense_mlp_parts(cfg, rows)[1]
            + n_sparse(cfg) * sum(by for _, by in moe_layer_parts(
                cfg, rows, experts_touched, local_rows).values()))


def mlps_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the layers' MLPs over `tokens`
    trained tokens, the local rows at their expectation."""
    return 3.0 * (cfg.first_k_dense * dense_mlp_parts(cfg, tokens)[0]
                  + n_sparse(cfg) * sum(
                      fl for fl, _ in moe_layer_parts(cfg, tokens).values()))


def decode_bytes(cfg, context_lens, experts_touched=None, local_rows=None):
    """HBM bytes one decode step over these rows has to move: the conv
    mixers (`sconv_decode_bytes`), the attention layers' weights and every
    row's K/V at its context plus the row written, the MLPs and the
    head."""
    rows = len(context_lens)
    attn = n_attn(cfg) * (
        attn_params(cfg) * BF16
        + (float(sum(context_lens)) + rows) * kv_token_bytes(cfg))
    return (sconv_decode_bytes(cfg, rows) + attn
            + cfg.hidden_dim * cfg.vocab_size * BF16
            + mlps_decode_bytes(cfg, rows, experts_touched, local_rows))

"""FLOP and byte arithmetic of a WINDOW / FULL MIX (mellum: sliding-window
attention layers beside full-attention layers in the order
`cfg.window_pattern` gives, gated experts behind every mixer, and of the
routed experts only the ones this rank holds).  `benchmark/peaks.py` counts
every layer's attention over the whole causal half of the score matrix and
a cache of every slot; it stays as it is for the configurations it was
written for, and the metrics of a mix divide by what this file counts.

Per layer kind, forward, a multiply-add as 2 FLOPs:
  * attention, either kind: q, k, v, o projections (`peaks_ssm.attn_params`),
    then QK^T and PV over the (query, key) pairs the mask keeps, 4 h_q d a
    pair: a full layer keeps the causal half, s^2 / 2 of a sequence of s
    (as `peaks.flops_forward`); a window layer keeps a token's last W keys,
    `window_pairs`.
  * MoE MLP: `peaks_hybrid.mlp_params` / `moe_layer_parts` as they count a
    rank's share of gated experts (no shared expert here).

A decode step reads a window layer's RING (the live entries of min(context,
W) slots a row) and a full layer's cache at the row's context.
"""

from benchmark.peaks_hybrid import (  # noqa: F401 - the share's counts
    BF16,
    experts_expected,
    mlp_params,
    moe_layer_parts,
)
from benchmark.peaks_ssm import attn_params  # noqa: F401

TILE = 128  # the flash kernels' block, both ways


def n_window(cfg):
    return cfg.window_pattern.count("S")


def n_full(cfg):
    return cfg.n_layers - n_window(cfg)


def window_pairs(s, w):
    """(query, key) pairs a window layer keeps of a sequence of s tokens:
    token i sees min(i + 1, w) keys."""
    s, w = float(s), float(min(s, w))
    return w * s - w * (w - 1.0) / 2.0


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_layers * (attn_params(cfg) + mlp_params(cfg))
            + cfg.hidden_dim * cfg.vocab_size)


def attn_pair_flops(cfg, seqlens):
    """Forward FLOPs of QK^T and PV over every layer's kept pairs."""
    full = sum(s * s / 2.0 for s in seqlens)
    band = sum(window_pairs(s, cfg.attn_window) for s in seqlens)
    return 4.0 * cfg.n_q_heads * cfg.head_dim * (
        n_full(cfg) * full + n_window(cfg) * band)


def flops_forward(cfg, seqlens):
    return (2.0 * matmul_params(cfg) * float(sum(seqlens))
            + attn_pair_flops(cfg, seqlens))


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest: a new
    token at context c scores c keys in a full layer, min(c, W) in a
    window layer."""
    total = flops_forward(cfg, prompt_lens)
    per_token = 2.0 * matmul_params(cfg)
    pair = 4.0 * cfg.n_q_heads * cfg.head_dim
    w = cfg.attn_window
    for p, g in zip(prompt_lens, gen_lens):
        full = g * p + g * g / 2.0
        band = sum(min(p + t + 1, w) for t in range(int(g)))
        total += per_token * g + pair * (
            n_full(cfg) * full + n_window(cfg) * band)
    return total


def flash_tile_flops(cfg, tiles_full, tiles_window, fwd_runs=2):
    """FLOPs the three flash kernels spend on the live tiles of a train
    step: per tile and query head 128^3 multiply-adds a product — the
    forward kernel 2 products (QK^T, PV) each time it runs (`fwd_runs`: 2
    where the backward pass recomputes it), `flash_dq` 3 (S, dP, dQ),
    `flash_dkv` 4 (S, dV, dP, dK) — over each kind's layers."""
    per_tile = (2 * fwd_runs + 3 + 4) * 2.0 * TILE ** 3
    return per_tile * cfg.n_q_heads * (
        n_full(cfg) * tiles_full + n_window(cfg) * tiles_window)


def kv_token_bytes(cfg):
    """One token's K and V in one layer's cache."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * BF16


def attn_decode_bytes(cfg, ring_slots, full_slots, rows):
    """HBM bytes the attention branches of one decode step over `rows`
    rows have to move: every layer's projection weights once, the slots
    read (`ring_slots` a window layer, `full_slots` a full layer, summed
    over the rows) and each row's new K and V written."""
    per_slot = kv_token_bytes(cfg)
    return (cfg.n_layers * attn_params(cfg) * BF16
            + n_window(cfg) * (ring_slots + rows) * per_slot
            + n_full(cfg) * (full_slots + rows) * per_slot)


def experts_decode_bytes(cfg, rows, experts_touched=None, local_rows=None):
    """HBM bytes ALL the layers' MLPs of one decode step over `rows` rows
    have to move (`peaks_hybrid.moe_layer_parts`)."""
    return cfg.n_layers * sum(by for _, by in moe_layer_parts(
        cfg, rows, experts_touched, local_rows).values())


def experts_train_flops(cfg, tokens):
    """Forward + backward FLOPs of ALL the layers' MLPs over `tokens`
    trained tokens, the local rows at their expectation."""
    return 3.0 * cfg.n_layers * sum(
        fl for fl, _ in moe_layer_parts(cfg, tokens).values())


def decode_bytes(cfg, context_lens, experts_touched=None, local_rows=None):
    """HBM bytes one decode step over these rows has to move: attention
    (`attn_decode_bytes`: a ring's live entries, min(context, W) a row; a
    full layer's cache at the row's context), the MLPs and the head."""
    rows = len(context_lens)
    ring = float(sum(min(c, cfg.attn_window) for c in context_lens))
    return (attn_decode_bytes(cfg, ring, float(sum(context_lens)), rows)
            + cfg.hidden_dim * cfg.vocab_size * BF16
            + experts_decode_bytes(cfg, rows, experts_touched, local_rows))

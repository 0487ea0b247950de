"""The yardstick: published device peaks and the FLOP / byte arithmetic.

Peaks are keyed by the `device_kind` JAX reports.  A device that is not in
the table is an error, never a default.  Source for "TPU v5 lite" (v5e):
Google Cloud documentation, "TPU v5e" system architecture — 197 TFLOP/s
bf16, 819 GB/s HBM, 16 GB per chip.

The FLOP functions are a copy of `areal_tpu/base/monitor.py`
(`matmul_params`, `flops_forward`, `flops_train`, `flops_generate`) with
one deliberate difference: causal attention counts the half of the score
matrix the mask keeps (2 * h_q * d * s^2 per layer for QK^T and PV
together) where the program counts the full square.  Recomputation
(`remat`) is never counted.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; add it to "
            "benchmark/peaks.py with its source"
        )
    return PEAKS[device_kind]


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward (head included,
    embedding lookup excluded)."""
    h, d = cfg.hidden_dim, cfg.head_dim
    attn = h * (cfg.n_q_heads + 2 * cfg.n_kv_heads) * d + cfg.n_q_heads * d * h
    mlp = 3 * h * cfg.intermediate_dim
    return cfg.n_layers * (attn + mlp) + h * cfg.vocab_size


def flops_forward(cfg, seqlens):
    """Forward FLOPs over packed sequences of the given lengths."""
    n = float(sum(seqlens))
    sq = float(sum(s * s for s in seqlens))
    attn = 2.0 * cfg.n_q_heads * cfg.head_dim * sq * cfg.n_layers
    return 2.0 * matmul_params(cfg) * n + attn


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def flops_generate(cfg, prompt_lens, gen_lens):
    """Prefill over the prompts + one token at a time over the rest."""
    total = flops_forward(cfg, prompt_lens)
    mm = 2.0 * matmul_params(cfg)
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * cfg.n_layers
    for p, g in zip(prompt_lens, gen_lens):
        total += mm * g + attn_c * (g * p + g * g / 2.0)
    return total


def weight_bytes(cfg, bytes_per_param=2):
    """Bytes of weights one decode step streams: every matmul weight
    once (the head included; the embedding table is only gathered from,
    unless it is the tied head)."""
    return matmul_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """K and V of one cached position over all layers."""
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * bytes_per_el


def decode_step_bytes(cfg, context_lens):
    """HBM bytes one decode step over these rows has to read: the weights
    once plus every row's cached context."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * float(sum(context_lens))

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

Parameters and bytes follow the config's STRUCTURE, read with
`getattr(cfg, ...)` and dense defaults: a config with `n_experts` > 0
counts the experts a token is routed to (FLOPs) or a step touches (bytes)
and its router; any other config counts what it counted before.
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


def attn_params(cfg):
    """Matmul parameters of one layer's attention: q, k, v and output."""
    h, d = cfg.hidden_dim, cfg.head_dim
    return h * (cfg.n_q_heads + 2 * cfg.n_kv_heads) * d + cfg.n_q_heads * d * h


def expert_params(cfg):
    """Parameters of ONE expert of a MoE layer (gate, up, down)."""
    return 3 * cfg.hidden_dim * cfg.moe_intermediate_dim


def mlp_params(cfg):
    """Matmul parameters ONE token's forward uses in one layer's MLP, as
    the config has it.  Dense (`n_experts` absent or 0): the gated MLP's
    three matrices, two where `mlp_gated` is false.  Sparse experts: the
    `n_experts_per_tok` experts a token is routed to, plus the router."""
    h = cfg.hidden_dim
    n_experts = getattr(cfg, "n_experts", 0)
    if n_experts > 0:
        return cfg.n_experts_per_tok * expert_params(cfg) + h * n_experts
    return (3 if getattr(cfg, "mlp_gated", True) else 2) * h * cfg.intermediate_dim


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward (head included,
    embedding lookup excluded): attention + the MLP as configured (the
    ACTIVE experts of a MoE layer, not all of them) + head.  Nothing else
    about a family is assumed; a key a config lacks takes the dense
    default."""
    return (cfg.n_layers * (attn_params(cfg) + mlp_params(cfg))
            + cfg.hidden_dim * cfg.vocab_size)


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


def experts_expected(cfg, rows):
    """Distinct experts of one MoE layer that `rows` tokens touch when
    routing is uniform: each row picks `n_experts_per_tok` DISTINCT
    experts, so an expert is missed by a row with probability 1 - k/E
    and by all rows with (1 - k/E) ** rows.  OLMoE (8 of 64): 42.0 at 8
    rows, 64.0 at 64."""
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    return e * (1.0 - (1.0 - k / e) ** rows)


def weight_bytes(cfg, rows=1, experts_touched=None, bytes_per_param=2):
    """Bytes of weights one decode step over `rows` rows streams: every
    matmul weight once (the head included; the embedding table is only
    gathered from, unless it is the tied head).  A dense model reads the
    same whatever the rows.  A MoE layer reads its router and the experts
    the step's rows touch: `experts_touched` (mean per MoE layer) where
    the caller has a count from the program, else `experts_expected`.
    Which floor that is: the count gives the bytes THIS step had to read;
    the expectation is the most a step of that many rows is expected to
    touch — uniform routing spreads the rows widest, a skewed router
    touches fewer — so a share over it can overstate what skewed traffic
    must read, and understates nothing."""
    n_experts = getattr(cfg, "n_experts", 0)
    if n_experts <= 0:
        return matmul_params(cfg) * bytes_per_param
    if experts_touched is None:
        experts_touched = experts_expected(cfg, rows)
    layer = (attn_params(cfg) + cfg.hidden_dim * n_experts
             + experts_touched * expert_params(cfg))
    return (cfg.n_layers * layer
            + cfg.hidden_dim * cfg.vocab_size) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """K and V of one cached position over all layers."""
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * bytes_per_el


def decode_step_bytes(cfg, context_lens, experts_touched=None):
    """HBM bytes one decode step over these rows has to read: the weights
    once (`weight_bytes` at this many rows) plus every row's cached
    context."""
    return (
        weight_bytes(cfg, len(context_lens), experts_touched)
        + kv_bytes_per_token(cfg) * float(sum(context_lens))
    )


def moe_layer_parts(cfg, tokens, experts_touched=None, bytes_per_el=2):
    """{part: (FLOPs, HBM bytes)} of ONE MoE layer's MLP over `tokens`
    tokens, forward only, as the grouped (dropless, `jax.lax.ragged_dot`)
    block does it: each part's multiply-adds as 2 FLOPs and the bytes it
    has to read and write once if nothing is fused across parts —
    activations at `bytes_per_el`, the router's logits in fp32.  What a
    roofline share of the scope `layer/mlp` divides by.

      router   x[T,h] @ w[h,E] -> fp32 logits[T,E] (softmax and top-k are
               O(T E), not counted)
      gather   x[T,h] -> the k copies sorted by expert, [T k,h]
      gate_up  two ragged matmuls [T k,h] x [E,h,F] -> [T k,F] each, then
               act(gate) * up in place
      down     one ragged matmul [T k,F] x [E,F,h] -> [T k,h]
      scatter  the k results of a token times its router weights, added
               into [T,h]

    Expert weights: the experts the tokens touch (`weight_bytes` has the
    rule), two thirds under gate_up and one third under down."""
    t, h = float(tokens), cfg.hidden_dim
    e, k, f = cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_intermediate_dim
    if experts_touched is None:
        experts_touched = experts_expected(cfg, tokens)
    b = bytes_per_el
    return {
        "router": (2 * t * h * e, (t * h + h * e) * b + t * e * 4),
        "gather": (0.0, (t * h + t * k * h) * b),
        "gate_up": (2 * 2 * t * k * h * f,
                    (t * k * h + 2 * experts_touched * h * f + t * k * f) * b),
        "down": (2 * t * k * f * h,
                 (t * k * f + experts_touched * f * h + t * k * h) * b),
        "scatter": (2 * t * k * h, (t * k * h + t * h) * b),
    }


def moe_layer_flops(cfg, tokens):
    """Forward FLOPs of one MoE layer's MLP (`moe_layer_parts`)."""
    return sum(fl for fl, _ in moe_layer_parts(cfg, tokens).values())


def moe_layer_bytes(cfg, tokens, experts_touched=None, bytes_per_el=2):
    """HBM bytes of one MoE layer's MLP (`moe_layer_parts`)."""
    return sum(by for _, by in moe_layer_parts(
        cfg, tokens, experts_touched, bytes_per_el).values())

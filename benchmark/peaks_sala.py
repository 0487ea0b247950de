"""FLOP and byte arithmetic of minicpm_sala (block-sparse attention beside
Lightning linear attention, a dense SwiGLU MLP a layer, an untied head).

Per layer, forward, a multiply-add as 2 FLOPs:
  * a block-sparse layer: q, k, v, the gate and o; for every token the
    scores and the weighted sum over its SELECTED keys — `selected_keys`:
    all of its causal prefix in a sequence under `dense_len` and wherever
    the prefix has fewer than topk blocks, else topk x block keys, whatever
    form the program attends in (dense under the mask, it multiplies every
    causal key: that is not counted as work) — and the scores against the
    compressed keys it can see (`visible_kernels`);
  * a Lightning layer: q, k, v, the gate and o, and the recurrence as it is
    DEFINED per token and state element: add k^T v, read q S — 2
    multiply-adds, 4 FLOPs (the decay's multiply is not counted);
  * the MLP: three matrices [h, f], every token.
A decode iteration of the static program moves, at least: every weight once
(bf16), each row's fp32 Lightning state read and written once, the
selected blocks' K and V rows and the compressed keys of the sparse layer,
and the rows' fp32 logits written and read once.
"""

BF16, FP32 = 2, 4


def attn_params(cfg):
    """A block-sparse layer's projections: q and the gate, k and v, o."""
    h, d = cfg.hidden_dim, cfg.head_dim
    return (2 * h * cfg.n_q_heads * d + 2 * h * cfg.n_kv_heads * d
            + cfg.n_q_heads * d * h)


def lightning_params(cfg):
    """A Lightning layer's projections: q, k, v, the gate, o."""
    return 5 * cfg.hidden_dim * cfg.lightning_dim


def mlp_params(cfg):
    return 3 * cfg.hidden_dim * cfg.intermediate_dim


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    return (cfg.n_sparse_layers * attn_params(cfg)
            + cfg.n_lightning_layers * lightning_params(cfg)
            + cfg.n_layers * mlp_params(cfg)
            + cfg.hidden_dim * cfg.vocab_size)


def lightning_flops_per_token(cfg):
    """One Lightning layer's recurrence, a token: 4 FLOPs a state element."""
    return 4.0 * cfg.lightning_n_heads * cfg.lightning_head_dim**2


def selected_keys(cfg, t, seq_len):
    """Keys the query at position `t` (0-based) of a sequence of `seq_len`
    tokens attends over."""
    if seq_len < cfg.sparse_dense_len:
        return t + 1
    return min(t + 1, cfg.sparse_topk * cfg.sparse_block_size)


def visible_kernels(cfg, t, seq_len):
    """Compressed keys the query at position `t` scores against."""
    if seq_len < cfg.sparse_dense_len:
        return 0
    return max(
        (t - (cfg.sparse_kernel_size - 1)) // cfg.sparse_kernel_stride + 1, 0)


def _sum_selected(cfg, lo, hi, seq_len):
    """sum of `selected_keys` over positions [lo, hi)."""
    cap = cfg.sparse_topk * cfg.sparse_block_size
    if seq_len < cfg.sparse_dense_len:
        cap = hi
    knee = max(min(cap, hi), lo)  # positions [lo, knee): t + 1 keys
    return (knee * (knee + 1) - lo * (lo + 1)) / 2.0 + (hi - knee) * float(cap)


def _sum_kernels(cfg, lo, hi, seq_len):
    return float(sum(visible_kernels(cfg, t, seq_len) for t in range(lo, hi)))


def sparse_flops(cfg, lo, hi, seq_len):
    """One block-sparse layer's score-and-value FLOPs over positions
    [lo, hi) of a sequence of `seq_len` tokens."""
    hd = cfg.n_q_heads * cfg.head_dim
    return (4.0 * hd * _sum_selected(cfg, lo, hi, seq_len)
            + 2.0 * hd * _sum_kernels(cfg, lo, hi, seq_len))


def flops_forward(cfg, seqlens):
    n = float(sum(seqlens))
    sparse = cfg.n_sparse_layers * sum(sparse_flops(cfg, 0, s, s) for s in seqlens)
    lightning = cfg.n_lightning_layers * lightning_flops_per_token(cfg) * n
    return 2.0 * matmul_params(cfg) * n + sparse + lightning


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward; recompute excluded."""
    return 3.0 * flops_forward(cfg, seqlens)


def flops_generate(cfg, prompt_lens, gen_lens):
    """Every prompt forwarded once (its own length decides dense or
    selected), then every new token through the cache, whose length is
    the sequence's."""
    total = flops_forward(cfg, prompt_lens)
    per_token = (2.0 * matmul_params(cfg)
                 + cfg.n_lightning_layers * lightning_flops_per_token(cfg))
    for p, g in zip(prompt_lens, gen_lens):
        total += per_token * g
        total += cfg.n_sparse_layers * sum(
            sparse_flops(cfg, t, t + 1, t + 1) for t in range(p, p + g))
    return total


def sparse_decode_bytes(cfg, contexts):
    """HBM bytes ONE block-sparse layer moves in a decode iteration over
    rows whose caches hold `contexts` tokens: the projections (bf16), the
    selected blocks' K and V rows and the compressed keys a row."""
    row = cfg.n_kv_heads * cfg.head_dim * BF16
    kv = sum(2 * row * selected_keys(cfg, c - 1, c) for c in contexts)
    ck = sum(row * visible_kernels(cfg, c - 1, c) for c in contexts)
    return attn_params(cfg) * BF16 + kv + ck


def lightning_decode_bytes(cfg, rows):
    """HBM bytes ONE Lightning layer moves in a decode iteration: the
    projections (bf16) and each row's fp32 state read and written once."""
    state = cfg.lightning_n_heads * cfg.lightning_head_dim**2 * FP32
    return lightning_params(cfg) * BF16 + 2 * rows * state


def decode_bytes(cfg, contexts):
    """HBM bytes one decode iteration of the static program has to move."""
    rows = len(contexts)
    return (cfg.n_sparse_layers * sparse_decode_bytes(cfg, contexts)
            + cfg.n_lightning_layers * lightning_decode_bytes(cfg, rows)
            + (cfg.n_layers * mlp_params(cfg)
               + cfg.hidden_dim * cfg.vocab_size) * BF16
            + 2 * rows * cfg.vocab_size * FP32)

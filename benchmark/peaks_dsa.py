"""FLOP and byte arithmetic of dots3_note (latent attention in two
geometries: full layers behind a token indexer, sliding layers over a ring
of latent rows; a headwise gate; a dense MLP in the leading layer, a
mixture of experts in the others; an untied head), counted from the shapes
whatever implements them.  A multiply-add is 2 FLOPs.

Per layer, forward, of the heads and experts HELD:
  * a full layer: the two low-rank query projections, the latent and
    shared-rope projection, the key and value up-projections, the gate,
    o, and the indexer's three projections; for every token its index
    scores against every visible key (`index_n_heads` x `index_head_dim`)
    and the scores (q/k width) and weighted sum (v width) over its
    SELECTED keys, min(visible, index_topk) — a form that multiplies every
    causal key under a mask, or carries v on zero columns, does more: that
    is not counted as work;
  * a sliding layer: the same projections at its own sizes, no indexer,
    scores and weighted sum over min(visible, window) keys;
  * the MLP: a dense one three matrices [h, f]; a sparse one the router,
    the shared expert and the held share of a token's k choices.
A decode iteration of the static program moves, at least: every weight
once (bf16), each row's index keys of a full layer (every visible slot, DI
wide), its SELECTED latent rows, its live ring rows, and the rows' fp32
logits written and read once.
"""

BF16, FP32 = 2, 4


def _geom(cfg, kind):
    """(heads held, rq, c, nope, rope, v) of a kind of layer."""
    if kind == "F":
        return (cfg.n_q_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim)
    return (cfg.swa_n_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
            cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
            cfg.swa_v_head_dim)


def row_width(cfg, kind):
    """Values a token's row of the kind's cache holds."""
    _, _, c, _, rope, _ = _geom(cfg, kind)
    return c + rope


def index_params(cfg):
    if not cfg.index_topk:
        return 0
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    return cfg.q_lora_rank * hi * di + cfg.hidden_dim * (di + hi)


def mixer_params(cfg, kind):
    """One layer's mixer: its projections, the gate and, a full layer, the
    indexer's."""
    h = cfg.hidden_dim
    hq, rq, c, nope, rope, v = _geom(cfg, kind)
    return (h * rq + rq * hq * (nope + rope) + h * (c + rope)
            + c * hq * (nope + v) + hq * v * h + h * hq
            + (index_params(cfg) if kind == "F" else 0))


def moe_params(cfg):
    """A sparse layer's MLP, a token: the router, the shared expert and the
    held share of its k choices."""
    h, f = cfg.hidden_dim, cfg.moe_intermediate_dim
    held = cfg.n_experts_per_tok * cfg.n_experts / cfg.router_width
    return h * cfg.router_width + 3 * h * cfg.shared_expert_dim + 3 * h * f * held


def matmul_params(cfg):
    """Parameters in matmuls for ONE token's forward, head included."""
    pattern = cfg.window_pattern
    mixers = sum(mixer_params(cfg, k) for k in pattern)
    dense = cfg.first_k_dense * 3 * cfg.hidden_dim * cfg.intermediate_dim
    sparse = (cfg.n_layers - cfg.first_k_dense) * moe_params(cfg)
    return mixers + dense + sparse + cfg.hidden_dim * cfg.vocab_size


def _sum_capped(lo, hi, cap):
    """sum over positions t in [lo, hi) of min(t + 1, cap)."""
    knee = max(min(cap, hi), lo)
    return (knee * (knee + 1) - lo * (lo + 1)) / 2.0 + (hi - knee) * float(cap)


def selected_keys(cfg, lo, hi):
    """Keys the queries at positions [lo, hi) of one sequence read in a
    full layer."""
    return _sum_capped(lo, hi, cfg.index_topk or hi)


def window_keys(cfg, lo, hi):
    return _sum_capped(lo, hi, cfg.attn_window)


def visible_keys(lo, hi):
    return (hi * (hi + 1) - lo * (lo + 1)) / 2.0


def index_flops(cfg, lo, hi):
    """One full layer's index scores over positions [lo, hi)."""
    if not cfg.index_topk:
        return 0.0
    return 2.0 * cfg.index_n_heads * cfg.index_head_dim * visible_keys(lo, hi)


def attend_flops(cfg, kind, lo, hi):
    """One layer's scores and weighted sums over positions [lo, hi), at the
    q/k and v widths of its geometry."""
    hq, _, _, nope, rope, v = _geom(cfg, kind)
    keys = selected_keys(cfg, lo, hi) if kind == "F" else window_keys(
        cfg, lo, hi)
    return 2.0 * hq * (nope + rope + v) * keys


def mixing_flops(cfg, lo, hi):
    """Every layer's index scores and attention over positions [lo, hi)."""
    return sum(
        attend_flops(cfg, k, lo, hi) + (index_flops(cfg, lo, hi) if k == "F"
                                        else 0.0)
        for k in cfg.window_pattern)


def flops_forward(cfg, seqlens):
    n = float(sum(seqlens))
    return 2.0 * matmul_params(cfg) * n + sum(
        mixing_flops(cfg, 0, s) for s in seqlens)


def flops_train(cfg, seqlens):
    """Forward + backward = 3x forward for what is differentiated; the
    indexer (its projections and scores) has no backward: once.  Recompute
    excluded."""
    n = float(sum(seqlens))
    n_full = cfg.window_pattern.count("F")
    once = 2.0 * n_full * index_params(cfg) * n + n_full * sum(
        index_flops(cfg, 0, s) for s in seqlens)
    return 3.0 * (flops_forward(cfg, seqlens) - once) + once


def flops_generate(cfg, prompt_lens, gen_lens):
    """Every prompt forwarded once, then every new token through the
    caches."""
    total = flops_forward(cfg, prompt_lens)
    for p, g in zip(prompt_lens, gen_lens):
        total += 2.0 * matmul_params(cfg) * g + mixing_flops(cfg, p, p + g)
    return total


def index_decode_flops(cfg, contexts):
    """ONE full layer's index scores in a decode iteration over rows whose
    caches hold `contexts` tokens."""
    return sum(index_flops(cfg, c - 1, c) for c in contexts)


def index_decode_bytes(cfg, contexts):
    """HBM bytes ONE full layer's indexer moves in a decode iteration: its
    projections (bf16) and every visible index key of every row."""
    keys = sum(contexts) * cfg.index_head_dim * BF16
    return index_params(cfg) * BF16 + keys


def selected_decode_bytes(cfg, contexts):
    """The latent rows ONE full layer's attention reads in a decode
    iteration: each row's selected ones."""
    return sum(
        min(c, cfg.index_topk or c) for c in contexts
    ) * row_width(cfg, "F") * BF16


def ring_decode_bytes(cfg, contexts):
    """The latent rows ONE sliding layer reads: each row's live ring."""
    return sum(
        min(c, cfg.attn_window) for c in contexts) * row_width(cfg, "S") * BF16


def decode_bytes(cfg, contexts):
    """HBM bytes one decode iteration of the static program has to move."""
    rows = len(contexts)
    pattern = cfg.window_pattern
    n_full, n_ring = pattern.count("F"), pattern.count("S")
    weights = (
        sum(mixer_params(cfg, k) for k in pattern)
        + cfg.first_k_dense * 3 * cfg.hidden_dim * cfg.intermediate_dim
        + cfg.hidden_dim * cfg.vocab_size) * BF16
    sparse = (cfg.n_layers - cfg.first_k_dense) * experts_decode_bytes(
        cfg, rows)
    caches = (
        n_full * (sum(contexts) * cfg.index_head_dim * BF16
                  + selected_decode_bytes(cfg, contexts))
        + n_ring * ring_decode_bytes(cfg, contexts))
    return weights + sparse + caches + 2 * rows * cfg.vocab_size * FP32


def experts_decode_bytes(cfg, rows):
    """ONE sparse layer's MLP weights a decode iteration reads: the router,
    the shared expert and the held experts the rows' choices touch in
    expectation (uniform routing)."""
    h, f = cfg.hidden_dim, cfg.moe_intermediate_dim
    miss = (1.0 - 1.0 / cfg.router_width) ** (rows * cfg.n_experts_per_tok)
    touched = cfg.n_experts * (1.0 - miss)
    return (h * cfg.router_width + 3 * h * cfg.shared_expert_dim
            + 3 * h * f * touched) * BF16


def cache_bytes(cfg, rows, s_max):
    """(latent rows, index keys, rings) bytes of a static program's cache."""
    pattern = cfg.window_pattern
    n_full, n_ring = pattern.count("F"), pattern.count("S")
    return (
        n_full * rows * s_max * row_width(cfg, "F") * BF16,
        n_full * rows * s_max * cfg.index_head_dim * BF16 * bool(cfg.index_topk),
        n_ring * rows * min(cfg.attn_window, s_max) * row_width(cfg, "S") * BF16,
    )

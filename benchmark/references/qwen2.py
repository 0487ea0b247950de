"""Plain reference of the qwen2 decoder (Qwen2 / R1-Distill-Qwen): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, no cache, no kernels, no packing, under
`jax.default_matmul_precision("highest")` (on a TPU an fp32 matmul is
otherwise done in bf16 passes).

Follows the published architecture (HF `modeling_qwen2.py`): pre-norm
RMSNorm, grouped-query attention with biases on q/k/v only, rotary
embedding in the half-rotation ("rotate_half") convention applied to q and
k, causal softmax attention scaled by head_dim ** -0.5, SwiGLU MLP, final
RMSNorm, an untied or tied linear head.  No departures.  Sliding-window
attention is declared off in both configs (`use_sliding_window: false`).

It reads the ENGINE'S weights (bf16, layer-stacked under "blocks") and
upcasts them, so a difference from the system is a difference in the
arithmetic, not in the weights.

TOLERANCE (per-token log-probability, system vs this reference): the
system computes activations in bf16 (8 bits of mantissa, rounding 2^-9
relative per operation) through every layer and takes the head's logits in
fp32 from bf16 inputs; the reference keeps fp32 throughout.  With seeded
random weights the logits have a spread of about 1, so rounding noise of a
few 1e-3 per layer accumulates to about 1e-2 in a log-probability.  The
bounds are set at about four times what the chip showed (PERF.md,
Findings, PR 22) and are far below what a lower-precision path would
give: an int8 or fp8 matmul path or a wrong rotary convention moves
log-probabilities by 0.1 to several nats.
"""

import jax
import jax.numpy as jnp
import numpy as np

# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {"mean_abs": 0.03, "max_abs": 0.15}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {"mean_abs": 1e-4, "max_abs": 1e-3}


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _layer(x, blocks, i, cfg):
    """One decoder layer over one sequence.  x: [T, D] fp32."""
    blk = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        .astype(jnp.float32),
        blocks,
    )
    t = x.shape[0]
    h = _rms_norm(x, blk["ln1"], cfg.rms_norm_eps)
    q = (h @ blk["wq"] + blk["bq"]).reshape(t, cfg.n_q_heads, cfg.head_dim)
    k = (h @ blk["wk"] + blk["bk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ blk["wv"] + blk["bv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
    inv_freq = 1.0 / (
        cfg.rope_theta
        ** (jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, d]
    q = q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)
    k = k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)
    rep = cfg.n_q_heads // cfg.n_kv_heads
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(t, cfg.q_dim) @ blk["wo"]
    h = _rms_norm(x, blk["ln2"], cfg.rms_norm_eps)
    return x + (jax.nn.silu(h @ blk["wg"]) * (h @ blk["wu"])) @ blk["wd"]


def _head_chunk(x, head, v0, size, targets):
    """(max, sum-exp, target logit) of one slice of the vocabulary.
    head: [D, V] (untied) sliced on axis 1."""
    w = jax.lax.dynamic_slice_in_dim(head, v0, size, axis=1)
    logits = x @ w.astype(jnp.float32)  # [T, size]
    m = jnp.max(logits, axis=-1)
    s = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    idx = targets - v0
    inside = (idx >= 0) & (idx < size)
    tl = jnp.take_along_axis(
        logits, jnp.clip(idx, 0, size - 1)[:, None], axis=1
    )[:, 0]
    return m, s, jnp.where(inside, tl, 0.0)


PAD_TO = 512  # sequence lengths are padded up to a multiple of this


def next_token_logprobs(params, cfg, tokens):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence.

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; attention is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    return _next_token_logprobs(params, cfg, padded)[: n - 1]


def _next_token_logprobs(params, cfg, tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    layer = jax.jit(_layer, static_argnums=3)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(cfg.n_layers):
            x = layer(x, params["blocks"], i, cfg)
        x = _rms_norm(
            x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps
        )[:-1]
        head = (params["embed"].T if cfg.tied_embeddings
                else params["lm_head"])
        vocab = head.shape[1]
        n_chunks = 8 if vocab % 8 == 0 else 1
        size = vocab // n_chunks
        targets = tokens[1:]
        m_all, s_all, tl_all = [], [], []
        for c in range(n_chunks):
            m, s, tl = head_chunk(x, head, c * size, size, targets)
            m_all.append(m), s_all.append(s), tl_all.append(tl)
        m_all, s_all = jnp.stack(m_all), jnp.stack(s_all)
        m = jnp.max(m_all, axis=0)
        lse = m + jnp.log(jnp.sum(s_all * jnp.exp(m_all - m), axis=0))
        return np.asarray(sum(tl_all) - lse, np.float32)

"""Plain reference of the OLMoE decoder (allenai/OLMoE-1B-7B): the forward
pass in straightforward `jax.numpy` and float32, one layer at a time, no
cache, no kernels, no packing, no sorting of tokens by expert, under
`jax.default_matmul_precision("highest")` (on a TPU an fp32 matmul is
otherwise done in bf16 passes).

Follows the public description (HF `modeling_olmoe.py`): pre-norm RMSNorm;
q, k, v projections WITHOUT bias; QK-norm — an RMSNorm with a learned
weight over the WHOLE projected vector (`q_norm` over all heads' 2,048
values, `k_norm` over `num_key_value_heads x head_dim`), after the
projection and before the split into heads and the rotary embedding;
rotary embedding in the half-rotation ("rotate_half") convention on q and
k; causal softmax attention scaled by head_dim ** -0.5 (MHA in the
published model; grouped heads are repeated if a config has fewer KV
heads); the MLP of EVERY layer a mixture of experts: router = linear
without bias, softmax in fp32 over all experts, the top
`num_experts_per_tok` taken, their probabilities used AS THEY ARE
(`norm_topk_prob: false`: not renormalised to sum to one; renormalised
only where the config says true), each expert a SwiGLU
`down(silu(gate x) * up x)`, the layer's output the plain sum over a
token's chosen experts of weight x expert(x); no shared expert; final
RMSNorm, an untied (or tied) linear head.  Departures: `clip_qkv` is null
in the published config and is not modelled (the program's reader refuses
a config that sets it).

The routing is the reference's OWN, in fp32 from its own fp32 layer
input; it is never handed the system's choice.  Every expert is computed
for every token (one expert at a time) and weighted by the token's
router weight, zero where the expert was not chosen: the sum over the
chosen experts, written without a gather.

It reads the ENGINE'S weights (bf16, layer-stacked under "blocks") and
upcasts them, so a difference from the system is a difference in the
arithmetic, not in the weights.

TOLERANCE (per-token log-probability, system vs this reference).  Two
sources.  (1) bf16 rounding, as in the dense reference (`qwen2.py`): the
system computes activations in bf16 through every layer, a few 1e-3 per
layer.  (2) Routing.  With 64 experts the 8th and 9th router
probabilities of a token lie about 7% apart on average, and closer than
the system's rounding for some tokens: the system's bf16 layer input
differs from the reference's fp32 one by about 1e-2 relative, so for a
share of the (token, layer) pairs it picks another 8th expert.  The
swapped experts carry the smallest of the eight weights and nearly equal
ones (about 0.03 of a sum of about 0.45), so a swap moves one layer's MLP
output of one token by about a tenth of its norm: bounded, but larger
than rounding — it is what sets the maximum.  It is counted, not hidden:
`routing_report` replays the layer equations in the system's arithmetic
(bf16 activations, default matmul precision, fp32 router) and counts
`router_flips` — (token, layer) pairs whose top-k SET differs between
the system-precision router and the fp32 `highest` router ON THE SAME
bf16 layer input — and `router_drift` — pairs whose set differs between
that replay and this reference's own pass.  On the chip at the published
widths (PERF.md, Findings, PR 26; 2,048 response tokens a run, 3 layers):
`router_flips` 0 of 3,504 pairs a sequence in every run (the router's
operands are bf16 values, whose products fp32 holds exactly),
`router_drift` 2.3–3.2% of the pairs, and |system - reference| mean
0.0036–0.0039, max 0.045–0.071, for generator and trainer alike.  The
bounds are about three times that, and still fail what they must
(tests/test_olmoe.py, at toy size): weights renormalised to sum to one
(every MLP output scaled by about 2.2), a QK-norm taken per head, and
tokens dropped by a capacity limit each move log-probabilities by more.
What they do NOT fail: router logits rounded to bf16 before the softmax.
That moves the eight weights by 2^-9 relative and swaps near-ties only —
the size of the rounding the bounds exist to admit (mean 0.003 at toy
size) — so it is not claimed.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

# The rotary half-rotation, the chunked log-softmax head and the padding
# rule are the dense reference's, unchanged by this architecture.
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rotate_half

# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {"mean_abs": 0.015, "max_abs": 0.2}
# On the CPU rehearsal the system itself computes in fp32: no rounding to
# speak of, and a swap only where two probabilities tie to 1e-6.
TOLERANCE_FP32 = {"mean_abs": 1e-4, "max_abs": 1e-3}


def _rms_norm(x, w, eps):
    """In fp32 whatever x's dtype (a no-op for the reference's own fp32
    pass; the replay in the system's arithmetic norms as the system does)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(
        x.dtype)


def _w(blocks, i, name, dtype=jnp.float32):
    """Layer i's slice of a stacked leaf, upcast to fp32 (the reference) or
    left in `dtype` (the replay in the system's arithmetic)."""
    return jax.lax.dynamic_index_in_dim(
        blocks[name], i, 0, keepdims=False
    ).astype(dtype)


def _attention(x, blocks, i, cfg):
    """x + attention(x) over one sequence.  x: [T, D]; computed in x's
    dtype (fp32 in the reference's own pass)."""
    t, dt = x.shape[0], x.dtype
    h = _rms_norm(x, _w(blocks, i, "ln1"), cfg.rms_norm_eps)
    q = _rms_norm(h @ _w(blocks, i, "wq", dt), _w(blocks, i, "q_norm"),
                  cfg.rms_norm_eps)
    k = _rms_norm(h @ _w(blocks, i, "wk", dt), _w(blocks, i, "k_norm"),
                  cfg.rms_norm_eps)
    v = h @ _w(blocks, i, "wv", dt)
    q = q.reshape(t, cfg.n_q_heads, cfg.head_dim)
    k = k.reshape(t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(t, cfg.n_kv_heads, cfg.head_dim)
    inv_freq = 1.0 / (
        cfg.rope_theta
        ** (jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, d]
    q = (q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)).astype(dt)
    k = (k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)).astype(dt)
    rep = cfg.n_q_heads // cfg.n_kv_heads
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    attn = jnp.einsum("hqk,khd->qhd", probs, v)
    return x + attn.reshape(t, cfg.q_dim) @ _w(blocks, i, "wo", dt)


def _route(h, router, cfg):
    """[T, E] router weights: a token's top-k softmax probabilities in
    their experts' columns, zero elsewhere.  fp32."""
    probs = jax.nn.softmax(
        h.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1
    )
    top_w, top_i = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)


def _experts(h, gates, blocks, i):
    """sum_e gates[:, e] * down_e(silu(gate_e h) * up_e h), one expert at a
    time; each expert's weights are upcast as it is used."""
    wg, wu, wd = (
        jax.lax.dynamic_index_in_dim(blocks[n], i, 0, keepdims=False)
        for n in ("wg", "wu", "wd")
    )

    def one(acc, xs):
        g, u, d, w = xs
        y = (jax.nn.silu(h @ g.astype(h.dtype)) * (h @ u.astype(h.dtype))
             ) @ d.astype(h.dtype)
        return acc + w[:, None].astype(h.dtype) * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, gates.T))
    return out


def _layer(x, blocks, i, cfg):
    """One decoder layer over one sequence.  x: [T, D] fp32."""
    x = _attention(x, blocks, i, cfg)
    h = _rms_norm(x, _w(blocks, i, "ln2"), cfg.rms_norm_eps)
    gates = _route(h, _w(blocks, i, "router"), cfg)
    return x + _experts(h, gates, blocks, i), gates > 0


def _pad(tokens):
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    return padded


# The last sequence's routing comparison, for whoever wants it after
# `next_token_logprobs` (the harness's report has no room for it).
LAST_ROUTING = {}


def next_token_logprobs(params, cfg, tokens):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence.

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; attention is causal, so the
    padding changes nothing before it and its own outputs are dropped.

    Also counts, off the timed path, how the system's arithmetic would
    have routed this sequence (`routing_report`), into `LAST_ROUTING` and
    one line on stderr."""
    n = len(tokens)
    padded = _pad(tokens)
    logp, chosen = _next_token_logprobs(params, cfg, padded)
    LAST_ROUTING.clear()
    LAST_ROUTING.update(routing_report(params, cfg, padded, n, chosen))
    print(f"[benchmark] olmoe reference, {n} tokens: {LAST_ROUTING}",
          file=sys.stderr, flush=True)
    return logp[: n - 1]


def _next_token_logprobs(params, cfg, tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    layer = jax.jit(_layer, static_argnums=3)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(cfg.n_layers):
            x, sel = layer(x, params["blocks"], i, cfg)
            chosen.append(sel)
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
        return np.asarray(sum(tl_all) - lse, np.float32), chosen


def _replay_layer(x, blocks, i, cfg):
    """The same layer in the SYSTEM's arithmetic — activations in the
    weights' dtype, default matmul precision, the router in fp32 from the
    rounded layer input — and the same input routed under `highest`."""
    x = _attention(x, blocks, i, cfg)
    h = _rms_norm(x, _w(blocks, i, "ln2"), cfg.rms_norm_eps)
    router = _w(blocks, i, "router")
    gates = _route(h, router, cfg)
    with jax.default_matmul_precision("highest"):
        exact = _route(h, router, cfg)
    return x + _experts(h, gates, blocks, i), gates > 0, exact > 0


def routing_report(params, cfg, padded, n, reference_choice):
    """{router_pairs, router_flips, router_drift} over the first `n`
    tokens of one padded sequence.  `router_flips`: (token, layer) pairs
    whose top-k SET differs between the system-precision router and the
    fp32 `highest` router on the same layer input of a replay in the
    system's arithmetic.  `router_drift`: pairs whose set differs between
    that replay and the reference's own fp32 pass (`reference_choice`,
    one [T, E] bool per layer) — what the tolerance has to absorb."""
    dtype = params["blocks"]["wq"].dtype
    layer = jax.jit(_replay_layer, static_argnums=3)
    x = jnp.take(params["embed"], jnp.asarray(padded, jnp.int32), axis=0)
    x = x.astype(dtype)
    flips = drift = 0
    for i in range(cfg.n_layers):
        x, sel, exact = layer(x, params["blocks"], i, cfg)
        flips += int(jnp.any(sel[:n] != exact[:n], axis=-1).sum())
        drift += int(jnp.any(sel[:n] != reference_choice[i][:n], axis=-1).sum())
    return {"router_pairs": n * cfg.n_layers, "router_flips": flips,
            "router_drift": drift}

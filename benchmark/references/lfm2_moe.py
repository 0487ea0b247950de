"""Plain reference of the LFM2-MoE decoder (LiquidAI/LFM2-8B-A1B,
`model_type: lfm2_moe`): the forward pass in straightforward `jax.numpy` and
float32, one layer at a time, the short convolution as three shifted
products, no cache, no kernels, no packing, no sorting of tokens by expert,
under `jax.default_matmul_precision("highest")`.

Per layer l, pre-norm, RMSNorm eps `norm_eps` with a plain weight, no
biases: y = x + Mixer_l(norm(x; operator_norm)), z = y + MLP_l(norm(y;
ffn_norm)); a final norm (`embedding_norm`), the head tied to the
embedding.

  * `layer_types[l] == "conv"`, the gated short convolution: (B, C, u) =
    the thirds of h W_in, in that order; g = B * u; c_t = sum_j taps[j] *
    g_{t - 2 + j} over j = 0..2 (depthwise, causal, taps oldest first, an
    input before the sequence's first token counts zero); the mixer's
    output is (C * c) W_out.  No activation anywhere in it.
  * `"full_attention"`, 32 query heads over 8 key/value heads of 64: q = h
    Wq, k = h Wk, v = h Wv; q and k RMS-normed PER HEAD over the 64 (a [64]
    weight each); rotary embedding (rotate-half, theta 1,000,000) over the
    whole head; scores scaled by 64^-1/2, causal softmax in fp32 over a
    dense mask built from the positions; concat(heads) Wo.
  * MLP, l < `num_dense_layers`: W_2(silu(W_1 h) * W_3 h), 7,168 wide.
  * MLP, the other layers: s = sigmoid(h R) over ALL the router's outputs
    in fp32; chosen = the top k of s + b (b: `expert_bias`); w = s[chosen]
    / (their sum + 1e-6) x `routed_scaling_factor`; sum of w_e W_2^e(
    silu(W_1^e h) * W_3^e h) over the chosen.  No shared expert.

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike:
  * The rank's share.  `cfg.n_experts` experts are HELD of the router's
    `cfg.router_width`, numbers [expert_offset, expert_offset + n_experts).
    The router scores and ranks all of them and renormalises over the k it
    chose; the layer's output is the held experts' part of the weighted
    sum.  What the absent experts would add is left out and nothing stands
    in for it.
  * The vocabulary is the slice the table holds: log-probabilities are over
    the slice.
  * Conventions where the published config is silent (the configuration's
    `assumed`): the tied head, heads of 64, the per-head q/k norm, the
    order B, C, u and the taps oldest first.

It reads the ENGINE'S weights (bf16, stacked under "blocks": the leading
dense layers' leaves under `dense_*`, each mixer's leaves over its own
layers) and upcasts them, so a difference from the system is a difference
in the arithmetic.  Attention is computed a block of queries at a time so
that 32 heads x 4,608 x 4,608 scores never exist at once; the mask of a
block is still the dense one.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  Besides the log-probabilities, `check_generator` builds a
`GeneratorEngine` over the same weights and mesh, runs ITS static decode
program at the cell's 32 slots over prompts cut from the sequence (the
program's own sampler, cache, kernels and types; the cache it leaves is one
more output), and holds what that program left in the conv layers' tails
(the last two gated inputs g of the tokens it consumed) and in the
attention layers' K/V to the g, the roped K and the V this reference
computes over the tokens it sampled.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.mellum import _engine, _padded
from benchmark.references.qwen2 import _head_chunk, _rms_norm, _rotate_half

_TOL = files.load_json("configs", "lfm2-8b-a1b-e8.json")["benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `rows_readings` (`check_generator`), the chip's and the CPU's.
ROWS_TOLERANCE = dict(_TOL["rows"])
ROWS_TOLERANCE_FP32 = dict(_TOL["fp32"]["rows"])

# `lower="lower"` computes what the tolerance has to refuse: the router's
# scores rounded to bfloat16, and the roped K, the V and the gated inputs g
# (what the cache keeps) rounded to 8 bits (e4m3), each a precision below
# what the configuration states.  One alone: "lower:router", "lower:cache".
LOWER_PRECISION = "lower"
_LOWER = {"router": (8, 7), "cache": (4, 3)}  # (exponent, mantissa) bits
# `fault=` computes the model with ONE part of its mathematics wrong; the
# tests and the configuration's file hold each outside a stated bound.
FAULTS = (
    "taps_newest_first",  # the conv's taps applied in the other order
    "swap_bc",  # B and C exchanged: g = C * u, the output gated by B
    "no_c_gate",  # out_proj of the conv's sum alone
    "no_qk_norm",  # q and k not normed per head
    "choice_without_bias",  # the top k chosen by the scores alone
    "bias_in_weights",  # the chosen weights taken from score + bias
    "no_topk_norm",  # the chosen scores not divided by their sum
    "experts_in_leading",  # an expert block in a leading layer's place
)
# `check_generator`'s call of the static decode program: the cell's 32
# slots in one wave and its 512 new tokens; the prompts are the sequence's
# first tokens, their lengths spread evenly from a quarter of it to all of
# it.  The first and the last slot are compared.
CHECK_SLOTS = 32
CHECK_NEW = 512
QUERY_BLOCK = 512  # queries a block of the dense attention


def _lower(x, lower, part):
    """x rounded as `lower` says for `part`, in float32.  Through
    `reduce_precision`: XLA drops a cast there and back on the TPU."""
    if lower is None:
        return x
    _, _, only = lower.partition(":")
    if only and only != part:
        return x
    return jax.lax.reduce_precision(x, *_LOWER[part])


def _short_conv(h, w, cfg, fault=None, lower=None):
    """One layer's gated short convolution over one sequence.  h: [T, D]
    normed input -> ([T, D], the gated inputs g [T, D] a cache keeps the
    last K - 1 of)."""
    b, c, u = jnp.split(h @ w["sc_in"], 3, axis=-1)
    if fault == "swap_bc":
        b, c = c, b
    g = _lower(b * u, lower, "cache")
    taps = w["sc_conv"][::-1] if fault == "taps_newest_first" else w["sc_conv"]
    kk, t = taps.shape[0], h.shape[0]
    conv = jnp.zeros_like(g)
    for j in range(kk):  # tap j meets the input K - 1 - j tokens back
        back = kk - 1 - j
        conv = conv + taps[j] * jnp.pad(g, ((back, 0), (0, 0)))[:t]
    y = conv if fault == "no_c_gate" else c * conv
    return y @ w["sc_out"], g


def _attention(h, w, cfg, fault=None, lower=None):
    """One layer's attention over one sequence.  h: [T, D] normed input ->
    ([T, D], the roped K and the V a cache keeps, [T, n_kv, d] each)."""
    t, hq, hkv, d = h.shape[0], cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ w["wq"]).reshape(t, hq, d)
    k = (h @ w["wk"]).reshape(t, hkv, d)
    v = (h @ w["wv"]).reshape(t, hkv, d)
    if fault != "no_qk_norm":
        q = _rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
    inv = cfg.rope_theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = q * cos + _rotate_half(q) * sin
    k = k * cos + _rotate_half(k) * sin
    k, v = _lower(k, lower, "cache"), _lower(v, lower, "cache")
    rep = hq // hkv
    kx, vx = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(t)
    out = []
    for q0 in range(0, t, QUERY_BLOCK):  # a block of queries, ALL the keys
        allowed = pos[q0: q0 + QUERY_BLOCK, None] >= pos[None, :]
        scores = jnp.einsum(
            "qhd,khd->hqk", q[q0: q0 + QUERY_BLOCK], kx) * d ** -0.5
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vx))
    attn = jnp.concatenate(out).reshape(t, hq * d)
    return attn @ w["wo"], k, v


def _route(h, w, cfg, fault=None, lower=None):
    """[T, router_width] router weights: a token's chosen experts' scores,
    renormalised over the chosen, in their columns; zero elsewhere."""
    scores = _lower(jax.nn.sigmoid(h @ w["router"]), lower, "router")
    biased = scores + w["router_bias"]
    _, top_i = jax.lax.top_k(
        scores if fault == "choice_without_bias" else biased,
        cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, top_i, axis=-1)
    if cfg.moe_norm_topk and fault != "no_topk_norm":
        top_w = top_w / (
            jnp.sum(top_w, axis=-1, keepdims=True) + cfg.moe_norm_topk_eps)
    top_w = top_w * cfg.moe_routed_scale
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)


def _moe(h, w, cfg, fault=None, lower=None):
    """The held experts' part of the routed sum, one expert at a time."""
    gates = _route(h, w, cfg, fault, lower)
    held = gates[:, cfg.expert_offset: cfg.expert_offset + cfg.n_experts]

    def one(acc, xs):
        g, u, dn, wt = xs
        f32 = jnp.float32
        y = (jax.nn.silu(h @ g.astype(f32)) * (h @ u.astype(f32))
             ) @ dn.astype(f32)
        return acc + wt[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (w["wg"], w["wu"], w["wd"], held.T))
    return out


# The engine's names of a mixer's leaves and of each kind of MLP's.
_MIXER_LEAVES = {
    "C": ("sc_in", "sc_conv", "sc_out"),
    "F": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}
_DENSE_LEAVES = ("wg", "wu", "wd")
_MOE_LEAVES = ("router", "router_bias", "wg", "wu", "wd")


def _layer(x, blocks, nth, mixer, dense, cfg, fault=None, lower=None):
    """One decoder layer over one sequence: `mixer` ("C" the short
    convolution, "F" attention) and `dense` (a leading layer's MLP, else
    the experts) are static, so one compiled program serves every layer of
    a kind; `nth` = the layer's traced indices (among its group's layers,
    among those with its mixer, among the expert layers).  x: [T, D] fp32
    -> (x, what the mixer leaves a cache: g, or the roped K and V stacked
    [T, 2, n_kv, d])."""
    layer, mix, moe = nth
    pre = "dense_" if dense else ""

    def leaves(names, at, pre=pre):
        return {
            n: blocks[pre + n][at] if blocks[pre + n].ndim == 4
            else blocks[pre + n][at].astype(jnp.float32)
            for n in names
        }

    w = {**leaves(("ln1", "ln2"), layer), **leaves(_MIXER_LEAVES[mixer], mix)}
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    if mixer == "C":
        out, left = _short_conv(h, w, cfg, fault, lower)
    else:
        out, k, v = _attention(h, w, cfg, fault, lower)
        left = jnp.stack([k, v], axis=1)
    x = x + out
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    if dense and fault != "experts_in_leading":
        m = leaves(_DENSE_LEAVES, layer)
        return x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wu"])) @ m["wd"], left
    return x + _moe(h, leaves(_MOE_LEAVES, moe, ""), cfg, fault, lower), left


_layer_jit = jax.jit(_layer, static_argnums=(3, 4, 5, 6, 7))


def layer_plan(cfg):
    """(mixer, dense, (index in its group, among the group's layers with
    the mixer, among the expert layers)) of every layer, in order; a
    leading layer's expert index is its own number (the `experts_in_
    leading` fault's block)."""
    out, seen = [], {}
    k = cfg.first_k_dense
    for l, mixer in enumerate(cfg.window_pattern):
        dense = l < k
        at = seen.get((dense, mixer), 0)
        seen[dense, mixer] = at + 1
        out.append((mixer, dense, (l if dense else l - k, at,
                                   l if dense else l - k)))
    return out


def _hidden_and_rows(params, cfg, tokens, fault=None, lower=None):
    """-> ([T, D] fp32 hidden states after the final norm, what every
    layer's mixer leaves a cache, in layer order: g [T, D] of a conv layer,
    the roped K and V [T, 2, n_kv, d] of an attention layer)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    left = []
    for mixer, dense, nth in layer_plan(cfg):
        x, here = _layer_jit(
            x, params["blocks"], tuple(jnp.int32(i) for i in nth), mixer,
            dense, cfg, fault, lower)
        left.append(here)
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, left


def final_hidden(params, cfg, tokens, fault=None, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_rows(params, cfg, tokens, fault, lower)[0]


def logits(params, cfg, tokens, fault=None, lower=None):
    """[T, V] fp32 logits over the table's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(
            params, cfg, jnp.asarray(tokens, jnp.int32), fault, lower)
        return x @ params["embed"].astype(jnp.float32).T


def next_token_logprobs(params, cfg, tokens, fault=None, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the generator's static program leaves in its tails and its cache (the
    reference proper only: `fault` and `lower` compute a control).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; every mixer is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    out, _ = _next_token_logprobs(params, cfg, _padded(tokens), fault, lower)
    print(f"[benchmark] lfm2_moe reference, {n} tokens, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if fault is not None or lower is not None:  # a control
        return out
    readings, problems = check_generator(params, cfg, tokens)
    print(f"[benchmark] lfm2_moe generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# What the generator's static program leaves in its tails and its cache,
# against g, the roped K and the V
# --------------------------------------------------------------------------

def generator_rollouts(params, cfg, tokens, slots=(0, CHECK_SLOTS - 1)):
    """The static decode program of a `GeneratorEngine`, once, over
    CHECK_SLOTS prompts cut from `tokens` -> for each slot of `slots` (its
    tokens, prompt and sampled ones, every one of which the program has
    then consumed; the log-probs the program returned for the sampled
    ones; for every layer, in layer order, what its cache holds for the
    slot, in the cache's type: a conv layer's tail [K - 1, D], an attention
    layer's K and V of every token [n, 2, n_kv, d])."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.packing import decode_bucket_len

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) * 8 // 9)
    lens = np.linspace(max(1, len(tokens) // 4), len(tokens), CHECK_SLOTS)
    prompts = [tokens[: int(n)] for n in lens]
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(48), with_cache=True)
    sp = decode_bucket_len(max(len(p) for p in prompts))
    out = []
    for r in slots:
        n, gl = len(prompts[r]), int(gen_len[r])
        first, end = sp - n, sp + gl  # the row's slots of the cache
        layers, n_conv, n_attn = [], 0, 0
        for mixer in cfg.window_pattern:
            if mixer == "C":
                layers.append(cache.conv[n_conv, r])
                n_conv += 1
            else:
                layers.append(jnp.stack(
                    [cache.k[n_attn, r, first:end],
                     cache.v[n_attn, r, first:end]], axis=1))
                n_attn += 1
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl], layers))
    return out


def _rel_err(got, want):
    """|got - want|_F / |want|_F."""
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.square(got - want).sum() / max(
        np.square(want).sum(), np.finfo(np.float32).tiny)))


def rows_readings(cfg, layers, ref_left, n):
    """Three numbers over the layers of one sequence of `n` tokens.
    `tail_rel_err_max`: the largest |tail - g[n - (K - 1): n]|_F / |.|_F
    of a conv layer's cached tail: it guards the shift, the slot and the
    prefill's last VALID inputs.  `rows_rel_err_unrouted`: the largest
    relative error of the K or the V the FIRST attention layer kept, whose
    input no routed expert has touched (the leading layers' convolutions
    and dense MLPs alone), where no flipped choice adds to the
    arithmetic's own error, so it reads the precision of the projections
    and of what the cache keeps.  `rows_rel_err_max`: over every attention
    layer."""
    kk = cfg.sconv_kernel
    tails, rows = [], []
    for mixer, got, ref in zip(cfg.window_pattern, layers, ref_left):
        if mixer == "C":
            tails.append(_rel_err(got, ref[n - (kk - 1): n]))
        else:
            got = jnp.asarray(got, jnp.float32)
            rows.append(max(
                _rel_err(got[:, i], ref[:n, i]) for i in range(2)))
    return {
        "tail_rel_err_max": max(tails),
        "rows_rel_err_unrouted": rows[0],
        "rows_rel_err_max": max(rows),
    }


def rows_problems(readings, tol):
    """What of `rows_readings` lies above `tol`, as text."""
    return [
        f"{name} {readings[name]:.3g} above {limit}"
        for name, limit in tol.items() if not readings[name] <= limit
    ]


def check_generator(params, cfg, tokens):
    """(`rows_readings` of what the generator's own program left in its
    tails and its cache — the largest over the compared slots — beside the
    mean and the largest |log-prob(program) - log-prob(reference)| over the
    tokens it sampled, which are reported and not limited here: `checks.py`
    limits the timed rollouts'; `rows_problems` under the backend's
    limits)."""
    readings, diffs = {}, []
    rollouts = generator_rollouts(params, cfg, tokens)
    longest = max(len(seq) for seq, _, _ in rollouts)
    for seq, logps, layers in rollouts:
        n = len(seq)
        # Both slots padded alike: one compiled shape a kind of layer.
        want, ref_left = _next_token_logprobs(
            params, cfg, _padded(seq, longest))
        for k, v in rows_readings(cfg, layers, ref_left, n).items():
            readings[k] = max(v, readings.get(k, 0.0))
        first = n - len(logps)  # position t scores token t + 1
        diffs.append(np.abs(logps - want[first - 1: n - 1]))
    diffs = np.concatenate(diffs)
    readings.update(
        logprob_mean_abs=float(diffs.mean()), logprob_max_abs=float(diffs.max()),
        n_tokens=int(diffs.size))
    cpu = jax.default_backend() == "cpu"
    return readings, rows_problems(
        readings, ROWS_TOLERANCE_FP32 if cpu else ROWS_TOLERANCE)


def _next_token_logprobs(params, cfg, tokens, fault=None, lower=None):
    """-> (log-probs [T - 1], what every layer's mixer leaves a cache:
    `_hidden_and_rows`)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, left = _hidden_and_rows(params, cfg, tokens, fault, lower)
        x = x[:-1]
        head = params["embed"].T  # tied: [D, V]
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
        return np.asarray(sum(tl_all) - lse, np.float32), left

"""Plain reference of the Mellum 2 decoder (JetBrains/Mellum2-12B-A2.5B,
`model_type: mellum`): the forward pass in straightforward `jax.numpy` and
float32, one layer at a time, no cache, no ring, no kernels, no packing, no
sorting of tokens by expert, under `jax.default_matmul_precision("highest")`.

Per layer l, pre-norm, RMSNorm eps `rms_norm_eps` with a plain weight, no
biases: y = x + Attn_l(norm(x)), z = y + MoE(norm(y)); a final norm, an
untied head.

  * Attention, 32 query heads over 4 key/value heads of 128: q = h Wq, k =
    h Wk, v = h Wv; q and k RMS-normed PER HEAD over the 128 (a [128]
    weight each); rotary embedding (rotate-half) on q and k; scores scaled
    by 128^-1/2, softmax in fp32 over the keys the MASK allows, built dense
    from the positions; concat(heads) Wo.
      - `layer_types[l] == "sliding_attention"`: plain rope, theta 500,000;
        token i sees key j where 0 <= i - j < `sliding_window` (1,024 keys,
        itself included).
      - `"full_attention"`: every j <= i; YaRN: with d = 128, base b, c(r) =
        d ln(original / (2 pi r)) / (2 ln b), low = floor(c(beta_fast)),
        high = ceil(c(beta_slow)) clamped to [0, d - 1], ramp_i = clip((i -
        low) / (high - low), 0, 1) for i < d / 2, inv_i = b^(-2i/d), inv'_i
        = inv_i / factor * ramp_i + inv_i (1 - ramp_i); cos and sin of
        position * inv' both times `attention_factor`.
  * MoE: p = softmax(h R) over ALL the router's outputs in fp32; the top k
    of p; their weights renormalised to sum to one (`norm_topk_prob`); sum
    of w_e W_d^e (silu(W_g^e h) * W_u^e h) over the chosen.  No shared
    expert.

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike:
  * The rank's share.  `cfg.n_experts` experts are HELD of the router's
    `cfg.router_width`, numbers [expert_offset, expert_offset + n_experts).
    The router scores and ranks all of them and renormalises over the k it
    chose; the layer's output is the held experts' part of the weighted
    sum.  What the absent experts would add is left out and nothing stands
    in for it.
  * The vocabulary is the slice the head holds: log-probabilities are over
    the slice.
  * The multi-token-prediction head the family's description mentions has
    no key in the published config and enters no next-token logit: left out.
  * Conventions where the published config is silent (the configuration's
    `assumed`): the per-head q/k norm, YaRN's `truncate` (the floor and the
    ceiling above).

It reads the ENGINE'S weights (bf16, every leaf stacked [L, ...] under
"blocks" in layer order, window and full layers alike) and upcasts them, so
a difference from the system is a difference in the arithmetic.  Attention
is computed a block of queries at a time so that 32 heads x 4,608 x 4,608
scores never exist at once; the mask of a block is still the dense one.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  Besides the log-probabilities, `check_generator` builds a
`GeneratorEngine` over the same weights and mesh, runs ITS static decode
program at the cell's 32 slots over prompts cut from the sequence (the
program's own sampler, cache, kernels and types; the cache it leaves is one
more output), and holds what that program left in the window layers' RINGS
(slot s at entry s mod 1,024: the last 1,024 tokens' roped K and V, after
the ring has wrapped in prefill and again in decode) and in the full
layers' cache to the roped K and the V this reference computes over the
tokens it sampled.
"""

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rms_norm, _rotate_half

_TOL = files.load_json("configs", "mellum2-12b-a2.5b-l4-e16.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `rows_readings` (`check_generator`), the chip's and the CPU's.
ROWS_TOLERANCE = dict(_TOL["rows"])
ROWS_TOLERANCE_FP32 = dict(_TOL["fp32"]["rows"])

# `lower="lower"` computes what the tolerance has to refuse: the router's
# probabilities rounded to bfloat16 and the roped K and the V (what the
# rings and the cache keep) rounded to 8 bits (e4m3), each a precision below
# what the configuration states.  One alone: "lower:router", "lower:cache".
LOWER_PRECISION = "lower"
_LOWER = {"router": (8, 7), "cache": (4, 3)}  # (exponent, mantissa) bits
# `fault=` computes the model with ONE part of its mathematics wrong; the
# tests and the configuration's file hold each outside a stated bound.
FAULTS = (
    "no_window",  # the sliding layers see every earlier key
    "window_minus",  # 1,023 keys
    "window_plus",  # 1,025 keys
    "no_yarn",  # plain rope on the full layers
    "yarn_on_sliding",  # YaRN on the sliding layers too
    "no_attention_factor",  # YaRN's cos and sin not scaled
    "no_topk_norm",  # the chosen experts' weights as the softmax gave them
    "no_qk_norm",  # q and k not normed per head
)
# `check_generator`'s call of the static decode program: the cell's 32
# slots in one wave and its 512 new tokens; the prompts are the sequence's
# first tokens, their lengths spread evenly from a quarter of it (under the
# window in the cell: a ring that starts part empty) to all of it (over two
# windows and the new tokens: a ring that wraps in prefill and in decode).
# The first and the last slot are compared.
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


def yarn_inv_freq(cfg):
    """inv' [d / 2] of the module's docstring, in float64 numpy."""
    d, base = cfg.head_dim, cfg.rope_theta

    def c(r):
        return d * math.log(cfg.rope_yarn_original / (2 * math.pi * r)) / (
            2 * math.log(base))

    low = max(math.floor(c(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(c(cfg.rope_yarn_beta_slow)), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv = base ** (-2.0 * i / d)
    return inv / cfg.rope_yarn_factor * ramp + inv * (1.0 - ramp)


def _rope_tables(cfg, kind, t, fault):
    """(cos, sin) [T, 1, d] of a layer of `kind` ("S" sliding, "F" full)."""
    d = cfg.head_dim
    yarn = bool(cfg.rope_yarn_factor) and (
        (kind == "F" and fault != "no_yarn")
        or (kind == "S" and fault == "yarn_on_sliding")
    )
    if yarn:
        inv = yarn_inv_freq(cfg)
        scale = cfg.rope_yarn_attention_factor or (
            0.1 * math.log(cfg.rope_yarn_factor) + 1.0)
        if fault == "no_attention_factor":
            scale = 1.0
    else:
        theta = cfg.rope_theta
        if kind == "S" and cfg.window_rope_theta:
            theta = cfg.window_rope_theta
        inv = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
        scale = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _attention(h, w, cfg, kind, fault=None, lower=None):
    """One layer's attention over one sequence.  h: [T, D] normed input ->
    ([T, D], the roped K and the V a cache keeps, [T, n_kv, d] each)."""
    t, hq, hkv, d = h.shape[0], cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ w["wq"]).reshape(t, hq, d)
    k = (h @ w["wk"]).reshape(t, hkv, d)
    v = (h @ w["wv"]).reshape(t, hkv, d)
    if fault != "no_qk_norm":
        q = _rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
    cos, sin = _rope_tables(cfg, kind, t, fault)
    q = q * cos + _rotate_half(q) * sin
    k = k * cos + _rotate_half(k) * sin
    k, v = _lower(k, lower, "cache"), _lower(v, lower, "cache")
    window = None
    if kind == "S" and fault != "no_window":
        window = cfg.attn_window + {
            "window_minus": -1, "window_plus": 1}.get(fault, 0)
    rep = hq // hkv
    kx, vx = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(t)
    out = []
    for q0 in range(0, t, QUERY_BLOCK):  # a block of queries, ALL the keys
        qi = pos[q0: q0 + QUERY_BLOCK, None]
        allowed = qi >= pos[None, :]
        if window is not None:
            allowed &= qi - pos[None, :] < window
        scores = jnp.einsum(
            "qhd,khd->hqk", q[q0: q0 + QUERY_BLOCK], kx) * d ** -0.5
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vx))
    attn = jnp.concatenate(out).reshape(t, hq * d)
    return attn @ w["wo"], k, v


def _route(h, w, cfg, fault=None, lower=None):
    """[T, router_width] router weights: a token's chosen experts'
    probabilities, renormalised over the chosen, in their columns; zero
    elsewhere."""
    probs = _lower(jax.nn.softmax(h @ w["router"], axis=-1), lower, "router")
    top_w, top_i = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    if cfg.moe_norm_topk and fault != "no_topk_norm":
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
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


def _layer(x, blocks, l, kind, cfg, fault=None, lower=None):
    """Decoder layer l of `kind` ("S" sliding, "F" full: static, so one
    compiled program serves every layer of a kind) over one sequence.
    x: [T, D] fp32 -> (x, roped K, V)."""
    w = {
        n: a[l] if a.ndim == 4 else a[l].astype(jnp.float32)
        for n, a in blocks.items()
    }
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    attn, k, v = _attention(h, w, cfg, kind, fault, lower)
    x = x + attn
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    return x + _moe(h, w, cfg, fault, lower), k, v


_layer_jit = jax.jit(_layer, static_argnums=(3, 4, 5, 6))


def _hidden_and_rows(params, cfg, tokens, fault=None, lower=None):
    """-> ([T, D] fp32 hidden states after the final norm, every layer's
    roped K and V stacked [L, T, 2, n_kv, d])."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    rows = []
    for l, kind in enumerate(cfg.window_pattern or "F" * cfg.n_layers):
        x, k, v = _layer_jit(
            x, params["blocks"], jnp.int32(l), kind, cfg, fault, lower)
        rows.append(jnp.stack([k, v], axis=1))
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, jnp.stack(rows)


def final_hidden(params, cfg, tokens, fault=None, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_rows(params, cfg, tokens, fault, lower)[0]


def logits(params, cfg, tokens, fault=None, lower=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(
            params, cfg, jnp.asarray(tokens, jnp.int32), fault, lower)
        return x @ params["lm_head"].astype(jnp.float32)


def _padded(tokens, at_least=0):
    n = len(tokens)
    padded = np.zeros(-(-max(n, at_least) // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    return padded


def next_token_logprobs(params, cfg, tokens, fault=None, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the generator's static program leaves in its rings and its cache (the
    reference proper only: `fault` and `lower` compute a control).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; attention is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    out, _ = _next_token_logprobs(params, cfg, _padded(tokens), fault, lower)
    print(f"[benchmark] mellum reference, {n} tokens, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if fault is not None or lower is not None:  # a control
        return out
    readings, problems = check_generator(params, cfg, tokens)
    print(f"[benchmark] mellum generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# What the generator's static program leaves in its rings and its cache,
# against the roped K and the V
# --------------------------------------------------------------------------

def _engine(params, cfg):
    """A `GeneratorEngine` over `params` as they lie (no copy), on their
    own mesh, built as a worker builds the timed one.  Built anew for every
    call and dropped with its compiled program, so nothing of the check
    stays on the device inside the window."""
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = getattr(params["embed"].sharding, "mesh", None)
    if mesh is None:
        mesh = make_mesh(
            ParallelConfig.from_str("d1"), sorted(params["embed"].devices()))
    # EOS is the first id past the vocabulary, as in every cell.
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=CHECK_SLOTS, donation_safe_swap=False)


def generator_rollouts(params, cfg, tokens, slots=(0, CHECK_SLOTS - 1)):
    """The static decode program of a `GeneratorEngine`, once, over
    CHECK_SLOTS prompts cut from `tokens` -> for each slot of `slots` (its
    tokens, prompt and sampled ones; the log-probs the program returned for
    the sampled ones; for every layer, in layer order, (the slots of the
    sequence the layer's cache still holds, the K and V it holds for them
    [n, 2, n_kv, d], in the cache's type): every slot of a full layer, the
    last `ring` of a window layer, read at slot mod ring)."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.packing import decode_bucket_len

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) * 8 // 9)
    lens = np.linspace(max(1, len(tokens) // 4), len(tokens), CHECK_SLOTS)
    prompts = [tokens[: int(n)] for n in lens]
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(44), with_cache=True)
    sp = decode_bucket_len(max(len(p) for p in prompts))
    pattern = cfg.window_pattern or "F" * cfg.n_layers
    out = []
    for r in slots:
        n, gl = len(prompts[r]), int(gen_len[r])
        first, end = sp - n, sp + gl  # the row's slots of the cache
        layers, n_full, n_ring = [], 0, 0
        for kind in pattern:
            if kind == "F":
                at = np.arange(first, end)
                kv = jnp.stack(
                    [cache.k[n_full, r, first:end],
                     cache.v[n_full, r, first:end]], axis=1)
                n_full += 1
            else:
                ring = cache.wk.shape[2]
                at = np.arange(max(first, end - ring), end)
                kv = jnp.stack(
                    [cache.wk[n_ring, r, at % ring],
                     cache.wv[n_ring, r, at % ring]], axis=1)
                n_ring += 1
            layers.append((at - first, kv))
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl], layers))
    return out


def rows_readings(layers, ref_rows):
    """Two numbers over the layers of one sequence, each the largest |R -
    R_ref|_F / |R_ref|_F of a layer's kept K or V.  `rows_rel_err_unrouted`:
    layer 0, whose input no routed expert has touched (embedding, norm and
    projections alone), where no flipped choice adds to the arithmetic's
    own error, so it reads the precision of the projections and of what the
    ring keeps.  `rows_rel_err_max`: over every layer, ring or cache."""
    def norm(x):
        return np.sqrt(np.square(np.asarray(x, np.float64)).sum((0, 2, 3)))

    err = []
    for (at, kv), ref in zip(layers, np.asarray(ref_rows, np.float32)):
        got = np.asarray(jnp.asarray(kv, jnp.float32))
        want = ref[at]
        err.append((norm(got - want) / (
            norm(want) + np.finfo(np.float32).tiny)).max())
    return {
        "rows_rel_err_unrouted": float(err[0]),
        "rows_rel_err_max": float(max(err)),
    }


def rows_problems(readings, tol):
    """What of `rows_readings` lies above `tol`, as text."""
    return [
        f"{name} {readings[name]:.3g} above {tol[name]}"
        for name in ("rows_rel_err_unrouted", "rows_rel_err_max")
        if not readings[name] <= tol[name]
    ]


def check_generator(params, cfg, tokens):
    """(`rows_readings` of what the generator's own program left in its
    rings and its cache — the largest over the compared slots — beside the
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
        want, ref_rows = _next_token_logprobs(
            params, cfg, _padded(seq, longest))
        for k, v in rows_readings(layers, ref_rows[:, :n]).items():
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
    """-> (log-probs [T - 1], every layer's roped K and V [L, T, 2, n_kv,
    d])."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, rows = _hidden_and_rows(params, cfg, tokens, fault, lower)
        x = x[:-1]
        head = params["lm_head"]
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
        return np.asarray(sum(tl_all) - lse, np.float32), rows

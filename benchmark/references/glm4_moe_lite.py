"""Plain reference of the GLM-4.7-Flash decoder (zai-org/GLM-4.7-Flash,
`model_type: glm4_moe_lite`, the deepseek_v3 block at other numbers): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, no cache, no absorbed form, no kernels, no packing, no sorting of
tokens by expert, under `jax.default_matmul_precision("highest")`.

Per layer, pre-norm, RMSNorm eps `rms_norm_eps` with a plain weight:
y = x + Attn(norm(x)), z = y + MLP(norm(y)); a final norm, an untied head.

  * Latent attention (MLA), H heads: c_q = norm(x W_qa); [q_nope | q_pe]_h
    = c_q W_qb; [c_kv | k_pe] = x W_kva, c_kv = norm(c_kv); k_nope_h = c_kv
    W_kb,h and v_h = c_kv W_vb,h; rotary embedding (rotate-half, theta
    `rope_theta`, every one of the `qk_rope_head_dim` columns) on q_pe per
    head and on the ONE k_pe all heads share; s_h = (q_nope . k_nope + q_pe
    . k_pe) / sqrt(nope + rope), causal softmax, o_h = sum p v_h; Attn =
    concat_h(o_h) W_o.  No biases.  Always the MATERIALISED form: every
    head's keys and values are built.
  * Dense MLP (the first `first_k_dense` layers): SwiGLU.
  * Sparse MLP (the others): s = sigmoid(x W_r) over ALL the router's
    outputs; chosen = top-k of s + b (b: `router_bias`, HF
    `e_score_correction_bias`; one group, no group limit); w = s[chosen],
    w <- w / (sum w + 1e-20) (`norm_topk_prob`), w <- routed_scaling_factor
    w; MLP(x) = sum_i w_i E_i(x) + E_shared(x), SwiGLU experts, the shared
    one UNGATED.

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike:
  * The rank's share.  `cfg.n_experts` experts are HELD of the router's
    `cfg.router_width`, numbers [expert_offset, expert_offset + n_experts).
    The router scores and ranks all of them; the layer's output is the
    held experts' part of the weighted sum plus the shared expert.  What
    the absent experts would add is left out and nothing stands in for it.
  * The vocabulary is the slice the head holds: log-probabilities are over
    the slice.
  * The multi-token-prediction layer (`num_nextn_predict_layers`) is not
    modelled: it enters no next-token logit.
  * Conventions where the published config is silent (the configuration's
    `assumed`): the rope columns are in the PROGRAM'S order (halves; the HF
    converter permutes interleaved pairs into it), the two latent norms
    take `rms_norm_eps`, b is stored like every weight (bfloat16).

It reads the ENGINE'S weights (bf16, stacked under "blocks": the scanned
sparse layers' leaves [L - K, ...] under their own names, the K leading
dense layers' [K, ...] under `dense_*`) and upcasts them, so a difference
from the system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  Besides the log-probabilities, `check_generator` builds a
`GeneratorEngine` over the same weights and mesh, runs ITS static decode
program at the cell's 64 slots over prompts cut from the sequence (the
program's own sampler, cache, kernel and types; the cache it leaves is one
more output), and holds the rows that program left in its latent cache to
the (c_kv, roped k_pe) this reference computes over the tokens it sampled:
the comparison that refuses a cache, weights or activations kept a
precision lower.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rotate_half

_TOL = files.load_json("configs", "glm-4.7-flash-l7-e8.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `rows_readings` (`check_generator`), the chip's and the CPU's.
ROWS_TOLERANCE = dict(_TOL["rows"])
ROWS_TOLERANCE_FP32 = dict(_TOL["fp32"]["rows"])

# `lower="lower"` computes what the tolerance has to refuse: the router's
# scores rounded to bfloat16 and the latent rows (c_kv and the roped k_pe,
# what the cache keeps) rounded to 8 bits (e4m3), each a precision below
# what the configuration states.  One alone: "lower:router", "lower:cache".
LOWER_PRECISION = "lower"
_LOWER = {"router": (8, 7), "cache": (4, 3)}  # (exponent, mantissa) bits
# `check_generator`'s call of the static decode program: the cell's 64
# slots in one wave and at most its 1,024 new tokens, 8/9 of the sequence's
# length (1,024 of the cell's 128 + 1,024); the prompts are the sequence's
# first tokens, their lengths spread evenly from 3/4 to 5/4 of the rest (96
# to 160 in the cell: the traffic's), so the program has the timed one's
# shapes.  The first and the last slot are compared.
CHECK_SLOTS = 64
CHECK_NEW = 1024
_DENSE = "dense_"  # the engine's names of the leading dense layers' leaves


def _lower(x, lower, part):
    """x rounded as `lower` says for `part`, in float32.  Through
    `reduce_precision`: XLA drops a cast there and back on the TPU."""
    if lower is None:
        return x
    _, _, only = lower.partition(":")
    if only and only != part:
        return x
    return jax.lax.reduce_precision(x, *_LOWER[part])


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_weights(blocks, l, cfg):
    """Layer l's leaves under their own names, fp32 (the expert stacks
    stay as they are: `_moe` upcasts one expert at a time)."""
    k = cfg.first_k_dense
    if l < k:
        names = {n[len(_DENSE):]: n for n in blocks if n.startswith(_DENSE)}
        i = l
    else:
        names = {n: n for n in blocks if not n.startswith(_DENSE)}
        i = l - k
    return {
        ours: blocks[theirs][i] if blocks[theirs].ndim == 4
        else blocks[theirs][i].astype(jnp.float32)
        for ours, theirs in names.items()
    }


def _attention(h, w, cfg, lower=None):
    """Latent attention over one sequence, materialised.  h: [T, D] normed
    input -> ([T, D], the rows a cache keeps [T, c + rope])."""
    t = h.shape[0]
    hq, nope, rope = cfg.n_q_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c, vd = cfg.kv_lora_rank, cfg.v_head_dim
    c_q = _rms_norm(h @ w["wq_a"], w["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ w["wq_b"]).reshape(t, hq, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = h @ w["wkv_a"]
    c_kv = _rms_norm(kv[:, :c], w["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = kv[:, None, c:]  # [T, 1, rope]: one for all heads
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, rope]
    q_pe = q_pe * jnp.cos(ang) + _rotate_half(q_pe) * jnp.sin(ang)
    k_pe = k_pe * jnp.cos(ang) + _rotate_half(k_pe) * jnp.sin(ang)
    c_kv, k_pe = _lower(c_kv, lower, "cache"), _lower(k_pe, lower, "cache")
    k_nope = (c_kv @ w["wk_b"]).reshape(t, hq, nope)
    v = (c_kv @ w["wv_b"]).reshape(t, hq, vd)
    scores = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        + jnp.einsum("qhd,kd->hqk", q_pe, k_pe[:, 0])
    ) * (nope + rope) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    rows = jnp.concatenate([c_kv, k_pe[:, 0]], axis=-1)
    return attn.reshape(t, hq * vd) @ w["wo"], rows


def _route(h, w, cfg, lower=None):
    """[T, router_width] router weights: a token's chosen experts' sigmoid
    scores, renormalised and scaled, in their columns; zero elsewhere."""
    scores = _lower(jax.nn.sigmoid(h @ w["router"]), lower, "router")
    _, top_i = jax.lax.top_k(scores + w["router_bias"], cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.moe_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.moe_routed_scale
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)


def _moe(h, w, cfg, lower=None):
    """The held experts' part of the routed sum, one expert at a time,
    plus the ungated shared expert."""
    gates = _route(h, w, cfg, lower)
    held = gates[:, cfg.expert_offset: cfg.expert_offset + cfg.n_experts]

    def one(acc, xs):
        g, u, d, wt = xs
        f32 = jnp.float32
        y = (jax.nn.silu(h @ g.astype(f32)) * (h @ u.astype(f32))
             ) @ d.astype(f32)
        return acc + wt[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (w["wg"], w["wu"], w["wd"], held.T))
    return out + (jax.nn.silu(h @ w["ws_g"]) * (h @ w["ws_u"])) @ w["ws_d"]


def _layer(x, blocks, l, cfg, lower=None):
    """Decoder layer l (a Python int: the kind of layer is static) over one
    sequence.  x: [T, D] fp32 -> (x, the rows a cache keeps)."""
    w = _layer_weights(blocks, l, cfg)
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    attn, rows = _attention(h, w, cfg, lower)
    x = x + attn
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    if l < cfg.first_k_dense:
        return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"], rows
    return x + _moe(h, w, cfg, lower), rows


def _hidden_and_rows(params, cfg, tokens, lower=None):
    """-> ([T, D] fp32 hidden states after the final norm, every layer's
    rows [L, T, c + rope])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    rows = []
    for l in range(cfg.n_layers):
        x, r = layer(x, params["blocks"], l, cfg, lower)
        rows.append(r)
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, jnp.stack(rows)


def final_hidden(params, cfg, tokens, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_rows(params, cfg, tokens, lower)[0]


def logits(params, cfg, tokens, lower=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(params, cfg, jnp.asarray(tokens, jnp.int32), lower)
        return x @ params["lm_head"].astype(jnp.float32)


def next_token_logprobs(params, cfg, tokens, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the generator's static program leaves in its latent cache (the
    reference proper only: `lower` computes a control).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; attention is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    out, _ = _next_token_logprobs(params, cfg, padded, lower)
    print(f"[benchmark] glm4_moe_lite reference, {n} tokens, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None:  # a control: nothing of the system's is checked
        return out
    readings, problems = check_generator(params, cfg, tokens)
    print(f"[benchmark] glm4_moe_lite generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# The rows the generator's static program leaves, against (c_kv, roped k_pe)
# --------------------------------------------------------------------------

def _engine(params, cfg):
    """A `GeneratorEngine` over `params` as they lie (no copy), on their
    own mesh, built as a worker builds the timed one: what the cache is
    made of, the kernel and the types are the engine's to choose.  Built
    anew for every call and dropped with its compiled program, so nothing
    of the check stays on the device inside the window."""
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
    CHECK_SLOTS prompts cut from `tokens` -> for each slot of `slots`
    (its tokens, prompt and sampled ones; the log-probs the program
    returned for the sampled ones; the rows the program left in its
    latent cache for them all, [L, T, c + rope], in the cache's type)."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.packing import bucket_len

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) * 8 // 9)
    rest = len(tokens) - n_new
    lens = np.linspace(max(1, rest * 3 // 4), rest * 5 // 4, CHECK_SLOTS)
    prompts = [tokens[: int(n)] for n in lens]
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(38), with_cache=True)
    sp = bucket_len(max(len(p) for p in prompts))
    out = []
    for r in slots:
        n, gl = len(prompts[r]), int(gen_len[r])
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl],
            cache.latent[:, r, sp - n: sp + gl],
        ))
    return out


def rows_readings(rows, ref_rows, cfg):
    """Two numbers over the layers of one sequence, each the largest |R -
    R_ref|_F / |R_ref|_F of a layer's rows.  `rows_rel_err_unrouted`: over
    the layers whose input no routed expert has touched (the leading dense
    layers and the first sparse one: embedding, attention and dense MLPs
    alone), where no flipped choice adds to the arithmetic's own error, so
    it reads the precision of the projections and of what the cache keeps.
    `rows_rel_err_max`: over every layer."""
    def norm(x):
        return np.sqrt(np.square(np.asarray(x, np.float64)).sum((-2, -1)))

    r = np.asarray(jnp.asarray(rows, jnp.float32))
    ref = np.asarray(ref_rows, np.float32)
    err = norm(r - ref) / (norm(ref) + np.finfo(np.float32).tiny)
    return {
        "rows_rel_err_unrouted": float(err[: cfg.first_k_dense + 1].max()),
        "rows_rel_err_max": float(err.max()),
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
    cache — the largest over the compared slots — beside the mean and the
    largest |log-prob(program) - log-prob(reference)| over the tokens it
    sampled, which are reported and not limited here: `checks.py` limits
    the timed rollouts'; `rows_problems` under the backend's limits)."""
    readings, diffs = {}, []
    for seq, logps, rows in generator_rollouts(params, cfg, tokens):
        n = len(seq)
        padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
        padded[:n] = seq
        want, ref_rows = _next_token_logprobs(params, cfg, padded)
        for k, v in rows_readings(rows, ref_rows[:, :n], cfg).items():
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


def _next_token_logprobs(params, cfg, tokens, lower=None):
    """-> (log-probs [T - 1], every layer's rows [L, T, c + rope])."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, rows = _hidden_and_rows(params, cfg, tokens, lower)
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

"""Plain reference of the Nemotron-H decoder (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B, `model_type: nemotron_h`): the forward pass in straightforward
`jax.numpy` and float32, one layer at a time, the Mamba recurrence TOKEN BY
TOKEN under `lax.scan` (no chunks), no cache, no kernels, no packing, no
sorting of tokens by expert, under `jax.default_matmul_precision("highest")`.

Follows the published description (HF `modeling_nemotron_h.py`).  Every
layer l is ONE residual branch, x <- x + f_l(rmsnorm(x; w_l, eps)), plain
weight, f_l by `hybrid_override_pattern[l]`; a final norm, an untied head;
no biases but the conv's.

  * "M", Mamba-2: [z | xBC | dt] = in_proj(u); xBC = silu(causal depthwise
    conv, kernel K, WITH bias); x [T, H, P], B and C [T, G, N], head h of
    group h // (H / G); dt = softplus(dt + dt_bias), A = -exp(A_log); per
    head, with S [P, N]:
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    then y * silu(z), RMSNorm over each GROUP of d_inner / G channels times
    a weight (gate first, then norm), out_proj.
  * "E", experts alone: s = sigmoid(x W_r) over ALL the router's outputs;
    chosen = top-k of s + b (b: `router_bias`, HF `e_score_correction_bias`;
    one group, no group limit); w = s[chosen] / (sum + 1e-20) x
    routed_scaling_factor; sum_i w_i down_i(relu(up_i(x))^2) plus one shared
    expert of the same form, added ungated.
  * "*", attention alone: q, k, v without bias, causal softmax at
    head_dim ** -0.5, o_proj; NO positional embedding (the configuration's
    `assumed.attention_positions`).

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike:
  * The rank's share.  `cfg.n_experts` experts are HELD of the router's
    `cfg.router_width`, numbers [expert_offset, expert_offset + n_experts).
    The router scores and ranks all of them; the layer's output is the
    held experts' part of the weighted sum plus the shared expert.  What
    the absent experts would add is left out and nothing stands in for it.
  * The vocabulary is the slice the head holds: log-probabilities are over
    the slice.

It reads the ENGINE'S weights (bf16, stacked under "blocks": `ln1` [L, D],
each kind's leaves over its own layers) and upcasts them, so a difference
from the system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  Log-probability bounds cannot tell a bf16 state from the
system's own bf16 activations (PERF.md section 6, PR 32 and PR 38), so the
limit that refuses a lower precision is on the STATE: `check_generator`
builds a `GeneratorEngine` over the same weights, runs ITS static decode
program at the cell's 64 slots (the program's own sampler, cache and
types; the cache it leaves is one more output) and holds the Mamba state
and conv tail that program left to the S and the conv inputs this
reference ends on over the tokens the program sampled.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk
from benchmark.references.qwen3_next import state_problems, state_readings

_TOL = files.load_json("configs", "nemotron-3-nano-30b-a3b-l9-e16.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `state_readings` (`check_generator`), the chip's and the CPU's.
STATE_TOLERANCE = dict(_TOL["state"])
STATE_TOLERANCE_FP32 = dict(_TOL["fp32"]["state"])

# `lower="bfloat16"` computes what the tolerance has to refuse: the state
# S, dt and the decay exp(dt A), and the router's logits rounded to that
# type at every step.  None: the reference proper.  One of the three
# alone: "bfloat16:state", "bfloat16:gates", "bfloat16:router".
LOWER_PRECISION = "bfloat16"
# `fault=` computes the layer with ONE part of its mathematics wrong (the
# tests hold each outside the fp32 bound): see `FAULTS`.
FAULTS = (
    "no_d_skip", "norm_over_all_channels", "no_conv_bias", "no_routed_scale",
    "bias_in_weights", "silu_gated_experts",
)
# `check_generator`'s call of the static decode program: the cell's 64
# slots in one wave and at most its 512 new tokens, 4/5 of the sequence's
# length (512 of the cell's 128 + 512); the prompts are the sequence's
# first tokens, their lengths spread evenly from 3/4 to 5/4 of the rest (96
# to 160 in the cell: the traffic's), so the program has the timed one's
# shapes.  The first and the last slot are compared.
CHECK_SLOTS = 64
CHECK_NEW = 512


# The engine's names of each layer kind's leaves (stacked over the layers
# of that kind); `ln1` is every layer's.
_KIND_LEAVES = {
    "M": ("ssm_in", "ssm_conv", "ssm_conv_b", "ssm_A_log", "ssm_D",
          "ssm_dt_bias", "ssm_norm", "ssm_out"),
    "E": ("router", "router_bias", "wu", "wd", "ws_u", "ws_d"),
    "*": ("wq", "wk", "wv", "wo"),
}


def _lower(x, lower, part):
    """x rounded to the type `lower` names, in float32.  Through
    `reduce_precision`: XLA drops a cast there and back on the TPU."""
    if lower is None:
        return x
    dtype, _, only = lower.partition(":")
    if only and only != part:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_weights(blocks, l, cfg):
    """Layer l's leaves under their own names, fp32 (the expert stacks stay
    as they are: `_moe` upcasts one expert at a time)."""
    kind = cfg.layer_pattern[l]
    i = cfg.layer_pattern[:l].count(kind)
    out = {"ln1": blocks["ln1"][l].astype(jnp.float32)}
    for name in _KIND_LEAVES[kind]:
        w = blocks[name]
        out[name] = w[i] if w.ndim == 4 else w[i].astype(jnp.float32)
    return out


def _mamba(h, w, cfg, lower=None, n_valid=None, fault=None):
    """One Mamba-2 layer over one sequence, token by token.  h: [T, D]
    normed input -> ([T, D], S after `n_valid` tokens [H, P, N], the conv's
    last K - 1 inputs there [K - 1, C])."""
    t = h.shape[0]
    hh, p, g, n = (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups,
                   cfg.ssm_state_dim)
    di, kk = cfg.ssm_inner_dim, cfg.ssm_conv_kernel
    n_valid = t if n_valid is None else n_valid
    zxbcdt = h @ w["ssm_in"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di: di + cfg.ssm_conv_dim],
                  zxbcdt[:, di + cfg.ssm_conv_dim:])
    padded = jnp.pad(xbc, ((kk - 1, 0), (0, 0)))
    conv = sum(padded[j: j + t] * w["ssm_conv"][j] for j in range(kk))
    if fault != "no_conv_bias":
        conv = conv + w["ssm_conv_b"]
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(t, hh, p)
    bm = jnp.repeat(conv[:, di: di + g * n].reshape(t, g, n), hh // g, axis=1)
    cm = jnp.repeat(conv[:, di + g * n:].reshape(t, g, n), hh // g, axis=1)
    dt = _lower(jax.nn.softplus(dt + w["ssm_dt_bias"]), lower, "gates")
    decay = _lower(jnp.exp(dt * -jnp.exp(w["ssm_A_log"])), lower, "gates")

    def step(s, xs):
        i, x_t, b_t, c_t, dt_t, da_t = xs
        new = da_t[:, None, None] * s + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        new = _lower(new, lower, "state")
        y_t = jnp.einsum("hpn,hn->hp", new, c_t)
        return jnp.where(i < n_valid, new, s), y_t

    s, y = jax.lax.scan(
        step, jnp.zeros((hh, p, n), jnp.float32),
        (jnp.arange(t), x, bm, cm, dt, decay))
    if fault != "no_d_skip":
        y = y + w["ssm_D"][:, None] * x
    y = y.reshape(t, di) * jax.nn.silu(z)
    groups = 1 if fault == "norm_over_all_channels" else g
    y = _rms_norm(
        y.reshape(t, groups, di // groups), 1.0, cfg.rms_norm_eps
    ).reshape(t, di) * w["ssm_norm"]
    tail = jax.lax.dynamic_slice_in_dim(padded, n_valid, kk - 1, axis=0)
    return y @ w["ssm_out"], s, tail


def _attention(h, w, cfg):
    """Grouped-query attention over one sequence, no positions."""
    t = h.shape[0]
    hq, hk, d = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ w["wq"]).reshape(t, hq, d)
    k = jnp.repeat((h @ w["wk"]).reshape(t, hk, d), hq // hk, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(t, hk, d), hq // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d**-0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(t, hq * d) @ w["wo"]


def _route(h, w, cfg, lower=None, fault=None):
    """[T, router_width] router weights: a token's chosen experts' sigmoid
    scores, renormalised and scaled, in their columns; zero elsewhere."""
    scores = jax.nn.sigmoid(_lower(h @ w["router"], lower, "router"))
    biased = scores + w["router_bias"]
    _, top_i = jax.lax.top_k(biased, cfg.n_experts_per_tok)
    top_w = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, top_i, axis=-1)
    if cfg.moe_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        top_w = top_w * cfg.moe_routed_scale
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)


def _moe(h, w, cfg, lower=None, fault=None):
    """The held experts' part of the routed sum, one expert at a time, plus
    the ungated shared expert: down(relu(up(x))^2)."""
    gates = _route(h, w, cfg, lower, fault)
    held = gates[:, cfg.expert_offset: cfg.expert_offset + cfg.n_experts]
    f32 = jnp.float32

    def act(u):
        if fault == "silu_gated_experts":  # a SwiGLU with gate = up
            return jax.nn.silu(u) * u
        return jnp.square(jax.nn.relu(u))

    def one(acc, xs):
        u, d, wt = xs
        return acc + wt[:, None] * (act(h @ u.astype(f32)) @ d.astype(f32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (w["wu"], w["wd"], held.T))
    return out + act(h @ w["ws_u"]) @ w["ws_d"]


def _layer(x, blocks, l, cfg, lower=None, n_valid=None, fault=None):
    """Decoder layer l (a Python int: the kind of layer is static) over one
    sequence.  x: [T, D] fp32 -> (x, (S, conv tail) after `n_valid` tokens
    for a Mamba layer, () otherwise)."""
    w = _layer_weights(blocks, l, cfg)
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    kind = cfg.layer_pattern[l]
    if kind == "M":
        y, s, tail = _mamba(h, w, cfg, lower, n_valid, fault)
        return x + y, (s, tail)
    if kind == "*":
        return x + _attention(h, w, cfg), ()
    return x + _moe(h, w, cfg, lower, fault), ()


def _hidden_and_state(params, cfg, tokens, lower=None, n_valid=None, fault=None):
    """-> ([T, D] fp32 hidden states after the final norm, the Mamba
    layers' S after `n_valid` tokens [n_ssm, H, P, N] and their convs' last
    inputs there [n_ssm, K - 1, C])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 6))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    left = []
    for l in range(cfg.n_layers):
        x, here = layer(x, params["blocks"], l, cfg, lower, n_valid, fault)
        left += [here] if here else []
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, tuple(jnp.stack(parts) for parts in zip(*left))


def final_hidden(params, cfg, tokens, lower=None, fault=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_state(params, cfg, tokens, lower, fault=fault)[0]


def logits(params, cfg, tokens, lower=None, fault=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(
            params, cfg, jnp.asarray(tokens, jnp.int32), lower, fault)
        return x @ params["lm_head"].astype(jnp.float32)


def _padded(tokens):
    """The sequence padded at its END to a multiple of PAD_TO, so that a
    few compiled shapes serve every seed; every mixer is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    return padded


def next_token_logprobs(params, cfg, tokens, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the generator's static program leaves in its cache (the reference
    proper only: `lower` computes a control)."""
    n = len(tokens)
    out, _, _ = _next_token_logprobs(params, cfg, _padded(tokens), lower, n)
    print(f"[benchmark] nemotron_h reference, {n} tokens, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None:  # a control: nothing of the system's is checked
        return out
    readings, problems = check_generator(params, cfg, tokens)
    print(f"[benchmark] nemotron_h generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# The state the generator's static program leaves, against the reference's S
# --------------------------------------------------------------------------

def generator_rollouts(params, cfg, tokens, slots=(0, CHECK_SLOTS - 1)):
    """The static decode program of a `GeneratorEngine`, once, over
    CHECK_SLOTS prompts cut from `tokens` -> for each slot of `slots` (its
    tokens, prompt and sampled ones; the log-probs the program returned
    for the sampled ones; the state [n_ssm, H, P, N] and conv tail [n_ssm,
    K - 1, C] the program left in its cache for the slot, which has then
    consumed every one of those tokens)."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from benchmark.references.glm4_moe_lite import _engine

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) * 4 // 5)
    rest = len(tokens) - n_new
    lens = np.linspace(max(1, rest * 3 // 4), rest * 5 // 4, CHECK_SLOTS)
    prompts = [tokens[: int(n)] for n in lens]
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(40), with_cache=True)
    out = []
    for r in slots:
        gl = int(gen_len[r])
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl],
            cache.state[:, r], cache.conv[:, r],
        ))
    return out


def check_generator(params, cfg, tokens):
    """(`state_readings` of what the generator's own program left in its
    cache — the worst over the compared slots — beside the mean and the
    largest |log-prob(program) - log-prob(reference)| over the tokens it
    sampled, which are reported and not limited here: `checks.py` limits
    the timed rollouts'; `state_problems` under the backend's limits)."""
    readings, diffs = {}, []
    for seq, logps, state, conv in generator_rollouts(params, cfg, tokens):
        n = len(seq)
        want, ref_state, ref_conv = _next_token_logprobs(
            params, cfg, _padded(seq), None, n)
        for k, v in state_readings(state, conv, ref_state, ref_conv).items():
            worst = min if k == "state_bf16_residual_min" else max
            readings[k] = worst(v, readings.get(k, v))
        first = n - len(logps)  # position t scores token t + 1
        diffs.append(np.abs(logps - want[first - 1: n - 1]))
    diffs = np.concatenate(diffs)
    readings.update(
        logprob_mean_abs=float(diffs.mean()), logprob_max_abs=float(diffs.max()),
        n_tokens=int(diffs.size))
    cpu = jax.default_backend() == "cpu"
    return readings, state_problems(
        readings, STATE_TOLERANCE_FP32 if cpu else STATE_TOLERANCE)


def _next_token_logprobs(params, cfg, tokens, lower=None, n_valid=None):
    """-> (log-probs [T - 1], and of `_hidden_and_state`: S and the conv
    inputs after `n_valid` tokens)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, (state, tail) = _hidden_and_state(
            params, cfg, tokens, lower, n_valid)
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
        return np.asarray(sum(tl_all) - lse, np.float32), state, tail

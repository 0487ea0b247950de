"""Plain reference of the Qwen3-Next decoder (Qwen/Qwen3-Next-80B-A3B): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, no cache, no chunks, no kernels, no packing, no sorting of tokens by
expert, under `jax.default_matmul_precision("highest")` (on a TPU an fp32
matmul is otherwise done in bf16 passes).

Follows the public description (HF `modeling_qwen3_next.py`).  Every
RMSNorm but one scales by (1 + w), eps 1e-6.  Layer i is full attention
when (i + 1) % full_attention_interval == 0, else Gated DeltaNet; every
layer's MLP is the mixture of experts below.

  * Gated attention: q_proj gives query and gate per head; per-head
    RMSNorm (1 + w) on q and k over head_dim; rotary embedding
    (rotate-half) on the first `partial_rotary_factor * head_dim`
    dimensions only; causal softmax attention scaled by head_dim ** -0.5;
    o_proj(attn * sigmoid(gate)).
  * Gated DeltaNet: projections to q, k (key heads), v, z (value heads)
    and b, a (one per value head); a causal depthwise conv of width 4
    without bias, then SiLU, over the channels of (q, k, v); beta =
    sigmoid(b), g = -exp(A_log) * softplus(a + dt_bias); q and k L2-
    normalised per head (eps 1e-6), q scaled by d_k ** -0.5, each key head
    serving n_v / n_k value heads; per value head the recurrence, TOKEN BY
    TOKEN under `lax.scan`, with the state S [d_k, d_v]:
        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
        o_t = S^T q_t
    then out_proj(w * rmsnorm(o_t) * silu(z_t)), that norm per head over
    d_v with a plain weight (not 1 + w).
  * MoE: router = linear without bias, softmax in fp32 over ALL router
    outputs, the top `num_experts_per_tok` renormalised to sum to one
    (`norm_topk_prob`), SwiGLU experts; plus sigmoid(shared_expert_gate x)
    * shared_expert(x).

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike:
  * The rank's share.  `cfg.n_experts` experts are HELD of the router's
    `cfg.router_width`, numbers [expert_offset, expert_offset +
    n_experts).  The router scores and ranks all of them; the layer's
    output is the held experts' part of the weighted sum plus the shared
    expert.  What the absent experts would add is left out and nothing
    stands in for it.  With n_experts == router_width this is the
    published layer.
  * The vocabulary is the slice the head holds (`lm_head.shape[1]` rows):
    log-probabilities are over the slice.
  * The model card mentions a multi-token-prediction module; the published
    config has no key for it and it is not modelled.

The routing is the reference's OWN, in fp32 from its own fp32 layer input.
It reads the ENGINE'S weights (bf16, stacked under "blocks": per-layer
leaves [L, ...], the full-attention layers' [L / interval, ...], the
`la_*` leaves of the linear layers [L - L / interval, ...]) and upcasts
them, so a difference from the system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  The log-probability limits sit about three times above the
chip's reading; they cannot tell a bf16 state from the system's own bf16
activations (PERF.md section 6), so the limit that refuses this reference
with S, the gates and the router's logits rounded to bf16
(`LOWER_PRECISION`) is on the STATE: `check_state` below drives the two
functions the static decode program is made of and compares what they
leave in the cache with the S this reference ends on.
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rotate_half

_TOL = files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `state_readings` (`check_state`), the chip's and the CPU's.
STATE_TOLERANCE = dict(_TOL["state"])
STATE_TOLERANCE_FP32 = dict(_TOL["fp32"]["state"])

# `lower="bfloat16"` computes what the tolerance has to refuse: the
# recurrent state, the gates (beta, g) and the router's logits rounded to
# that type at every step.  None: the reference proper.  One of the three
# alone: "bfloat16:state", "bfloat16:gates", "bfloat16:router".
LOWER_PRECISION = "bfloat16"
# Of a sequence's tokens `check_state` decodes the last half, at most this
# many (the cell's max_new_tokens); the rest is the prompt it prefills.
DECODE_TOKENS = 512


def _lower(x, lower, part):
    """x rounded to the type `lower` names, in float32.  Through
    `reduce_precision`: a cast there and back is a pair of converts that
    XLA may drop (`xla_allow_excess_precision`), and on the TPU does."""
    if lower is None:
        return x
    dtype, _, only = lower.partition(":")
    if only and only != part:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rms_norm(x, w, eps, offset):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    w = w.astype(jnp.float32)
    return xf * jax.lax.rsqrt(var + eps) * (1.0 + w if offset else w)


def _w(blocks, i, name):
    return jax.lax.dynamic_index_in_dim(
        blocks[name], i, 0, keepdims=False
    ).astype(jnp.float32)


def _attention(h, blocks, p, cfg):
    """Gated softmax attention of period p's full layer over one sequence.
    h: [T, D] normed input -> [T, D]."""
    t = h.shape[0]
    hq, hkv, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ _w(blocks, p, "wq")).reshape(t, hq, hd)
    gate = h @ _w(blocks, p, "wqg")
    k = (h @ _w(blocks, p, "wk")).reshape(t, hkv, hd)
    v = (h @ _w(blocks, p, "wv")).reshape(t, hkv, hd)
    q = _rms_norm(q, _w(blocks, p, "q_norm"), cfg.rms_norm_eps, True)
    k = _rms_norm(k, _w(blocks, p, "k_norm"), cfg.rms_norm_eps, True)
    r = cfg.rotary_dim or hd
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    )
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]  # [T, 1, r]

    def rope(x):
        xr = x[..., :r]
        xr = xr * jnp.cos(ang) + _rotate_half(xr) * jnp.sin(ang)
        return jnp.concatenate([xr, x[..., r:]], axis=-1)

    q, k = rope(q), rope(k)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return (attn.reshape(t, hq * hd) * jax.nn.sigmoid(gate)) @ _w(
        blocks, p, "wo")


def _delta_net(h, blocks, i, cfg, lower=None, n_valid=None):
    """Gated DeltaNet of linear layer i over one sequence, token by token.
    h: [T, D] normed input -> ([T, D], S [h_v, d_k, d_v] after token
    `n_valid` - 1 (None: the last), the conv's inputs at that token and
    the K - 2 before it [K - 1, C])."""
    t = h.shape[0]
    n_valid = t if n_valid is None else n_valid
    hk, hv = cfg.linear_n_k_heads, cfg.linear_n_v_heads
    dk, dv, kd = cfg.linear_k_head_dim, cfg.linear_v_head_dim, cfg.linear_key_dim
    kk = cfg.linear_conv_kernel
    qkv = h @ _w(blocks, i, "la_wqkv")  # [T, C]
    z = (h @ _w(blocks, i, "la_wz")).reshape(t, hv, dv)
    ba = h @ _w(blocks, i, "la_wba")
    taps = _w(blocks, i, "la_conv")  # [K, C], oldest first
    padded = jnp.pad(qkv, ((kk - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[j: j + t] for j in range(kk))
    conv = jax.nn.silu(conv)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2norm(conv[:, :kd].reshape(t, hk, dk)) * dk ** -0.5
    k = l2norm(conv[:, kd: 2 * kd].reshape(t, hk, dk))
    v = conv[:, 2 * kd:].reshape(t, hv, dv)
    q = jnp.repeat(q, hv // hk, axis=1)
    k = jnp.repeat(k, hv // hk, axis=1)
    beta = _lower(jax.nn.sigmoid(ba[:, :hv]), lower, "gates")
    g = _lower(
        -jnp.exp(_w(blocks, i, "la_A_log"))
        * jax.nn.softplus(ba[:, hv:] + _w(blocks, i, "la_dt_bias")),
        lower, "gates",
    )

    def step(carry, xs):  # state [hv, dk, dv]
        state, at_n = carry
        q_t, k_t, v_t, g_t, b_t, pos = xs
        state = state * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = _lower(state + k_t[:, :, None] * d[:, None, :], lower, "state")
        at_n = jnp.where(pos < n_valid, state, at_n)
        return (state, at_n), jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((hv, dk, dv), jnp.float32)
    (_, at_n), o = jax.lax.scan(
        step, (zero, zero), (q, k, v, g, beta, jnp.arange(t))
    )
    o = _rms_norm(o, _w(blocks, i, "la_norm"), cfg.rms_norm_eps, False)
    o = o * jax.nn.silu(z)
    tail = jax.lax.dynamic_slice_in_dim(padded, n_valid, kk - 1, axis=0)
    return o.reshape(t, hv * dv) @ _w(blocks, i, "la_wo"), at_n, tail


def _route(h, router, cfg, lower=None):
    """[T, router_width] router weights: a token's top-k softmax
    probabilities, renormalised where the config says so, in their
    experts' columns; zero elsewhere.  fp32."""
    probs = jax.nn.softmax(_lower(h @ router, lower, "router"), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)


def _moe(h, blocks, l, cfg, lower=None):
    """The held experts' part of the routed sum, one expert at a time,
    plus the gated shared expert."""
    gates = _route(h, _w(blocks, l, "router"), cfg, lower)
    held = gates[:, cfg.expert_offset: cfg.expert_offset + cfg.n_experts]
    wg, wu, wd = (
        jax.lax.dynamic_index_in_dim(blocks[n], l, 0, keepdims=False)
        for n in ("wg", "wu", "wd")
    )

    def one(acc, xs):
        g, u, d, w = xs
        f32 = jnp.float32
        y = (jax.nn.silu(h @ g.astype(f32)) * (h @ u.astype(f32))
             ) @ d.astype(f32)
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, held.T))
    if cfg.shared_expert_dim:
        shared = (
            jax.nn.silu(h @ _w(blocks, l, "ws_g")) * (h @ _w(blocks, l, "ws_u"))
        ) @ _w(blocks, l, "ws_d")
        out = out + jax.nn.sigmoid(h @ _w(blocks, l, "ws_gate")) * shared
    return out


def _layer(x, blocks, l, cfg, lower=None, n_valid=None):
    """Decoder layer l (a Python int: the kind of layer is static) over
    one sequence.  x: [T, D] fp32 -> (x, what a Gated DeltaNet layer's
    `_delta_net` leaves after `n_valid` tokens; () for an attention
    layer)."""
    n = cfg.full_attn_interval
    p, j = divmod(l, n)
    h = _rms_norm(x, _w(blocks, l, "ln1"), cfg.rms_norm_eps, True)
    left = ()
    if j == n - 1:
        x = x + _attention(h, blocks, p, cfg)
    else:
        y, *left = _delta_net(h, blocks, p * (n - 1) + j, cfg, lower, n_valid)
        x = x + y
    h = _rms_norm(x, _w(blocks, l, "ln2"), cfg.rms_norm_eps, True)
    return x + _moe(h, blocks, l, cfg, lower), tuple(left)


def _hidden_and_state(params, cfg, tokens, lower=None, n_valid=None):
    """-> ([T, D] fp32 hidden states after the final norm, the linear
    layers' S after `n_valid` tokens [n_linear, h_v, d_k, d_v] and their
    convs' last inputs there [n_linear, K - 1, C])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    left = []
    for l in range(cfg.n_layers):
        x, here = layer(x, params["blocks"], l, cfg, lower, n_valid)
        left += [here] if here else []
    x = _rms_norm(x, params["final_ln"], cfg.rms_norm_eps, True)
    return x, tuple(jnp.stack(parts) for parts in zip(*left))


def final_hidden(params, cfg, tokens, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_state(params, cfg, tokens, lower)[0]


def logits(params, cfg, tokens, lower=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(params, cfg, jnp.asarray(tokens, jnp.int32), lower)
        return x @ params["lm_head"].astype(jnp.float32)


def next_token_logprobs(params, cfg, tokens, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_state` refuses what the
    system's prefill and decode steps leave in the cache over the same
    tokens (the reference proper only: `lower` computes a control).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; every mixer is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    out, state, tail = _next_token_logprobs(params, cfg, padded, lower, n)
    print(f"[benchmark] qwen3_next reference, {n} tokens, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None:  # a control: nothing of the system's is checked
        return out
    readings, problems = check_state(params, cfg, tokens, state, tail)
    print(f"[benchmark] qwen3_next state check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# The state the static decode program leaves, against the reference's S
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 5, 6))
def _system_cache(params, cfg, prompt, prompt_len, new, s_total, in_place):
    """The generator's static program without its sampler: right-aligned
    prompts [B, sp] through `prefill` into the cache `init_kv_cache`
    allocates, then the given tokens `new` [B, n] one `decode_step` each.
    -> the cache's (state, conv)."""
    from areal_tpu.models import transformer as tfm

    sp = prompt.shape[1]
    seg = (jnp.arange(sp)[None] >= (sp - prompt_len)[:, None]).astype(jnp.int32)
    valid_from = sp - prompt_len
    cache = tfm.init_kv_cache(
        cfg, prompt.shape[0], s_total, dtype=params["embed"].dtype)
    _, cache = tfm.prefill(params, cfg, prompt, seg, cache)

    def step(cache, xs):
        t, tok = xs
        _, cache = tfm.decode_step(
            params, cfg, tok, prompt_len + t, cache, sp + t, valid_from,
            experts_in_place=in_place)
        return cache, None

    cache, _ = jax.lax.scan(step, cache, (jnp.arange(new.shape[1]), new.T))
    return cache.state, cache.conv


def system_state(params, cfg, tokens):
    """What the system's `prefill` over the first tokens and its
    `decode_step` over the last `min(T // 2, DECODE_TOKENS)` leave in the
    hybrid cache, in the system's own precision: (state [n_linear, h_v,
    d_k, d_v], conv [n_linear, K - 1, C])."""
    from areal_tpu.engines.packing import bucket_len
    from areal_tpu.models import transformer as tfm

    tokens = np.asarray(tokens, np.int32)
    n_new = min(len(tokens) // 2, DECODE_TOKENS)
    n_prompt = len(tokens) - n_new
    sp = bucket_len(n_prompt)
    prompt = np.zeros((1, sp), np.int32)
    prompt[0, sp - n_prompt:] = tokens[:n_prompt]
    state, conv = _system_cache(
        params, cfg, prompt, np.asarray([n_prompt], np.int32),
        tokens[None, n_prompt:], bucket_len(sp + n_new),
        tfm.expert_leaves_in_place(cfg, params["blocks"]))
    return state[:, 0], conv[:, 0]


def state_readings(state, conv, ref_state, ref_conv):
    """Three numbers over the Gated DeltaNet layers and value heads of one
    sequence.  `state_rel_err_max`: the largest |S - S_ref|_F / |S_ref|_F
    of a head.  `conv_rel_err_max`: the same of a layer's conv inputs.
    `state_bf16_residual_min`: the smallest |S - bf16(S)|_F / |S|_F of a
    head — what rounding S to bfloat16 would change.  A state computed and
    kept in float32 reads 1.2e-3 to 1.6e-3 whatever it holds; one kept in
    bfloat16 reads 0 exactly, however it was computed."""
    def norm(x, axes):
        return np.sqrt(np.square(np.asarray(x, np.float64)).sum(axes))

    s = jnp.asarray(state)
    rounded = np.asarray(s.astype(jnp.bfloat16).astype(jnp.float32))
    s, r = np.asarray(s, np.float32), np.asarray(ref_state, np.float32)
    c, rc = np.asarray(conv, np.float32), np.asarray(ref_conv, np.float32)
    tiny = np.finfo(np.float32).tiny
    return {
        "state_rel_err_max": float(
            (norm(s - r, (-2, -1)) / (norm(r, (-2, -1)) + tiny)).max()),
        "conv_rel_err_max": float(
            (norm(c - rc, (-2, -1)) / (norm(rc, (-2, -1)) + tiny)).max()),
        "state_bf16_residual_min": float(
            (norm(s - rounded, (-2, -1)) / (norm(s, (-2, -1)) + tiny)).min()),
    }


def state_problems(readings, tol):
    """What of `state_readings` lies outside `tol`, as text."""
    out = [
        f"{name} {readings[name]:.3g} above {tol[name]}"
        for name in ("state_rel_err_max", "conv_rel_err_max")
        if not readings[name] <= tol[name]
    ]
    name = "state_bf16_residual_min"
    if not readings[name] >= tol[name]:
        out.append(f"{name} {readings[name]:.3g} under {tol[name]}: the "
                   "recurrent state holds no more than bfloat16")
    return out


def check_state(params, cfg, tokens, ref_state, ref_conv):
    """(`state_readings` of `system_state` over `tokens` against the
    reference's, `state_problems` under the backend's limits)."""
    readings = state_readings(
        *system_state(params, cfg, tokens), ref_state, ref_conv)
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

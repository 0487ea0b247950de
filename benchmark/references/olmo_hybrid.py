"""Plain reference of the Olmo-Hybrid decoder (allenai/Olmo-Hybrid-7B): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, the recurrence TOKEN BY TOKEN under `lax.scan`, no cache, no chunks,
no kernels, no packing, under `jax.default_matmul_precision("highest")` (on
a TPU an fp32 matmul is otherwise done in bf16 passes).

`layer_types` gives every layer its mixer; a dense SwiGLU MLP stands behind
each; every RMSNorm scales by a plain weight, eps `rms_norm_eps`.

  * "linear_attention" — Flash Linear Attention's `GatedDeltaNet`, fixed by
    the config's keys: projections to q, k (h_k heads of d_k), v, the
    output gate z (h_v heads of d_v) and b, a (one per value head); a
    causal depthwise conv of width 4 without bias, then SiLU, over the
    channels of q | k | v; q and k L2-normalised per head (eps 1e-6), q
    scaled by d_k ** -0.5; per value head, with the state S [d_k, d_v]:
        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
        o_t = S^T q_t
    beta_t = 2 sigmoid(b_t) (`linear_allow_neg_eigval`: an eigenvalue of I
    - beta k k^T in (-1, 1)), g_t = -exp(A_log) softplus(a_t + dt_bias);
    then o_proj(w * rmsnorm(o_t) * silu(z_t)), that norm per head over d_v.
  * "full_attention" — q, k, v projections without bias, causal softmax
    attention scaled by head_dim ** -0.5, as many key as query heads.

THREE ASSUMPTIONS, the family's conventions (OLMo 2, Olmo 3) where the
config has no key (`benchmark/configs/olmo-hybrid-7b-l4-v8.json`,
`assumed`; each is one field of the program's ModelConfig and one fault
below):
  1. the norms sit on a branch's OUTPUT: h = x + RMSNorm(mixer(x)), y = h +
     RMSNorm(mlp(h)); the mixer and the MLP are fed the raw stream; one
     final norm before the head;
  2. the full layer norms q and k over the WHOLE projection (one [h * d]
     weight each, before the cut into heads), as olmoe does;
  3. `rope_parameters.rope_theta` null parameterises no rotary table: the
     full layers take no positions; order reaches the model through the
     conv and the recurrence of the linear layers.

Departure, forced by the cut to one chip (model-configs guide, section 4)
and made in the program and here alike: the vocabulary is the slice the
head holds (`lm_head.shape[1]` rows); log-probabilities are over the slice.
Nothing is cut inside a layer.

It reads the ENGINE'S weights (bf16, stacked under "blocks": per-layer
leaves [L, ...], the full layers' [L / 4, ...], the `la_*` leaves of the
linear layers [3 L / 4, ...]) and upcasts them, so a difference from the
system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons and readings).  The limit that refuses this reference with S
and the gates rounded to bfloat16 (`LOWER_PRECISION`) on the chip is on the
STATE: `check_state` drives the two functions the static decode program is
made of and compares what they leave in the cache with the S this
reference ends on (`references/qwen3_next.py`'s readings: the cache has the
same two populations).  `fault=` computes one of `FAULTS`, what the
tolerance has to refuse.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rotate_half
from benchmark.references.qwen3_next import (  # the hybrid cache's readings
    _lower,
    state_problems,
    state_readings,
    system_state,
)

_TOL = files.load_json("configs", "olmo-hybrid-7b-l4-v8.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `state_readings` (`check_state`), the chip's and the CPU's.
STATE_TOLERANCE = dict(_TOL["state"])
STATE_TOLERANCE_FP32 = dict(_TOL["fp32"]["state"])

# `lower="bfloat16"` computes what the tolerance has to refuse: the
# recurrent state and the gates (beta, g) rounded to that type at every
# step.  None: the reference proper.  One alone: "bfloat16:state",
# "bfloat16:gates".
LOWER_PRECISION = "bfloat16"
# `fault=`: one departure from the equations above, each of which the
# tolerance has to refuse (tests/test_olmo_hybrid.py).
FAULTS = (
    "beta_in_0_1",  # beta = sigmoid(b): no negative eigenvalue
    "norm_on_input",  # x + f(norm(x)): assumption 1 undone
    "qk_norm_per_head",  # assumption 2: each head normed on its own
    "no_qk_norm",  # assumption 2: no norm on q and k
    "rope",  # assumption 3: rotary positions at theta 10,000
    "no_output_gate",  # w * rmsnorm(o) without silu(z)
    "no_conv_silu",  # the conv's output as it is
    "no_final_norm",  # the head fed the raw stream
)


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _w(blocks, i, name):
    return jax.lax.dynamic_index_in_dim(
        blocks[name], i, 0, keepdims=False
    ).astype(jnp.float32)


def _attention(h, blocks, p, cfg, fault=None):
    """Softmax attention of period p's full layer over one sequence,
    without positions.  h: [T, D] -> [T, D]."""
    t = h.shape[0]
    hq, hkv, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q, k = h @ _w(blocks, p, "wq"), h @ _w(blocks, p, "wk")
    v = (h @ _w(blocks, p, "wv")).reshape(t, hkv, hd)
    wq, wk = _w(blocks, p, "q_norm"), _w(blocks, p, "k_norm")
    if fault == "qk_norm_per_head":
        q = _rms_norm(q.reshape(t, hq, hd), wq.reshape(hq, hd), cfg.rms_norm_eps)
        k = _rms_norm(k.reshape(t, hkv, hd), wk.reshape(hkv, hd),
                      cfg.rms_norm_eps)
    elif fault != "no_qk_norm":
        q = _rms_norm(q, wq, cfg.rms_norm_eps)
        k = _rms_norm(k, wk, cfg.rms_norm_eps)
    q, k = q.reshape(t, hq, hd), k.reshape(t, hkv, hd)
    if fault == "rope":
        inv_freq = 1.0 / (
            10000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        q = q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)
        k = k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(t, hq * hd) @ _w(blocks, p, "wo")


def _delta_net(h, blocks, i, cfg, lower=None, n_valid=None, fault=None):
    """Gated DeltaNet of linear layer i over one sequence, token by token.
    h: [T, D] -> ([T, D], S [h_v, d_k, d_v] after token `n_valid` - 1
    (None: the last), the conv's inputs at that token and the K - 2 before
    it [K - 1, C], the largest beta_t)."""
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
    if fault != "no_conv_silu":
        conv = jax.nn.silu(conv)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2norm(conv[:, :kd].reshape(t, hk, dk)) * dk ** -0.5
    k = l2norm(conv[:, kd: 2 * kd].reshape(t, hk, dk))
    v = conv[:, 2 * kd:].reshape(t, hv, dv)
    q = jnp.repeat(q, hv // hk, axis=1)
    k = jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    if cfg.linear_neg_eigval and fault != "beta_in_0_1":
        beta = 2.0 * beta
    beta = _lower(beta, lower, "gates")
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
    o = _rms_norm(o, _w(blocks, i, "la_norm"), cfg.rms_norm_eps)
    if fault != "no_output_gate":
        o = o * jax.nn.silu(z)
    tail = jax.lax.dynamic_slice_in_dim(padded, n_valid, kk - 1, axis=0)
    return (o.reshape(t, hv * dv) @ _w(blocks, i, "la_wo"), at_n, tail,
            jnp.max(beta))


def _mlp(h, blocks, l):
    return (jax.nn.silu(h @ _w(blocks, l, "wg")) * (h @ _w(blocks, l, "wu"))
            ) @ _w(blocks, l, "wd")


def _layer(x, blocks, l, cfg, lower=None, n_valid=None, fault=None):
    """Decoder layer l (a Python int: the kind of layer is static) over one
    sequence.  x: [T, D] fp32 -> (x, what a Gated DeltaNet layer's
    `_delta_net` leaves after `n_valid` tokens; () for a full layer)."""
    n = cfg.full_attn_interval
    p, j = divmod(l, n)
    eps = cfg.rms_norm_eps

    def mixer(h):
        if j == n - 1:
            return _attention(h, blocks, p, cfg, fault), ()
        y, *left = _delta_net(
            h, blocks, p * (n - 1) + j, cfg, lower, n_valid, fault)
        return y, tuple(left)

    if fault == "norm_on_input":
        y, left = mixer(_rms_norm(x, _w(blocks, l, "ln1"), eps))
        x = x + y
        return x + _mlp(_rms_norm(x, _w(blocks, l, "ln2"), eps), blocks, l), left
    y, left = mixer(x)
    x = x + _rms_norm(y, _w(blocks, l, "ln1"), eps)
    return x + _rms_norm(_mlp(x, blocks, l), _w(blocks, l, "ln2"), eps), left


def _hidden_and_state(params, cfg, tokens, lower=None, n_valid=None,
                      fault=None):
    """-> ([T, D] fp32 hidden states after the final norm, the linear
    layers' S after `n_valid` tokens [n_linear, h_v, d_k, d_v], their
    convs' last inputs there [n_linear, K - 1, C], their largest beta
    [n_linear])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 6))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    left = []
    for l in range(cfg.n_layers):
        x, here = layer(x, params["blocks"], l, cfg, lower, n_valid, fault)
        left += [here] if here else []
    if fault != "no_final_norm":
        x = _rms_norm(x, params["final_ln"], cfg.rms_norm_eps)
    return x, tuple(jnp.stack(parts) for parts in zip(*left))


def final_hidden(params, cfg, tokens, lower=None, fault=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params`."""
    return _hidden_and_state(params, cfg, tokens, lower, fault=fault)[0]


def logits(params, cfg, tokens, lower=None, fault=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(
            params, cfg, jnp.asarray(tokens, jnp.int32), lower, fault)
        return x @ params["lm_head"].astype(jnp.float32)


def final_state(params, cfg, tokens, lower=None):
    """(S [n_linear, h_v, d_k, d_v], the convs' last K - 1 inputs [n_linear,
    K - 1, C], the largest beta a layer [n_linear]) after the whole
    sequence (small sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        return _hidden_and_state(
            params, cfg, jnp.asarray(tokens, jnp.int32), lower)[1]


def next_token_logprobs(params, cfg, tokens, lower=None, fault=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_state` refuses what the
    system's prefill and decode steps leave in the cache over the same
    tokens (the reference proper only: `lower` and `fault` compute
    controls).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; every mixer is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = np.asarray(tokens)
    out, (state, tail, beta_max) = _next_token_logprobs(
        params, cfg, padded, lower, n, fault)
    print(f"[benchmark] olmo_hybrid reference, {n} tokens, largest beta a "
          f"linear layer {np.round(np.asarray(beta_max), 3).tolist()}",
          file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None or fault is not None:
        return out  # a control: nothing of the system's is checked
    readings, problems = check_state(params, cfg, tokens, state, tail)
    print(f"[benchmark] olmo_hybrid state check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


def check_state(params, cfg, tokens, ref_state, ref_conv):
    """(`state_readings` of what the system's `prefill` and `decode_step`
    leave in the cache over `tokens` against the reference's,
    `state_problems` under the backend's limits)."""
    readings = state_readings(
        *system_state(params, cfg, tokens), ref_state, ref_conv)
    cpu = jax.default_backend() == "cpu"
    return readings, state_problems(
        readings, STATE_TOLERANCE_FP32 if cpu else STATE_TOLERANCE)


def _next_token_logprobs(params, cfg, tokens, lower=None, n_valid=None,
                         fault=None):
    """-> (log-probs [T - 1], what `_hidden_and_state` leaves after
    `n_valid` tokens)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, left = _hidden_and_state(params, cfg, tokens, lower, n_valid, fault)
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
        return np.asarray(sum(tl_all) - lse, np.float32), left

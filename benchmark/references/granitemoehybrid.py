"""Plain reference of the Granite 4.0-H decoder (ibm-granite/granite-4.0-h-
micro, `model_type: granitemoehybrid`, dense: `num_local_experts` 0): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, the Mamba recurrence TOKEN BY TOKEN under `lax.scan` (no chunks),
attention as a dense masked softmax in blocks of queries, the head in
blocks of the vocabulary, no cache, no kernels, no packing, no batching,
under `jax.default_matmul_precision("highest")`.

Follows the published description (HF `modeling_granitemoehybrid.py`), `h`
the hidden size:

    x0      = E[ids] * embedding_multiplier
    layer l : x <- x + residual_multiplier * mixer_l(rmsnorm(x; ln1_l))
              x <- x + residual_multiplier * mlp(rmsnorm(x; ln2_l))
    mixer_l = Mamba-2 where layer_types[l] == "mamba", else attention
    Mamba-2 : [z | xBC | dt] = in_proj(u); xBC = silu(causal depthwise
              conv, K taps, WITH bias); x [T, H, P], B and C [T, G, N];
              dt = softplus(dt + dt_bias), A = -exp(A_log); per head,
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                y_t = S_t C_t + D x_t
              out_proj(w * rmsnorm(y * silu(z))) — gate first, then a norm
              over each GROUP of d_inner / G channels (G = 1: all of them)
    attention: q, k, v without bias, NO positional embedding,
              softmax(q k^T * attention_multiplier + causal mask) v, o_proj
    mlp     : output_linear(silu(gate) * up), [gate | up] = input_linear(u)
    logits  = (rmsnorm(x_L; final) E^T) / logits_scaling     (head tied)

No departures: the cut is depth alone (the configuration's file).

It reads the ENGINE'S weights (bf16, stacked under "blocks": `ln1`, `ln2`
and the MLP over all layers, each mixer's leaves over its own) and upcasts
them, so a difference from the system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  Log-probability bounds cannot tell a bf16 state from the
system's own bf16 activations (PERF.md section 6, PR 32, 38, 40), so the
limit that refuses a lower precision is on the STATE: `check_generator`
builds a `GeneratorEngine` over the same weights, drives ITS SERVING PLANE
(`serving_rollout`: the page pool, the ragged chunk, admission in waves —
the cell's route) with the cell's engine arguments and more requests than
slots, and holds the Mamba state and conv tail the chunk left in two slots
— one of them a slot that served a SECOND request — to the S and the conv
inputs this reference ends on over the tokens the plane sampled there.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
# The Mamba-2 mixer token by token (`_mamba`: the same mixer, `n_groups` 1
# here), the rounding of a control and the padding are nemotron_h's.
from benchmark.references.nemotron_h import _mamba, _padded, _rms_norm
from benchmark.references.qwen2 import _head_chunk
from benchmark.references.qwen3_next import state_problems, state_readings

_TOL = files.load_json("configs", "granite-4.0-h-micro-l10.json")[
    "benchmark"]["tolerance"]
# mean and max of |system - reference| over the compared tokens.
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
# On the CPU rehearsal the system itself computes in fp32.
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `state_readings` (`check_generator`), the chip's and the CPU's.
STATE_TOLERANCE = dict(_TOL["state"])
STATE_TOLERANCE_FP32 = dict(_TOL["fp32"]["state"])

# `lower="bfloat16"` computes what the tolerance has to refuse: the state
# S, dt and the decay exp(dt A) rounded to that type at every step.  None:
# the reference proper.  One alone: "bfloat16:state", "bfloat16:gates".
LOWER_PRECISION = "bfloat16"
# `check_generator`'s call of the serving plane: the cell's 64 slots, 96
# requests (its 24 prompts x 4), at most its 128 new tokens; the prompts
# are the compared sequence's first tokens, their lengths spread evenly
# from 1/2 to 3/2 of what the sequence has beside the new tokens, so the
# plane runs the timed one's shapes (slots, lanes, pages by the longest
# prompt) and a second wave reuses half the slots.
CHECK_SLOTS = 64
CHECK_REQUESTS = 96
CHECK_NEW = 128
QUERY_BLOCK = 256

_MAMBA = ("ssm_in", "ssm_conv", "ssm_conv_b", "ssm_A_log", "ssm_D",
          "ssm_dt_bias", "ssm_norm", "ssm_out")
_ATTENTION = ("wq", "wk", "wv", "wo")
_EVERY = ("ln1", "ln2", "wg", "wu", "wd")


def _layer_weights(blocks, l, cfg):
    """Layer l's leaves under their own names, fp32."""
    kind = cfg.window_pattern[l]
    i = cfg.window_pattern[:l].count(kind)
    out = {n: blocks[n][l].astype(jnp.float32) for n in _EVERY}
    for name in _MAMBA if kind == "M" else _ATTENTION:
        out[name] = blocks[name][i].astype(jnp.float32)
    return out


def _attention(h, w, cfg):
    """Grouped-query attention over one sequence, no positions, the scores
    scaled by `attention_multiplier`; a block of queries at a time."""
    t = h.shape[0]
    hq, hk, d = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ w["wq"]).reshape(t, hq, d)
    k = jnp.repeat((h @ w["wk"]).reshape(t, hk, d), hq // hk, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(t, hk, d), hq // hk, axis=1)
    scale = cfg.attention_multiplier or d**-0.5
    keys = jnp.arange(t)
    out = []
    for q0 in range(0, t, QUERY_BLOCK):
        qb = q[q0: q0 + QUERY_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        seen = keys[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(
            jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out).reshape(t, hq * d) @ w["wo"]


def _layer(x, blocks, l, cfg, lower=None, n_valid=None):
    """Decoder layer l (a Python int: the kind of mixer is static) over one
    sequence.  x: [T, D] fp32 -> (x, (S, conv tail) after `n_valid` tokens
    for a Mamba layer, () otherwise)."""
    w = _layer_weights(blocks, l, cfg)
    r = cfg.residual_multiplier
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    left = ()
    if cfg.window_pattern[l] == "M":
        y, s, tail = _mamba(h, w, cfg, lower, n_valid)
        left = (s, tail)
    else:
        y = _attention(h, w, cfg)
    x = x + r * y
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    return x + r * ((jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]), left


def _hidden_and_state(params, cfg, tokens, lower=None, n_valid=None):
    """-> ([T, D] fp32 hidden states after the final norm, the Mamba
    layers' S after `n_valid` tokens [n_ssm, H, P, N] and their convs' last
    inputs there [n_ssm, K - 1, C])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x = x * cfg.embedding_multiplier
    left = []
    for l in range(cfg.n_layers):
        x, here = layer(x, params["blocks"], l, cfg, lower, n_valid)
        left += [here] if here else []
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, tuple(jnp.stack(parts) for parts in zip(*left))


def final_hidden(params, cfg, tokens, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_state(params, cfg, tokens, lower)[0]


def _head(params, cfg):
    """[D, V]: the tied table transposed, or the model's own head."""
    return params["embed"].T if cfg.tied_embeddings else params["lm_head"]


def logits(params, cfg, tokens, lower=None):
    """[T, V] fp32 logits, divided by `logits_scaling` (small sizes:
    tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(params, cfg, jnp.asarray(tokens, jnp.int32), lower)
        return (x @ _head(params, cfg).astype(jnp.float32)) / cfg.logits_scaling


def next_token_logprobs(params, cfg, tokens, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the serving plane leaves in its slots (the reference proper only:
    `lower` computes a control)."""
    n = len(tokens)
    out, _, _ = _next_token_logprobs(params, cfg, _padded(tokens), lower, n)
    print(f"[benchmark] granitemoehybrid reference, {n} tokens",
          file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None:  # a control: nothing of the system's is checked
        return out
    readings, problems = check_generator(params, cfg, tokens)
    print(f"[benchmark] granitemoehybrid generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# The state the SERVING PLANE leaves in its slots, against the reference's S
# --------------------------------------------------------------------------

def _engine(params, cfg, n_slots):
    """A `GeneratorEngine` over `params` as they lie (no copy), on their
    own mesh, built as a worker builds the timed one (the cell sets no
    engine option: pages, lanes, W and the kernels are the engine's own).
    Built anew for every call and dropped with its compiled program."""
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = getattr(params["embed"].sharding, "mesh", None)
    if mesh is None:
        mesh = make_mesh(
            ParallelConfig.from_str("d1"), sorted(params["embed"].devices()))
    # EOS is the first id past the vocabulary, as in every cell.
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=n_slots, donation_safe_swap=False)


def generator_rollouts(params, cfg, tokens, n_slots=CHECK_SLOTS,
                       n_requests=CHECK_REQUESTS):
    """The serving plane of a `GeneratorEngine`, once, over `n_requests`
    prompts cut from `tokens` in `n_slots` slots -> for two slots, the
    first that served ONE request and the first that served more (its
    tokens, prompt and sampled ones, of the LAST request it served; the
    log-probs the plane returned for the sampled ones; the state [n_ssm,
    H, P, N] and conv tail [n_ssm, K - 1, C] the chunk left in the slot,
    which has then consumed every one of those tokens), and the engine's
    `last_pool_stats`."""
    from areal_tpu.api.model_api import GenerationHyperparameters

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) * 2 // 3)
    rest = len(tokens) - n_new
    lens = np.linspace(max(2, rest // 2), max(2, rest * 3 // 2), n_requests)
    lens = np.minimum(lens.astype(int), len(tokens))
    # Admitted in the order given: long prompts first, as `generate` sorts.
    prompts = [tokens[: int(n)] for n in sorted(lens, reverse=True)]
    eng = _engine(params, cfg, n_slots)
    results, pool, served = eng.serving_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(53))
    once = [s for s in sorted(served) if len(served[s]) == 1]
    again = [s for s in sorted(served) if len(served[s]) > 1]
    out = []
    for s in once[:1] + again[:1]:
        i = served[s][-1]
        toks, logps = results[i]
        out.append((
            np.concatenate([prompts[i], toks]), logps, *pool.slot_state(s),
            len(served[s]),
        ))
    return out, dict(eng.last_pool_stats)


def check_generator(params, cfg, tokens, **how):
    """(`state_readings` of what the serving plane left in the compared
    slots — the worst over them — beside the mean and the largest
    |log-prob(plane) - log-prob(reference)| over the tokens it sampled
    there, which are reported and not limited here: `checks.py` limits the
    timed rollouts'; `state_problems` under the backend's limits, and a
    problem where no compared slot served a second request)."""
    readings, diffs, most = {}, [], 0
    rollouts, stats = generator_rollouts(params, cfg, tokens, **how)
    for seq, logps, state, conv, n_served in rollouts:
        n = len(seq)
        want, ref_state, ref_conv = _next_token_logprobs(
            params, cfg, _padded(seq), None, n)
        for k, v in state_readings(state, conv, ref_state, ref_conv).items():
            worst = min if k == "state_bf16_residual_min" else max
            readings[k] = worst(v, readings.get(k, v))
        first = n - len(logps)  # position t scores token t + 1
        diffs.append(np.abs(logps - want[first - 1: n - 1]))
        most = max(most, n_served)
    diffs = np.concatenate(diffs)
    readings.update(
        logprob_mean_abs=float(diffs.mean()), logprob_max_abs=float(diffs.max()),
        n_tokens=int(diffs.size), slots_compared=len(rollouts),
        most_requests_in_a_slot=most,
        slots_zeroed=stats.get("ssm_slots_zeroed"),
    )
    cpu = jax.default_backend() == "cpu"
    problems = state_problems(
        readings, STATE_TOLERANCE_FP32 if cpu else STATE_TOLERANCE)
    if most < 2:
        problems.append("no compared slot served a second request")
    return readings, problems


def _next_token_logprobs(params, cfg, tokens, lower=None, n_valid=None):
    """-> (log-probs [T - 1], and of `_hidden_and_state`: S and the conv
    inputs after `n_valid` tokens)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, (state, tail) = _hidden_and_state(
            params, cfg, tokens, lower, n_valid)
        x = x[:-1] / cfg.logits_scaling
        head = _head(params, cfg)
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

"""Plain reference of SDAR-MoE (JetLM/SDAR-30B-A3B-Chat, `model_type:
sdar_moe`): the Qwen3-MoE layer under generation by DIFFUSION OVER BLOCKS
(BD3-LM, arXiv:2503.09573; SDAR, arXiv:2510.06303), in straightforward
`jax.numpy` and float32, one layer at a time, no cache, no kernels, no
packing, no batching, no sorting of tokens by expert, under
`jax.default_matmul_precision("highest")`.

n = RMSNorm (eps `rms_norm_eps`, float32), B = `block_length`, M =
`mask_token_id`, b(i) = floor(pos_i / B) the block of a token by its
ABSOLUTE position in its sequence.

  * Layer: h = x + Attn(n1(x)); y = h + MoE(n2(h)); all layers alike; a
    final norm, an untied head.
  * Attention, 32 query heads over 4 key/value heads of 128: q = x Wq, k =
    x Wk, v = x Wv; an RMSNorm with a [128] weight over each head of q and
    of k; rope (rotate-half, the whole head, theta 1e6) by position; scores
    / sqrt(128), float32 softmax over the VISIBLE keys; concat(o) Wo; no
    bias.
  * Visibility is BLOCK-causal: token i sees token j iff b(j) <= b(i) —
    every earlier block and the whole of its own, the tokens after it too.
  * MoE: p = softmax(h Wr) over all the router's outputs in float32; the
    top k of p, renormalised to sum 1 (`norm_topk_prob`); sum of w_e Wd^e
    (silu(Wg^e h) * Wu^e h) over the chosen.  No shared expert.
  * Output: the head's row at position i is the distribution of the token
    AT i (no shift), the logit of M left out (-inf before every softmax).
  * THE LOG-PROBABILITY of token j of a sequence, a function of the tokens
    alone: l_j = log softmax(head(y_j))[x_j], y_j the output at position j
    of the sequence [x_0 .. x_{b(j) B - 1}, M x B] — every earlier block
    clean, EVERY place of j's block holding M (BD3-LM's likelihood term at
    masking level 1, diffu-GRPO's one-step estimate made per block under
    the real prefix).  `block_logprobs` computes it block by block: one
    forward of prefix + B mask tokens a block.
  * Generation (`replay`): the sampler's trajectory from given uniforms —
    each block starts as its prompt tail followed by M; step s: one forward
    of prefix + block, at every masked place an inverse-CDF draw from the
    softmax (temperature 1) and its probability as confidence; the B / T
    masked places of largest confidence are revealed (the family's
    `low_confidence_static`; remainder to the earliest steps, ties to the
    lower position).

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike: the rank's share
(`cfg.n_experts` experts HELD of the router's `cfg.router_width`; the
layer's output is the held experts' part of the routed sum, nothing stands
in for the rest); the vocabulary is the slice the head holds, the mask
token its last row.

It reads the ENGINE'S weights (bf16, every leaf stacked [L, ...] under
"blocks") and upcasts them.  TOLERANCE lives in the configuration's file.
`check_generator` builds a `GeneratorEngine` over the same weights, runs
ITS block program at the TIMED shapes (the cell's 64 slots, prompts of the
traffic's lengths, 512 new tokens: the program the window times, with one
more output), and holds the rows that program left in its cache (what the
COMMIT forwards wrote) to the roped K and the V of this reference's clean
forward over the tokens it sampled, and its returned log-probs to
`block_logprobs`.  The timed engine's own cache cannot be had: the harness
hands a reference weights, configuration and token ids alone, and the
program's cache is gone when its call returns.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import _rms_norm, _rotate_half

_TOL = files.load_json("configs", "sdar-30b-a3b-chat-l8-e16.json")[
    "benchmark"]["tolerance"]
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
ROWS_TOLERANCE = dict(_TOL["rows"])
ROWS_TOLERANCE_FP32 = dict(_TOL["fp32"]["rows"])

# `lower="lower"`: the router's probabilities rounded to bfloat16 and the
# roped K and the V (what the cache keeps) to 8 bits (e4m3), each a
# precision below the configuration's.  One alone: "lower:router",
# "lower:cache".
LOWER_PRECISION = "lower"
_LOWER = {"router": (8, 7), "cache": (4, 3)}  # (exponent, mantissa) bits
# `fault=` computes the model with ONE part of its mathematics wrong.
FAULTS = (
    "causal",  # a clean token sees no token after it (the masked block
    #            still sees the whole of itself)
    "masked_sees_clean",  # the masked block also sees its own clean tokens
    "shifted",  # the head's row at i read as the distribution of token i + 1
    "block8",  # blocks of 2 B
    "no_qk_norm",  # q and k not normed per head
    "no_topk_norm",  # the chosen experts' weights as the softmax gave them
)
CHECK_SLOTS = 64  # the cell's slots
CHECK_NEW = 512  # new tokens `check_generator`'s call makes: the cell's
CHECK_PROMPTS = (98, 158)  # its prompts' lengths: the traffic's, every tail
PAD_TO = 256  # forwards are padded to multiples of this (few shapes)


def _lower(x, lower, part):
    if lower is None:
        return x
    _, _, only = lower.partition(":")
    if only and only != part:
        return x
    return jax.lax.reduce_precision(x, *_LOWER[part])


def _attention(h, w, cfg, pos, visible, fault=None, lower=None):
    """h [T, D] normed, pos [T], visible [T, T] bool (query, key) ->
    ([T, D], the roped K and the V a cache keeps, [T, n_kv, d] each)."""
    t, hq, hkv, d = h.shape[0], cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ w["wq"]).reshape(t, hq, d)
    k = (h @ w["wk"]).reshape(t, hkv, d)
    v = (h @ w["wv"]).reshape(t, hkv, d)
    if fault != "no_qk_norm":
        q = _rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
    inv = cfg.rope_theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = q * cos + _rotate_half(q) * sin
    k = k * cos + _rotate_half(k) * sin
    k, v = _lower(k, lower, "cache"), _lower(v, lower, "cache")
    rep = hq // hkv
    kx, vx = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kx) * d ** -0.5
    scores = jnp.where(visible[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(visible.any(-1)[None, :, None], probs, 0.0)  # pads
    attn = jnp.einsum("hqk,khd->qhd", probs, vx).reshape(t, hq * d)
    return attn @ w["wo"], k, v


def _route(h, w, cfg, fault=None, lower=None):
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


def _layer(x, blocks, l, pos, visible, cfg, fault=None, lower=None):
    w = {
        n: a[l] if a.ndim == 4 else a[l].astype(jnp.float32)
        for n, a in blocks.items()
    }
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    attn, k, v = _attention(h, w, cfg, pos, visible, fault, lower)
    x = x + attn
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    return x + _moe(h, w, cfg, fault, lower), k, v


_layer_jit = jax.jit(_layer, static_argnums=(5, 6, 7))


def _block_len(cfg, fault):
    return cfg.block_length * (2 if fault == "block8" else 1)


def _visible(cfg, pos, stream, real, fault=None):
    """[T, T] bool: query i sees key j.  `stream` 0 clean, 1 the masked
    block; `real` marks the tokens that are not padding."""
    blk = pos // _block_len(cfg, fault)
    bq, bk = blk[:, None], blk[None, :]
    sq, sk = stream[:, None], stream[None, :]
    earlier = (sk == 0) & (bk < bq)
    own = (sk == sq) & (bk == bq)
    if fault == "causal":  # among clean tokens, nothing after the query
        own &= (sq == 1) | (pos[None, :] <= pos[:, None])
    if fault == "masked_sees_clean":
        own |= (sq == 1) & (sk == 0) & (bk == bq)
    return (earlier | own) & real[:, None] & real[None, :]


def _forward(params, cfg, tokens, pos, stream, real, fault=None, lower=None):
    """-> (final-normed hidden states [T, D] fp32, every layer's roped K
    and V [L, T, 2, n_kv, d])."""
    visible = _visible(cfg, pos, stream, real, fault)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    rows = []
    for l in range(cfg.n_layers):
        x, k, v = _layer_jit(
            x, params["blocks"], jnp.int32(l), pos, visible, cfg, fault,
            lower)
        rows.append(jnp.stack([k, v], axis=1))
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, jnp.stack(rows)


def _logits(params, cfg, x):
    out = x @ params["lm_head"].astype(jnp.float32)
    return jnp.where(
        jnp.arange(out.shape[-1]) == cfg.mask_token_id, -jnp.inf, out)


def _padded(n):
    return -(-max(n, 1) // PAD_TO) * PAD_TO


def clean_forward(params, cfg, tokens, fault=None, lower=None):
    """The block-causal forward of a clean sequence -> (logits [T, V] IN
    PLACE, every layer's roped K and V [L, T, 2, n_kv, d])."""
    n = len(tokens)
    t = _padded(n)
    tok = np.zeros(t, np.int32)
    tok[:n] = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x, rows = _forward(
            params, cfg, jnp.asarray(tok), jnp.arange(t),
            jnp.zeros(t, jnp.int32), jnp.arange(t) < n, fault, lower)
        return _logits(params, cfg, x[:n]), rows[:, :n]


def masked_block_logits(params, cfg, prefix, block_tokens, fault=None,
                        lower=None, clean_block=None):
    """Logits [B', V] at the places of ONE block that holds `block_tokens`
    (mask tokens or not) behind the clean `prefix` (whole blocks).
    `clean_block` (the fault `masked_sees_clean` alone): the block's clean
    tokens, which the masked block then also sees."""
    p, nb = len(prefix), len(block_tokens)
    extra = [] if clean_block is None else list(clean_block)
    n = p + len(extra) + nb
    t = _padded(n)
    tok = np.zeros(t, np.int32)
    tok[:n] = np.concatenate([prefix, extra, block_tokens]).astype(np.int32)
    pos = np.arange(t)
    pos[p + len(extra): n] = p + np.arange(nb)
    stream = np.zeros(t, np.int32)
    if extra:
        stream[p + len(extra): n] = 1
    with jax.default_matmul_precision("highest"):
        x, _ = _forward(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(stream), jnp.arange(t) < n, fault, lower)
        return _logits(params, cfg, x[n - nb: n])


def block_logprobs(params, cfg, tokens, fault=None, lower=None):
    """l_j of the module's docstring for every j < T, numpy fp32 [T]:
    block by block, prefix clean, the block all M (a full block of B mask
    tokens also where the sequence ends inside it, as the generator's
    block is full)."""
    tokens = np.asarray(tokens, np.int32)
    blk = _block_len(cfg, fault)
    out = np.zeros(len(tokens), np.float32)
    for b0 in range(0, len(tokens), blk):
        own = tokens[b0: b0 + blk]
        logits = masked_block_logits(
            params, cfg, tokens[:b0], np.full(blk, cfg.mask_token_id),
            fault, lower,
            clean_block=own if fault == "masked_sees_clean" else None)
        lsm = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        if fault == "shifted":
            # Row i read as the distribution of token i + 1 (a block's
            # first token from the clean row before it).
            if b0:
                before = clean_forward(params, cfg, tokens[:b0], lower=lower)
                out[b0] = np.asarray(jax.nn.log_softmax(
                    before[0][-1]))[own[0]]
            out[b0 + 1: b0 + len(own)] = lsm[
                np.arange(len(own) - 1), own[1:]]
        else:
            out[b0: b0 + len(own)] = lsm[np.arange(len(own)), own]
    return out


def next_token_logprobs(params, cfg, tokens, fault=None, lower=None):
    """The harness's call: index j - 1 holds l_j for every j >= 1, numpy
    fp32 [T - 1] — all NaN (so that the run is not `correct`) where
    `check_generator` refuses what the generator's block program leaves in
    its cache.  `fault` and `lower` compute a control: a lower precision
    is held to the cache's rows like the reference proper (its rows are
    what the cache would keep), a fault by its log-probs alone."""
    out = block_logprobs(params, cfg, tokens, fault, lower)[1:]
    print(f"[benchmark] sdar_moe reference, {len(tokens)} tokens, blocks of "
          f"{cfg.block_length}, experts [{cfg.expert_offset}, "
          f"{cfg.expert_offset + cfg.n_experts}) of {cfg.router_width}",
          file=sys.stderr, flush=True)
    if fault is not None:
        return out
    readings, problems = check_generator(params, cfg, tokens, lower)
    print(f"[benchmark] sdar_moe generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


# --------------------------------------------------------------------------
# The sampler's trajectory, replayed from given uniforms
# --------------------------------------------------------------------------

def _draw(logits, u):
    """Inverse-CDF draw from softmax(logits) at uniform u -> (token, its
    probability), in float64 numpy."""
    z = np.asarray(logits, np.float64)
    p = np.exp(z - z[np.isfinite(z)].max())
    p[~np.isfinite(z)] = 0.0
    cum = np.cumsum(p)
    r = min(u * cum[-1], cum[-1] * (1 - 1e-6))
    tok = int(np.argmax((cum > r) & (p > 0)))
    return tok, p[tok] / cum[-1]


def replay(params, cfg, prompt, uniforms, n_blocks, steps):
    """The tokens the sampler makes behind `prompt` over `n_blocks` blocks,
    and the step that revealed each place -> (tokens [n_blocks x B] from
    the first block's start — its tail included — , steps [n_blocks x B],
    -1 at a tail's places).  `uniforms(k, s)` -> [B] uniforms of block k's
    step s."""
    blk, mask = cfg.block_length, cfg.mask_token_id
    prompt = np.asarray(prompt, np.int32)
    whole = len(prompt) // blk * blk
    seq, tail = prompt[:whole], prompt[whole:]
    n_by_step = [blk // steps + (s < blk % steps) for s in range(steps)]
    toks_out, steps_out = [], []
    for k in range(n_blocks):
        x = np.full(blk, mask, np.int32)
        step_of = np.full(blk, -1, np.int64)
        if k == 0:
            x[: len(tail)] = tail
        masked = x == mask
        s = 0
        while masked.any() and s < steps:
            logits = np.asarray(masked_block_logits(params, cfg, seq, x))
            u = uniforms(k, s)
            drawn = [_draw(logits[i], u[i]) for i in range(blk)]
            conf = np.asarray([c for _, c in drawn])
            order = sorted(
                (i for i in range(blk) if masked[i]),
                key=lambda i: (-conf[i], i))
            for i in order[: n_by_step[s]]:
                x[i], masked[i], step_of[i] = drawn[i][0], False, s
            s += 1
        toks_out.append(x.copy())
        steps_out.append(step_of)
        seq = np.concatenate([seq, x])
    return np.concatenate(toks_out), np.concatenate(steps_out)


# --------------------------------------------------------------------------
# What the generator's block program leaves in its cache, against the roped
# K and the V of the clean forward
# --------------------------------------------------------------------------

def _engine(params, cfg):
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = getattr(params["embed"].sharding, "mesh", None)
    if mesh is None:
        mesh = make_mesh(
            ParallelConfig.from_str("d1"), sorted(params["embed"].devices()))
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=CHECK_SLOTS, donation_safe_swap=False)


def generator_rollouts(params, cfg, tokens, slots=None):
    """The block program of a `GeneratorEngine`, once, over CHECK_SLOTS
    prompts cut from `tokens` (their lengths spread over CHECK_PROMPTS:
    every tail 0 .. B - 1) -> for each slot of `slots` (its tokens, prompt
    and sampled;
    the log-probs the program returned for the sampled ones; the K and V
    every layer's cache holds for the row's tokens [L, n, 2, n_kv, d])."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.packing import decode_bucket_len

    tokens = np.asarray(tokens, np.int32)
    slots = (0, CHECK_SLOTS - 1) if slots is None else slots
    lo, hi = (min(n, len(tokens)) for n in CHECK_PROMPTS)
    prompts = [tokens[: int(n)] for n in np.linspace(lo, hi, CHECK_SLOTS)]
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=CHECK_NEW),
        jax.random.PRNGKey(68), with_cache=True)
    sp = decode_bucket_len(max(len(p) for p in prompts))
    blk = cfg.block_length
    out = []
    for r in slots:
        n, gl = len(prompts[r]), int(gen_len[r])
        first = sp - n // blk * blk  # the slot of the row's position 0
        end = first + n + gl
        kv = jnp.stack(
            [cache.k[:, r, first:end], cache.v[:, r, first:end]], axis=2)
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl], kv))
    return out


def rows_readings(kv, ref_rows):
    """The largest |R - R_ref|_F / |R_ref|_F of a layer's kept K or V:
    `rows_rel_err_unrouted` layer 0 (no routed expert before it: the
    precision of the projections and of what the cache keeps),
    `rows_rel_err_max` every layer (the slots, the commit)."""
    def norm(x):
        return np.sqrt(np.square(np.asarray(x, np.float64)).sum((0, 2, 3)))

    err = []
    got_all = np.asarray(jnp.asarray(kv, jnp.float32))
    for got, want in zip(got_all, np.asarray(ref_rows, np.float32)):
        err.append((norm(got - want) / (
            norm(want) + np.finfo(np.float32).tiny)).max())
    return {
        "rows_rel_err_unrouted": float(err[0]),
        "rows_rel_err_max": float(max(err)),
    }


def rows_problems(readings, tol):
    return [
        f"{name} {readings[name]:.3g} above {tol[name]}"
        for name in ("rows_rel_err_unrouted", "rows_rel_err_max")
        if not readings[name] <= tol[name]
    ]


_CHECKED = []  # (the weights checked, lower, what `check_generator` found)


def check_generator(params, cfg, tokens, lower=None):
    """(`rows_readings` of what the generator's own program left in its
    cache — the largest over the compared slots — beside the mean and the
    largest |log-prob(program) - log-prob(reference)| over the tokens it
    sampled, reported and not limited here; `rows_problems` under the
    backend's limits).  Once a set of weights: it holds the program to the
    reference, whatever sequence the harness compares.  `lower`: against
    the reference in that precision (a control)."""
    for embed, low, found in _CHECKED:
        if embed is params["embed"] and low == lower:
            return found
    readings, diffs = {}, []
    for seq, logps, kv in generator_rollouts(params, cfg, tokens):
        n = len(seq)
        _, ref_rows = clean_forward(params, cfg, seq, lower=lower)
        # The last block's spare places were dropped: its kept tokens'
        # clean rows stand beside tokens the reference never saw, and no
        # later block read them — compare the whole blocks.
        whole = n // cfg.block_length * cfg.block_length
        for k, v in rows_readings(kv[:, :whole], ref_rows[:, :whole]).items():
            readings[k] = max(v, readings.get(k, 0.0))
        want = block_logprobs(params, cfg, seq, lower=lower)
        diffs.append(np.abs(logps - want[n - len(logps):]))
    diffs = np.concatenate(diffs)
    readings.update(
        logprob_mean_abs=float(diffs.mean()),
        logprob_max_abs=float(diffs.max()), n_tokens=int(diffs.size))
    cpu = jax.default_backend() == "cpu"
    found = readings, rows_problems(
        readings, ROWS_TOLERANCE_FP32 if cpu else ROWS_TOLERANCE)
    _CHECKED[:] = [c for c in _CHECKED if c[0] is params["embed"]] + [
        (params["embed"], lower, found)]
    return found

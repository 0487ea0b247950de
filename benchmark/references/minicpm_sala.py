"""Plain reference of the MiniCPM-SALA decoder (openbmb/MiniCPM-SALA,
`model_type: minicpm_sala`): the forward pass in straightforward
`jax.numpy` and float32, one layer at a time, block selection as an
explicit [queries, blocks] mask built a block of queries at a time,
attention dense under that mask, Lightning attention as the recurrence
TOKEN BY TOKEN under `lax.scan` (no chunks), no cache, no kernels, no
packing, no batching, under `jax.default_matmul_precision("highest")`.

Follows ISSUE 55's layer equations, `r = scale_depth / sqrt(32)` (the
PUBLISHED depth, at any depth):

    x0      = E[ids] * scale_emb
    layer l : x <- x + r * mixer_l(rmsnorm(x; ln1_l))
              x <- x + r * mlp(rmsnorm(x; ln2_l)),  mlp = down(silu(gate) * up)
    logits  = W_head . (rmsnorm(x_L; final) / (hidden_size / dim_model_base))

    minicpm4 (sparse), Hq query and Hkv key heads of d, G = Hq / Hkv:
        q = rmsnorm_head(W_q x), k = rmsnorm_head(W_k x), v = W_v x, NO rope
        a sequence of fewer than dense_len tokens: causal softmax attention
        else, per key head:
          kc_j   = mean(k[16 j : 16 j + 32]), every kernel inside the sequence
          p_t    = softmax_j(q_t . kc_j / sqrt(d)) over 16 j + 31 <= t, a head
          s_t[j] = the sum of p_t[j] over the key head's G query heads
          S_t[b] = max of s_t[4 b - 1 .. 4 b + 3]       (block b = keys 64 b ..)
          chosen = block 0, the 32 blocks ending at t's own, then the highest
                   S_t: 64 in all, of the blocks that start at or before t
          attention, causal, over the chosen blocks' keys, the G heads alike
        o = W_o (sigmoid(W_g x) * attn)
    lightning-attn, H heads of d:
        q = rmsnorm_head(W_q x), k = rmsnorm_head(W_k x), v = W_v x,
        rope (theta, whole head, rotate-half) on q and k
        S_t = lambda_h S_{t-1} + k_t^T v_t,   lambda_h = exp(-2^(-8 (h+1) / H))
        y_t = (q_t / sqrt(d)) S_t
        o = W_o (sigmoid(W_g x) * rmsnorm_head(y))

Departures from the published model, each listed in the configuration's
`assumed`: the selection's seven sizes (MiniCPM4's `sparse_config`; the
catalog row has no such group), the decay rule, the output norm per head,
no activation on q / k / v, the gates' width.  The hidden state is divided
before the head as published (the program divides the logits: the same
product in another order).  The vocabulary is the slice the head holds.

It reads the ENGINE'S weights (bf16, stacked under "blocks": `ln1`, `ln2`
and the MLP over all layers, each mixer's leaves over its own) and upcasts
them, so a difference from the system is a difference in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  The limit that refuses a lower precision is on the STATE:
`check_generator` builds a `GeneratorEngine` over the same weights, runs
ITS static program at the cell's shape (with `with_cache`) and holds what
it left — K/V rows, compressed keys, the Lightning state after prefill and
after the last decode step — to this reference over the tokens it sampled,
and counts the (token, key head) selections that differ (`block_flips`).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.nemotron_h import _padded, _rms_norm
from benchmark.references.qwen2 import _head_chunk, _rotate_half
from benchmark.references.qwen3_next import _lower

_TOL = files.load_json("configs", "minicpm-sala-l4-v8.json")[
    "benchmark"]["tolerance"]
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
# Limits on `check_generator`'s readings, the chip's and the CPU's.
STATE_TOLERANCE = dict(_TOL["state"])
STATE_TOLERANCE_FP32 = dict(_TOL["fp32"]["state"])

# `lower="bfloat16"` computes what the tolerance has to refuse: the
# Lightning state S rounded to that type at every step.
LOWER_PRECISION = "bfloat16"
QUERY_BLOCK = 256
# `check_generator`'s call of the static program: the cell's 8 rows and at
# most its 256 new tokens.
CHECK_ROWS = 8
CHECK_NEW = 256

_SPARSE = ("wq", "wk", "wv", "wo", "wqg", "q_norm", "k_norm")
_LIGHTNING = ("lt_wq", "lt_wk", "lt_wv", "lt_wg", "lt_q_norm", "lt_k_norm",
              "lt_norm", "lt_wo")
_EVERY = ("ln1", "ln2", "wg", "wu", "wd")


def _layer_weights(blocks, l, cfg):
    """Layer l's leaves under their own names, fp32."""
    kind = cfg.window_pattern[l]
    i = cfg.window_pattern[:l].count(kind)
    out = {n: blocks[n][l].astype(jnp.float32) for n in _EVERY}
    for name in _SPARSE if kind == "B" else _LIGHTNING:
        out[name] = blocks[name][i].astype(jnp.float32)
    return out


def _block_choice(qb, t0, kc, n, cfg):
    """For a block of queries qb [Q, Hq, d] at positions t0.., against the
    compressed keys kc [NK, Hkv, d] of a sequence of n tokens -> the chosen
    blocks [Q, Hkv, NB] bool."""
    hq, hk, d = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    ks, st, bs = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                  cfg.sparse_block_size)
    per = bs // st  # kernels that START in a block
    nk = kc.shape[0]
    nb = -(-n // bs)
    t = t0 + jnp.arange(qb.shape[0])
    seen = jnp.arange(nk)[None, :] * st + ks - 1 <= t[:, None]  # [Q, NK]
    scores = jnp.einsum(
        "qgrd,jgd->qgrj", qb.reshape(-1, hk, hq // hk, d), kc) * d**-0.5
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    p = jnp.where(seen[:, None, None, :], jax.nn.softmax(scores, axis=-1), 0.0)
    p = jnp.where(seen.any(-1)[:, None, None, None], p, 0.0)  # no kernel yet
    s = p.sum(axis=2)  # [Q, Hkv, NK]
    # Block b: the kernels that overlap keys [bs b, bs b + bs) are numbers
    # per * b - 1 .. per * b + per - 1 (a kernel is two strides).
    first = per * jnp.arange(nb) - 1
    among = first[:, None] + jnp.arange(per + 1)[None, :]  # [NB, per + 1]
    inside = (among >= 0) & (among < nk)
    pooled = jnp.where(
        inside, s[..., jnp.clip(among, 0, nk - 1)], 0.0).max(axis=-1)
    block = jnp.arange(nb)[None, :]
    own = (t // bs)[:, None]
    visible = block <= own  # [Q, NB]
    forced = (block < cfg.sparse_init_blocks) | (
        block > own - cfg.sparse_window // bs)
    ranked = jnp.where(forced[:, None, :], 1e9, pooled)
    ranked = jnp.where(visible[:, None, :], ranked, -1.0)
    order = jnp.argsort(-ranked, axis=-1, stable=True)[
        ..., : min(cfg.sparse_topk, nb)]
    chosen = jnp.zeros(ranked.shape, bool)
    chosen = jnp.put_along_axis(chosen, order, True, axis=-1, inplace=False)
    return chosen & visible[:, None, :]


def _sparse_attention(h, w, cfg, n):
    """The minicpm4 mixer over one sequence of `n` tokens (h [T, D] may be
    padded past it) -> (o [T, D], what the cache would hold: k, v [T, Hkv,
    d] and the compressed keys [NK, Hkv, d], the chosen blocks [T, Hkv,
    NB] or None for a dense sequence)."""
    t = h.shape[0]
    hq, hk, d = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    ks, st, bs = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                  cfg.sparse_block_size)
    q = _rms_norm((h @ w["wq"]).reshape(t, hq, d), w["q_norm"], cfg.rms_norm_eps)
    k = _rms_norm((h @ w["wk"]).reshape(t, hk, d), w["k_norm"], cfg.rms_norm_eps)
    v = (h @ w["wv"]).reshape(t, hk, d)
    nk = max((n - ks) // st + 1, 0)
    kc = _kernels(k, nk, ks, st)
    select = n >= cfg.sparse_dense_len
    keys = jnp.arange(t)
    out, chosen_all = [], []
    for q0 in range(0, t, QUERY_BLOCK):
        qb = q[q0: q0 + QUERY_BLOCK]
        at = q0 + jnp.arange(qb.shape[0])
        seen = keys[None, :] <= at[:, None]  # [Q, T]
        seen = jnp.broadcast_to(seen[:, None, :], (qb.shape[0], hk, t))
        if select:
            chosen = _block_choice(qb, q0, kc, n, cfg)  # [Q, Hkv, NB]
            chosen_all.append(chosen)
            by_key = jnp.repeat(chosen, bs, axis=-1)  # blocks -> their keys
            by_key = jnp.pad(
                by_key, ((0, 0), (0, 0), (0, max(t - by_key.shape[-1], 0))))
            seen = seen & by_key[..., :t]
        scores = jnp.einsum(
            "qgrd,kgd->qgrk", qb.reshape(-1, hk, hq // hk, d), k) * d**-0.5
        scores = jnp.where(seen[:, :, None, :], scores, -jnp.inf)
        out.append(jnp.einsum(
            "qgrk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(-1, hq * d))
    attn = jnp.concatenate(out)
    o = (jax.nn.sigmoid(h @ w["wqg"]) * attn) @ w["wo"]
    chosen_all = jnp.concatenate(chosen_all) if select else None
    return o, (k, v, kc, chosen_all)


def _kernels(k, nk, ks, st):
    """mean(k[st j : st j + ks]) for j < nk, [NK, Hkv, d] (zero rows where
    nk is 0)."""
    if nk <= 0:
        return jnp.zeros((0, *k.shape[1:]), k.dtype)
    at = st * jnp.arange(nk)[:, None] + jnp.arange(ks)[None, :]
    return k[at].mean(axis=1)


def _lightning(h, w, cfg, lower=None, marks=()):
    """The lightning-attn mixer over one sequence, token by token ->
    (o [T, D], the state S [H, d, d] after each of `marks` tokens)."""
    t = h.shape[0]
    nh, d = cfg.lightning_n_heads, cfg.lightning_head_dim
    q = _rms_norm((h @ w["lt_wq"]).reshape(t, nh, d), w["lt_q_norm"],
                   cfg.rms_norm_eps)
    k = _rms_norm((h @ w["lt_wk"]).reshape(t, nh, d), w["lt_k_norm"],
                   cfg.rms_norm_eps)
    v = (h @ w["lt_wv"]).reshape(t, nh, d)
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    q = q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)
    k = k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)
    lam = jnp.exp(-jnp.exp2(
        -8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh))[:, None, None]
    marks = jnp.asarray(marks or (t,), jnp.int32)

    def step(carry, xs):
        s, kept = carry
        i, q_t, k_t, v_t = xs
        s = _lower(lam * s + k_t[:, :, None] * v_t[:, None, :], lower, "state")
        kept = jnp.where((marks == i + 1)[:, None, None, None], s[None], kept)
        return (s, kept), jnp.einsum("hd,hde->he", q_t * d**-0.5, s)

    zero = jnp.zeros((nh, d, d), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, jnp.zeros((marks.shape[0], nh, d, d), jnp.float32)),
        (jnp.arange(t), q, k, v))
    y = _rms_norm(y, w["lt_norm"], cfg.rms_norm_eps).reshape(t, nh * d)
    return (jax.nn.sigmoid(h @ w["lt_wg"]) * y) @ w["lt_wo"], kept


def _layer(x, blocks, l, cfg, n, lower=None, marks=()):
    """Decoder layer l (a Python int: the kind of mixer is static) over one
    sequence of `n` tokens.  x: [T, D] fp32 -> (x, what the mixer leaves)."""
    w = _layer_weights(blocks, l, cfg)
    r = cfg.residual_multiplier
    h = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    if cfg.window_pattern[l] == "B":
        y, left = _sparse_attention(h, w, cfg, n)
    else:
        y, left = _lightning(h, w, cfg, lower, marks)
    x = x + r * y
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    return x + r * ((jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]), left


def _hidden_and_left(params, cfg, tokens, n, lower=None, marks=()):
    """-> ([T, D] fp32 hidden states after the final norm, a dict of what
    the mixers leave: `k`, `v`, `ck`, `chosen` of the sparse layers (lists,
    a layer each) and `state` [n_lightning, len(marks), H, d, d])."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x = x * cfg.embedding_multiplier
    left = {"k": [], "v": [], "ck": [], "chosen": [], "state": []}
    for l in range(cfg.n_layers):
        x, here = layer(x, params["blocks"], l, cfg, n, lower, tuple(marks))
        # A layer at a time ON THE DEVICE too: dispatched ahead of their
        # execution, the layers' fp32 temporaries at 13 k tokens piled up
        # by how far the host had run ahead, and this pass — not the timed
        # path — set the run's `peak_hbm_gb`, at 12.23 or 12.56 GB by the
        # run (my chip runs, PR 55: 15 runs, the peak always inside the
        # second compared sequence's pass, never inside a timed step).
        jax.block_until_ready(x)
        if cfg.window_pattern[l] == "B":
            for name, part in zip(("k", "v", "ck", "chosen"), here):
                left[name].append(part)
        else:
            left["state"].append(here)
    left["state"] = jnp.stack(left["state"]) if left["state"] else None
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, left


def final_hidden(params, cfg, tokens, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_left(params, cfg, tokens, len(tokens), lower)[0]


def _head(params, cfg):
    return params["embed"].T if cfg.tied_embeddings else params["lm_head"]


def logits(params, cfg, tokens, lower=None):
    """[T, V] fp32 logits, the hidden state divided as published (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(params, cfg, jnp.asarray(tokens, jnp.int32), lower)
        return (x / cfg.logits_scaling) @ _head(params, cfg).astype(jnp.float32)


def next_token_logprobs(params, cfg, tokens, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the static program leaves in its cache (the reference proper only:
    `lower` computes a control; the check runs once a process, its shapes
    are the cell's whatever the sequence)."""
    n = len(tokens)
    out, _ = _next_token_logprobs(params, cfg, _padded(tokens), n, lower)
    print(f"[benchmark] minicpm_sala reference, {n} tokens",
          file=sys.stderr, flush=True)
    out = out[: n - 1]
    if lower is not None:  # a control: nothing of the system's is checked
        return out
    if "problems" not in _CHECKED:
        readings, problems = check_generator(params, cfg, tokens)
        print(f"[benchmark] minicpm_sala generator check {readings} "
              f"{problems or 'ok'}", file=sys.stderr, flush=True)
        _CHECKED["problems"] = problems
    return np.full_like(out, np.nan) if _CHECKED["problems"] else out


_CHECKED = {}


def _next_token_logprobs(params, cfg, tokens, n, lower=None, marks=()):
    """-> (log-probs [T - 1], what `_hidden_and_left` says the mixers
    leave)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, left = _hidden_and_left(params, cfg, tokens, n, lower, marks)
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
        return np.asarray(sum(tl_all) - lse, np.float32), left


# --------------------------------------------------------------------------
# What the STATIC PROGRAM leaves in its cache, against this reference
# --------------------------------------------------------------------------


def _engine(params, cfg, rows):
    """A `GeneratorEngine` over `params` as they lie (no copy), on their
    own mesh, built as a worker builds the timed one."""
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = getattr(params["embed"].sharding, "mesh", None)
    if mesh is None:
        mesh = make_mesh(
            ParallelConfig.from_str("d1"), sorted(params["embed"].devices()))
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=rows, donation_safe_swap=False)


def generator_rollout(params, cfg, prompt, n_new, rows=CHECK_ROWS):
    """The static program of a `GeneratorEngine`, `rows` copies of
    `prompt`, `n_new` new tokens -> (row 0's tokens, prompt and sampled; the
    log-probs returned for the sampled ones; what the program left in row
    0 of its `KVCache`, ON THE HOST, a population a key; the engine's
    `last_pool_stats`).  Engine, program and cache are dropped before it
    returns, collector run: nothing of the check stays on the device into
    the timed steps, whenever Python would have collected the engine's
    cycles."""
    import gc

    from areal_tpu.api.model_api import GenerationHyperparameters

    eng = _engine(params, cfg, rows)
    toks, logps, gen_len, cache = eng.static_rollout(
        [np.asarray(prompt, np.int32)] * rows,
        GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(55), with_cache=True)
    n = int(gen_len[0])
    left = {
        name: np.asarray(getattr(cache, name)[:, 0], np.float32)
        for name in ("k", "v", "ck", "state")
    }
    stats = dict(eng.last_pool_stats)
    del cache, eng
    gc.collect()
    return np.concatenate([prompt, toks[0, :n]]), logps[0, :n], left, stats


def _rel(a, b, axes):
    """|a - b|_F / |b|_F over `axes`, float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.sqrt(np.square(a - b).sum(axes)) / (
        np.sqrt(np.square(b).sum(axes)) + np.finfo(np.float32).tiny)


def _bf16_residual_min(state):
    """The smallest |S - bf16(S)|_F / |S|_F of a head: what rounding the
    state to bfloat16 would change (0 exactly for a state kept in it)."""
    s = jnp.asarray(state, jnp.float32)
    rounded = np.asarray(s.astype(jnp.bfloat16).astype(jnp.float32))
    return float(_rel(rounded, np.asarray(s), (-2, -1)).min())


def system_choice(params, cfg, tokens, n):
    """The SYSTEM's selections over one sequence in its own arithmetic
    (the program's q/k projection and `block_sparse` in the weights' type,
    layer 0, whose input is the embedding alone) -> chosen [T, Hkv, NB]
    bool over the sequence's own blocks, or None where layer 0 is no
    sparse layer."""
    from areal_tpu.models import transformer as tfm
    from areal_tpu.ops import block_sparse

    if cfg.window_pattern[0] != "B":
        return None
    sz = block_sparse.Sizes.of(cfg)

    @jax.jit
    def choose(params, tokens):
        seg = (jnp.arange(tokens.shape[0]) < n).astype(jnp.int32)[None]
        pos = tfm.positions_from_segments(seg)
        x = tfm._embed(params, cfg, tokens[None], pos)
        blk = {k: params["blocks"][k][0] for k in _SPARSE + ("ln1",)}
        h = tfm._norm(x, blk["ln1"], None, cfg)
        (cos, sin), _ = tfm._rope(cfg, pos)
        q, k, _ = tfm._block_kv(h, blk, cfg, cos, sin)
        kc, knum, kseg = block_sparse.compress_row(k[0], pos[0], seg[0], sz)
        t = tokens.shape[0]
        pad = -t % QUERY_BLOCK
        qs = jnp.pad(q[0], ((0, pad), (0, 0), (0, 0)))
        chunks = (t + pad) // QUERY_BLOCK

        def one(xs):
            qc, at = xs
            return block_sparse._select_chunk(
                qc, kc, knum, kseg, jnp.where(at < n, 1, 0),
                jnp.minimum(at, t - 1), jnp.zeros_like(at), sz)

        at = jnp.arange(t + pad).reshape(chunks, QUERY_BLOCK)
        chosen = jax.lax.map(
            one, (qs.reshape(chunks, QUERY_BLOCK, *qs.shape[1:]), at))
        return chosen.reshape(t + pad, *chosen.shape[2:])[:t]

    chosen = np.asarray(choose(params, jnp.asarray(tokens, jnp.int32)))
    # The segment starts at index 0: its first kernel ends at slot
    # (kernel - 1) // stride, global block 0 is the sequence's block 0.
    return chosen[..., : -(-n // cfg.sparse_block_size)]


def check_generator(params, cfg, tokens):
    """(readings, problems): what the static program leaves after prefill
    (a roll-out of ONE new token) and after the last decode step (the
    cell's `max_new_tokens`) over the compared sequence's prompt, held to
    this reference over the tokens the program sampled — K/V rows and
    compressed keys (relative error, the worst key head), the Lightning
    state (relative error of the worst head; `state_bf16_residual_min`),
    the log-probs it returned (reported, limited by `checks.py` for the
    timed roll-outs) and `block_flips`, the share of (token, key head)
    selections in which the system's arithmetic chooses another set of
    blocks than this reference (reported beside its limit: a choice of 31
    free blocks among up to 175 flips on rounding, as a router's does)."""
    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, max(len(tokens) // 3, 1))
    n_prompt = len(tokens) - n_new
    prompt = tokens[:n_prompt]
    seq, logps, cache, stats = generator_rollout(params, cfg, prompt, n_new)
    _, _, first, _ = generator_rollout(params, cfg, prompt, 1)  # prefill + 1
    n = len(seq)
    marks = (n_prompt + 1, n)
    want, left = _next_token_logprobs(
        params, cfg, _padded(seq), n, None, marks)
    from areal_tpu.engines.generator import bucket_len  # the cache's own

    lo = bucket_len(n_prompt) - n_prompt  # row 0's first slot
    readings = {}
    for name in ("k", "v"):
        ref = np.stack([np.asarray(x[:n]) for x in left[name]])
        got = cache[name][:, lo: lo + n]
        readings[f"{name}_rel_err_max"] = float(_rel(got, ref, (1, 3)).max())
    ref_ck = np.stack([np.asarray(x) for x in left["ck"]])
    got_ck = cache["ck"][:, : ref_ck.shape[1]]
    readings["ck_rel_err_max"] = float(_rel(got_ck, ref_ck, (1, 3)).max())
    readings["ck_rows"] = int(ref_ck.shape[1])
    after_prefill, after_decode = first["state"], cache["state"]
    readings["state_prefill_rel_err_max"] = float(
        _rel(after_prefill, left["state"][:, 0], (-2, -1)).max())
    readings["state_rel_err_max"] = float(
        _rel(after_decode, left["state"][:, 1], (-2, -1)).max())
    readings["state_bf16_residual_min"] = min(
        _bf16_residual_min(after_prefill), _bf16_residual_min(after_decode))
    theirs = left["chosen"][0] if left["chosen"] else None
    ours = system_choice(params, cfg, _padded(seq), n)
    if theirs is not None and ours is not None:
        theirs = np.asarray(theirs[:n])
        differ = (ours[:n] != theirs).any(axis=-1)  # [T, Hkv]
        free = np.arange(n) // cfg.sparse_block_size >= cfg.sparse_topk
        readings["block_flips"] = float(differ[free].mean()) if free.any() \
            else 0.0
        readings["blocks_differing_mean"] = float(
            (ours[:n] != theirs).sum(axis=-1)[free].mean()) if free.any() \
            else 0.0
    first_tok = n - len(logps)
    diffs = np.abs(logps - want[first_tok - 1: n - 1])
    readings.update(
        logprob_mean_abs=float(diffs.mean()),
        logprob_max_abs=float(diffs.max()), n_tokens=int(diffs.size),
        sparse_dense_rows=stats.get("sparse_dense_rows"),
        sparse_read_share=(
            stats["sparse_keys_read"] / max(stats["sparse_keys_cached"], 1.0)
            if "sparse_keys_read" in stats else None),
    )
    cpu = jax.default_backend() == "cpu"
    return readings, state_problems(
        readings, STATE_TOLERANCE_FP32 if cpu else STATE_TOLERANCE)


def state_problems(readings, tol):
    """What of `check_generator`'s readings lies outside `tol`, as text."""
    out = [
        f"{name} {readings[name]:.3g} above {tol[name]}"
        for name in ("k_rel_err_max", "v_rel_err_max", "ck_rel_err_max",
                     "state_prefill_rel_err_max", "state_rel_err_max",
                     "block_flips")
        if name in readings and not readings[name] <= tol[name]
    ]
    name = "state_bf16_residual_min"
    if not readings[name] >= tol[name]:
        out.append(f"{name} {readings[name]:.3g} under {tol[name]}: the "
                   "Lightning state holds no more than bfloat16")
    return out

"""Plain reference of the dots3-note-prev decoder (dots-studio/
dots3-note-prev, `model_type: dots3_note`, the language model only): the
forward pass in straightforward `jax.numpy` and float32, one layer at a
time, no cache, no ring, no absorbed form, no kernels, no packing, no
batching, under `jax.default_matmul_precision("highest")`; the attention
and the selection in blocks of QUERY_BLOCK queries against every key (the
cell's sequences are 13,312 tokens).

x is a layer's input, n an RMSNorm (eps `rms_norm_eps`, plain weight):
h = x + Attn_kind(n1(x)), y = h + MLP(n2(h)); a final norm, an untied head.

  * Latent attention in one geometry (H heads held, ranks rq / c, head
    widths nope + rope for q and k, v for values, rope base theta):
    cq = a_q nq(u W_qa), [q_nope | q_pe]_h = cq W_qb; [ckv | k_pe] = u W_kva,
    c = a_kv nkv(ckv); k_nope_h = c W_kb,h, v_h = c W_vb,h; rotate-half rope
    on q_pe and the ONE k_pe all heads share; s_h = (q_nope . k_nope + q_pe
    . k_pe) / sqrt(nope + rope); softmax over the keys the kind allows;
    o_h = sum p v_h; gate g = sigmoid(u W_g), one scalar a head; Attn =
    concat_h(g_h o_h) W_o.  u is the NORMED input n1(x).  a_q = sqrt(hidden
    / rq), a_kv = sqrt(hidden / c) (`apply_mla_qkv_lora_rescale`), on the
    normed latents and not on k_pe.
  * A FULL layer ("F": 128 heads published, rq 1,024, c 512, 128 + 64 | 128,
    theta 80,000,000) allows a query the min(index_topk, visible) visible
    keys of largest index score, ties to the LOWER position:
    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]); qI = cq W_Iq (HI heads
    of DI); kI = LayerNorm(u W_Ik) (eps 1e-6, weight and bias); both roped
    on their FIRST `rope` columns with the layer's table; w = u W_Iw /
    sqrt(HI) / sqrt(DI).
  * A SLIDING layer ("S": 64 heads published, rq 1,024, c 1,024, 192 + 64 |
    128, theta 50,000) allows the last `attn_window` keys, the query's own
    included (513 = 512 behind and itself); no indexer.
  * Layer 0's MLP is dense SwiGLU; the others sparse: s = sigmoid(u W_r)
    over ALL the router's outputs, chosen = top-k of s + b (one group), w =
    s[chosen] / (sum + 1e-20), scaled by `routed_scaling_factor`; SwiGLU
    experts plus one ungated shared expert.

Departures, each forced by the cut to one chip (model-configs guide,
section 4) and made in the program and here alike: the HEADS are a rank's
share (`cfg.n_q_heads`, `cfg.swa_n_heads` held; a layer's attention output
is this rank's partial o_proj sum), the EXPERTS too (`cfg.n_experts` held
of `cfg.router_width`; the routed sum's held part plus the shared expert),
the vocabulary is the slice the head holds.  Nothing stands in for absent
ranks.  The vision and audio towers and the multi-token-prediction module
are not modelled.

It reads the ENGINE'S weights (bf16, stacked under "blocks": a kind's
leaves over the scanned layers of the kind, the leading dense layer's under
`dense_*`, a sliding layer's under `sw_*`, the indexer's matrices flat) and
upcasts them, so a difference from the system is one in the arithmetic.

TOLERANCE lives in the configuration's file (`benchmark.tolerance`, with
its reasons).  `check_generator` builds a `GeneratorEngine` over the same
weights, runs ITS static decode program in the shape of the cell's traffic
(two prompts cut from the sequence, each a group of four rows of the 8
slots) and holds what it left in its caches — latent rows,
ring rows in ring order, index keys — to this reference over the tokens it
sampled, and the program's own selection (its indexer, its types, on this
reference's layer input) to this reference's (`SelectFlips`).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import files
from benchmark.references.qwen2 import PAD_TO, _head_chunk, _rotate_half

_TOL = files.load_json("configs", "dots3-note-prev-l5-e8-h8.json")[
    "benchmark"]["tolerance"]
TOLERANCE = {k: _TOL[k] for k in ("mean_abs", "max_abs")}
TOLERANCE_FP32 = {k: _TOL["fp32"][k] for k in ("mean_abs", "max_abs")}
ROWS_TOLERANCE = dict(_TOL["rows"])
ROWS_TOLERANCE_FP32 = dict(_TOL["fp32"]["rows"])

# `lower="lower"` computes what the tolerance has to refuse: the latent
# rows and the index keys (what the caches keep) rounded to 8 bits (e4m3)
# and the router's scores to bfloat16, each a precision below what the
# configuration states.  One alone: "lower:router", "lower:cache".
LOWER_PRECISION = "lower"
_LOWER = {"router": (8, 7), "cache": (4, 3)}  # (exponent, mantissa) bits
# `fault=` computes the model with ONE part of its mathematics wrong.  The
# first three are the controls of the configuration's three assumptions,
# the fourth the fault the cell exists to catch.
FAULTS = (
    "no_rescale",  # a_q = a_kv = 1
    "gate_on_raw_input",  # the gate reads x, not n1(x)
    "no_index_rope",  # qI and kI not roped
    "dense_read",  # index_topk ignored: every visible key
    "window_512",  # 511 behind and itself
    "no_gate",  # the heads' outputs as they are
    "index_no_relu",  # I = sum_j w (qI . kI)
)
CHECK_SLOTS = 8
CHECK_NEW = 256
QUERY_BLOCK = 256  # queries a block of the attention and of the selection
INDEX_NORM_EPS = 1e-6  # the index keys' LayerNorm (the configuration's `assumed`)
_DENSE, _SW = "dense_", "sw_"


def _lower(x, lower, part):
    if lower is None:
        return x
    _, _, only = lower.partition(":")
    if only and only != part:
        return x
    return jax.lax.reduce_precision(x, *_LOWER[part])


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _kind(cfg, l):
    return cfg.window_pattern[l]


def _layer_weights(blocks, l, cfg):
    """Layer l's leaves under plain names, fp32 (expert stacks as they
    are): the leading layers' from `dense_*`, a sliding layer's mixer from
    `sw_*`, each by its index among the layers that own the leaf."""
    k, pattern = cfg.first_k_dense, cfg.window_pattern
    lead = l < k
    kind = pattern[l]
    span = pattern[:l] if lead else pattern[k:l]
    n_kind = span.count(kind)  # earlier layers of this kind in the stack
    n_any = len(span)
    out = {}
    for name, w in blocks.items():
        if name.startswith(_DENSE) != lead:
            continue
        ours = name[len(_DENSE):] if lead else name
        if ours.startswith(_SW):
            if kind != "S":
                continue
            ours, i = ours[len(_SW):], n_kind
        elif ours in ("ln1", "ln2") or ours in _MLP_LEAVES:
            i = n_any
        else:  # a full layer's mixer and indexer
            if kind != "F":
                continue
            i = n_kind
        # the MLP's matrices (a dense layer's, the experts' stacks) stay
        # as they are: `_swiglu` upcasts what it multiplies by
        big = ours in ("wg", "wu", "wd")
        out[ours] = w[i] if big else w[i].astype(jnp.float32)
    return out


_MLP_LEAVES = (
    "wg", "wu", "wd", "router", "router_bias", "ws_g", "ws_u", "ws_d")


def _sizes(cfg, kind):
    """(heads held, rq, c, nope, rope, v, theta) of a kind of layer."""
    if kind == "F":
        return (cfg.n_q_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.rope_theta)
    return (cfg.swa_n_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
            cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
            cfg.swa_v_head_dim, cfg.window_rope_theta)


def _rope(x, theta, width):
    """Rotate-half rope at positions 0..T-1 on the first `width` columns
    of x [T, ..., d]."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), width)
    head = x[..., :width]
    head = head * jnp.cos(ang) + _rotate_half(head) * jnp.sin(ang)
    return jnp.concatenate([head, x[..., width:]], axis=-1)


def _blocks(t):
    qb = max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)
    return qb, t // qb


def _selection(u, cq, w, cfg, fault, lower):
    """-> (allowed [T, T] bool: the keys each query of a full layer reads,
    index keys [T, DI])."""
    t = u.shape[0]
    hi, di, k = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk
    rope = cfg.qk_rope_head_dim
    qi = (cq @ w["idx_q"].reshape(cfg.q_lora_rank, hi * di)).reshape(t, hi, di)
    ki = _layer_norm(
        u @ w["idx_k"].reshape(cfg.hidden_dim, di), w["idx_k_norm"],
        w["idx_k_norm_b"], INDEX_NORM_EPS)
    if fault != "no_index_rope":
        qi = _rope(qi, cfg.rope_theta, rope)
        ki = _rope(ki, cfg.rope_theta, rope)
    ki = _lower(ki, lower, "cache")
    wt = (u @ w["idx_w"].reshape(cfg.hidden_dim, hi)) * hi**-0.5 * di**-0.5
    qb, nb = _blocks(t)
    pos = jnp.arange(t)

    def block(i):
        at = i * qb + jnp.arange(qb)
        visible = pos[None, :] <= at[:, None]
        if fault == "dense_read" or t <= k:
            return visible
        q = jax.lax.dynamic_slice_in_dim(qi, i * qb, qb, 0)
        wq = jax.lax.dynamic_slice_in_dim(wt, i * qb, qb, 0)
        s = jnp.einsum("qjd,sd->qjs", q, ki)
        if fault != "index_no_relu":
            s = jax.nn.relu(s)
        score = jnp.where(visible, jnp.einsum("qjs,qj->qs", s, wq), -jnp.inf)
        _, top = jax.lax.top_k(score, k)  # equal scores: the lower position
        chosen = jnp.zeros((qb, t), bool).at[
            jnp.arange(qb)[:, None], top].set(True)
        return chosen & visible

    return jax.lax.map(block, jnp.arange(nb)).reshape(t, t), ki


def _attention(x, u, w, cfg, kind, fault=None, lower=None):
    """One latent-attention layer over one sequence.  x: the layer's input
    [T, D], u = n1(x) -> (its output [T, D], what a cache keeps: the rows
    [T, c + rope] and, a full layer, the index keys [T, DI]; the keys each
    query read [T, T] bool)."""
    t = u.shape[0]
    hq, rq, c, nope, rope, vd, theta = _sizes(cfg, kind)
    a_q = a_kv = 1.0
    if cfg.latent_rescale and fault != "no_rescale":
        a_q, a_kv = (cfg.hidden_dim / rq) ** 0.5, (cfg.hidden_dim / c) ** 0.5
    cq = _rms_norm(u @ w["wq_a"], w["q_a_norm"], cfg.rms_norm_eps) * a_q
    q = (cq @ w["wq_b"]).reshape(t, hq, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], theta, rope)
    kv = u @ w["wkv_a"]
    ckv = _rms_norm(kv[:, :c], w["kv_a_norm"], cfg.rms_norm_eps) * a_kv
    k_pe = _rope(kv[:, c:], theta, rope)
    ckv, k_pe = _lower(ckv, lower, "cache"), _lower(k_pe, lower, "cache")
    k_nope = (ckv @ w["wk_b"]).reshape(t, hq, nope)
    v = (ckv @ w["wv_b"]).reshape(t, hq, vd)
    pos = jnp.arange(t)
    ikeys = None
    if kind == "F":
        if cfg.index_topk:
            allowed, ikeys = _selection(u, cq, w, cfg, fault, lower)
        else:
            allowed = pos[None, :] <= pos[:, None]
    else:
        window = cfg.attn_window - (fault == "window_512")
        allowed = (pos[None, :] <= pos[:, None]) & (
            pos[:, None] - pos[None, :] < window)
    qb, nb = _blocks(t)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb, 0)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * qb, qb, 0)
        ok = jax.lax.dynamic_slice_in_dim(allowed, i * qb, qb, 0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qp, k_pe)) * (nope + rope) ** -0.5
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(t, hq, vd)
    if cfg.attn_gate_headwise and fault != "no_gate":
        src = x if fault == "gate_on_raw_input" else u
        o = o * jax.nn.sigmoid(src @ w["hgate"])[..., None]
    rows = jnp.concatenate([ckv, k_pe], axis=-1)
    return o.reshape(t, hq * vd) @ w["wo"], (rows, ikeys), allowed


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


def _swiglu(h, g, u, d):
    """SwiGLU in blocks of tokens (13 k tokens by 13,824 is 0.7 GB)."""
    qb, nb = _blocks(h.shape[0])
    f32 = jnp.float32
    g, u, d = g.astype(f32), u.astype(f32), d.astype(f32)
    return jax.lax.map(
        lambda x: (jax.nn.silu(x @ g) * (x @ u)) @ d,
        h.reshape(nb, qb, -1)).reshape(h.shape[0], -1)


def _moe(h, w, cfg, lower=None):
    """The held experts' part of the routed sum, one expert at a time,
    plus the ungated shared expert."""
    gates = _route(h, w, cfg, lower)
    held = gates[:, cfg.expert_offset: cfg.expert_offset + cfg.n_experts]

    def one(acc, xs):
        g, u, d, wt = xs
        return acc + wt[:, None] * _swiglu(h, g, u, d), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (w["wg"], w["wu"], w["wd"], held.T))
    return out + _swiglu(h, w["ws_g"], w["ws_u"], w["ws_d"])


def _layer(x, blocks, l, cfg, fault=None, lower=None):
    """Decoder layer l (a Python int) over one sequence.  x: [T, D] fp32 ->
    (x, what a cache keeps, n1(x), the keys each query read)."""
    w = _layer_weights(blocks, l, cfg)
    u = _rms_norm(x, w["ln1"], cfg.rms_norm_eps)
    attn, kept, allowed = _attention(
        x, u, w, cfg, _kind(cfg, l), fault, lower)
    x = x + attn
    h = _rms_norm(x, w["ln2"], cfg.rms_norm_eps)
    if l < cfg.first_k_dense:
        return x + _swiglu(h, w["wg"], w["wu"], w["wd"]), kept, u, allowed
    return x + _moe(h, w, cfg, lower), kept, u, allowed


def _hidden_and_kept(params, cfg, tokens, fault=None, lower=None, flips=None):
    """-> ([T, D] fp32 hidden states after the final norm, every layer's
    (rows, index keys or None)).  `flips`: a function (l, u, allowed) the
    full layers' normed input and selection are handed to."""
    layer = jax.jit(_layer, static_argnums=(2, 3, 4, 5))
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    kept = []
    for l in range(cfg.n_layers):
        x, k, u, allowed = layer(x, params["blocks"], l, cfg, fault, lower)
        kept.append(k)
        if flips is not None and _kind(cfg, l) == "F" and cfg.index_topk:
            flips(l, u, allowed)
        del u, allowed
    x = _rms_norm(x, params["final_ln"].astype(jnp.float32), cfg.rms_norm_eps)
    return x, kept


def final_hidden(params, cfg, tokens, fault=None, lower=None):
    """[T, D] fp32 hidden states after the final norm; differentiable in
    `params` (the gradient test's reference)."""
    return _hidden_and_kept(params, cfg, tokens, fault, lower)[0]


def logits(params, cfg, tokens, fault=None, lower=None):
    """[T, V] fp32 logits over the head's slice of the vocabulary (small
    sizes: tests)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(
            params, cfg, jnp.asarray(tokens, jnp.int32), fault, lower)
        return x @ params["lm_head"].astype(jnp.float32)


def _padded(tokens, to=None):
    n = len(tokens)
    to = to or -(-n // PAD_TO) * PAD_TO
    out = np.zeros(to, np.int32)
    out[:n] = np.asarray(tokens)
    return out


def next_token_logprobs(params, cfg, tokens, fault=None, lower=None):
    """log p(tokens[t + 1] | tokens[: t + 1]) for t < T - 1, as a numpy
    fp32 array of length T - 1, teacher-forced over one sequence — all NaN
    (so that the run is not `correct`) where `check_generator` refuses what
    the generator's static program leaves in its caches (the reference
    proper only: `fault` and `lower` compute a control).

    The sequence is padded at its END to a multiple of PAD_TO so that a
    few compiled shapes serve every seed; attention is causal, so the
    padding changes nothing before it and its own outputs are dropped."""
    n = len(tokens)
    out, _ = _next_token_logprobs(params, cfg, _padded(tokens), fault, lower)
    print(f"[benchmark] dots3_note reference, {n} tokens, heads "
          f"{cfg.n_q_heads} / {cfg.swa_n_heads} of "
          f"{cfg.n_q_heads * cfg.head_share} / "
          f"{cfg.swa_n_heads * cfg.head_share}, experts "
          f"[{cfg.expert_offset}, {cfg.expert_offset + cfg.n_experts}) of "
          f"{cfg.router_width}", file=sys.stderr, flush=True)
    out = out[: n - 1]
    if fault is not None or lower is not None:  # a control
        return out
    readings, problems = _checked(params, cfg, tokens)
    print(f"[benchmark] dots3_note generator check {readings} "
          f"{problems or 'ok'}", file=sys.stderr, flush=True)
    return np.full_like(out, np.nan) if problems else out


_CHECKS = {}  # what `check_generator` was last asked -> what it said


def _checked(params, cfg, tokens):
    """`check_generator`, once for the sequences that ask the same of it:
    the compared responses to one prompt cut the same prompts from it, and
    the program samples from one key."""
    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) // 9)
    key = (id(params["embed"]), cfg, tokens[: len(tokens) - n_new].tobytes())
    if key not in _CHECKS:
        _CHECKS.clear()
        _CHECKS[key] = check_generator(params, cfg, tokens)
    return _CHECKS[key]


# --------------------------------------------------------------------------
# What the generator's static program leaves in its caches, and what its
# indexer selects, against this reference
# --------------------------------------------------------------------------

def _engine(params, cfg):
    """A `GeneratorEngine` over `params` as they lie (no copy), on their
    own mesh, built as a worker builds the timed one.  Built anew for every
    call and dropped with its compiled program."""
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = getattr(params["embed"].sharding, "mesh", None)
    if mesh is None:
        mesh = make_mesh(
            ParallelConfig.from_str("d1"), sorted(params["embed"].devices()))
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=CHECK_SLOTS, donation_safe_swap=False)


def generator_rollouts(params, cfg, tokens, slots=(0, CHECK_SLOTS - 1)):
    """The static decode program of a `GeneratorEngine`, once, in the shape
    of the cell's traffic: TWO prompts cut from `tokens` (a quarter of the
    sequence less its new tokens, and all of it: in the cell 3,264 and
    13,056, both past `index_topk` and past the ring), each asked at half
    of the CHECK_SLOTS rows as one group — prefilled once and LANDED at the
    group's other rows where the prefill goes in waves, as the timed
    program lands them — -> for each slot of `slots` (its tokens, prompt
    and sampled ones; the log-probs the program returned for the sampled
    ones; for every layer, in layer order, (the positions of the sequence
    the layer's cache still holds, its rows for them, its index keys for
    them or None), in the cache's type: every position of a full layer,
    the last `ring` of a sliding layer, read at slot mod ring).  The first
    slot is the short prompt's prefilled row, the last a landed copy of the
    long one's."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.packing import decode_bucket_len as bucket_len

    tokens = np.asarray(tokens, np.int32)
    n_new = min(CHECK_NEW, len(tokens) // 9)
    rest = len(tokens) - n_new
    half = CHECK_SLOTS // 2
    prompts = [tokens[: max(1, rest // 4)]] * half + [tokens[: rest]] * half
    eng = _engine(params, cfg)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=n_new),
        jax.random.PRNGKey(64), with_cache=True,
        src=[0] * half + [half] * half)
    sp = bucket_len(max(len(p) for p in prompts))
    out = []
    for r in slots:
        n, gl = len(prompts[r]), int(gen_len[r])
        first, end = sp - n, sp + gl  # the row's slots of the cache
        layers, n_full, n_ring = [], 0, 0
        for kind in cfg.window_pattern:
            if kind == "F":
                at = np.arange(first, end)
                rows = cache.latent[n_full, r, first:end]
                keys = None if cache.ikeys is None else (
                    cache.ikeys[n_full, r, first:end])
                n_full += 1
            else:
                ring = cache.wlatent.shape[2]
                at = np.arange(max(first, end - ring), end)
                rows, keys = cache.wlatent[n_ring, r, at % ring], None
                n_ring += 1
            layers.append((at - first, rows, keys))
        out.append((
            np.concatenate([prompts[r], toks[r, :gl]]), logps[r, :gl], layers))
    return out


def _rel_err(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(want, np.float64)
    return float(
        np.sqrt(np.square(got - want).sum())
        / (np.sqrt(np.square(want).sum()) + np.finfo(np.float32).tiny))


def rows_readings(layers, kept, cfg):
    """Over the layers of one sequence, |R - R_ref|_F / |R_ref|_F of what a
    layer's cache holds.  `rows_rel_err_first` / `ikeys_rel_err_first`:
    layer 0's latent rows and index keys, whose input is the embedding
    alone — no selection, no routed expert upstream, so no flipped choice
    adds to the arithmetic's own error: they read the precision of the
    projections and of what the caches keep.  `rows_rel_err_max`: every
    layer's rows and index keys, ring or cache.  `by_layer`: each layer's
    (rows, index keys), reported and not limited."""
    by_layer = []
    for (at, rows, keys), (ref_rows, ref_keys) in zip(layers, kept):
        by_layer.append((
            _rel_err(rows, np.asarray(ref_rows)[at]),
            None if keys is None else _rel_err(keys, np.asarray(ref_keys)[at]),
        ))
    return {
        "rows_rel_err_first": by_layer[0][0],
        "ikeys_rel_err_first": by_layer[0][1] or 0.0,
        "rows_rel_err_max": max(
            e for pair in by_layer for e in pair if e is not None),
        "by_layer": [
            [round(e, 5) if e is not None else None for e in pair]
            for pair in by_layer],
    }


_READINGS = (
    "rows_rel_err_first", "ikeys_rel_err_first", "rows_rel_err_max",
    "select_keys_flipped",
)


def rows_problems(readings, tol):
    """What of the readings lies above `tol`, as text."""
    return [
        f"{name} {readings[name]:.3g} above {tol[name]}"
        for name in _READINGS if not readings[name] <= tol[name]
    ]


def program_selection(params, cfg, l, u):
    """The keys the PROGRAM'S indexer selects for the queries of one
    sequence, [T, T] bool: its projections, types and top-k
    (`models/latent_select.py`), over full layer l's weights as the engine
    holds them, fed this reference's normed input u [T, D]."""
    from areal_tpu.models import latent_select as ls
    from areal_tpu.ops.norms import rms_norm, rope_cos_sin

    blocks = params["blocks"]
    lead = l < cfg.first_k_dense
    i = cfg.window_pattern[: l].count("F") if lead else (
        cfg.window_pattern[cfg.first_k_dense: l].count("F"))
    blk = {
        n.removeprefix(_DENSE): w[i] for n, w in blocks.items()
        if n.startswith(_DENSE) == lead
        and n.removeprefix(_DENSE) in ls.INDEX_LEAVES + ("wq_a", "q_a_norm")
    }
    g = ls.full_geom(cfg)
    dtype = blk["wq_a"].dtype
    h = u.astype(dtype)[None]
    t = h.shape[1]
    cos, sin = rope_cos_sin(
        jnp.arange(t)[None], cfg.qk_rope_head_dim, cfg.rope_theta)
    c_q = rms_norm(
        h @ blk["wq_a"], blk["q_a_norm"].astype(jnp.float32) * g.q_alpha,
        g.eps)
    qi, ki, w = ls.index_projections(cfg, h, c_q, blk, cos, sin)
    if t <= cfg.index_topk:
        return jnp.tril(jnp.ones((t, t), bool))
    qb, n_blocks = _blocks(t)

    def block(i):  # the program's selection, a block of queries at a time
        at = i * qb + jnp.arange(qb)
        return ls.select_block(
            cfg, jax.lax.dynamic_slice_in_dim(qi, i * qb, qb, 1),
            jax.lax.dynamic_slice_in_dim(w, i * qb, qb, 1), ki,
            (at[:, None] >= jnp.arange(t)[None, :])[None])[0]

    return jax.lax.map(block, jnp.arange(n_blocks)).reshape(t, t)


def flips_between(theirs, ours, k, n):
    """Two selections [T, T] bool over the queries [k, n) of one sequence
    -> (queries whose SET of keys differs, the keys that differ summed over
    the queries: |A - B|, each set k keys)."""
    differ = theirs[k:n, :n] != ours[k:n, :n]
    return (int(jnp.sum(jnp.any(differ, axis=-1))),
            int(jnp.sum(differ)) // 2)


class SelectFlips:
    """Over the (token, full layer) selections past `index_topk` visible
    keys among the first n tokens of one sequence, the program's indexer
    against this reference's, both fed this reference's layer input; a
    `flips` of `_hidden_and_kept`.  `sets`: the share whose SET of keys
    differs at all — a top-k of 2,048 among 13 k flips at its K-th score on
    any rounding, so it reads near 1 and is reported, not limited; `keys`:
    the share of the selected KEYS that differ, which is limited: a guard
    on the indexer's wiring and on the precision its keys are kept in."""

    def __init__(self, params, cfg, n):
        self.params, self.cfg, self.n = params, cfg, n
        self.differ_sets = self.differ_keys = self.rows = 0
        self.select = jax.jit(program_selection, static_argnums=(1, 2))

    def __call__(self, l, u, allowed):
        k, n = self.cfg.index_topk, self.n
        if n <= k:
            return
        a, b = flips_between(
            self.select(self.params, self.cfg, l, u), allowed, k, n)
        self.differ_sets += a
        self.differ_keys += b
        self.rows += n - k

    @property
    def sets(self):
        return self.differ_sets / max(self.rows, 1)

    @property
    def keys(self):
        return self.differ_keys / max(self.rows * self.cfg.index_topk, 1)


def check_generator(params, cfg, tokens):
    """(readings: `rows_readings` of what the generator's own program left
    in its caches, the largest over the compared slots; `SelectFlips` over
    the longest compared slot; the mean and the largest |log-prob(program) -
    log-prob(reference)| over the tokens it sampled, reported and not
    limited here; `rows_problems` under the backend's limits)."""
    readings, diffs = {}, []
    rollouts = generator_rollouts(params, cfg, tokens)
    to = len(_padded(rollouts[-1][0]))  # one compiled length for both
    for seq, logps, layers in rollouts:
        n = len(seq)
        # the selections of the longest compared slot, in the same pass
        flips = SelectFlips(params, cfg, n) if seq is rollouts[-1][0] else None
        want, kept = _next_token_logprobs(
            params, cfg, _padded(seq, to), flips=flips)
        for k, v in rows_readings(layers, kept, cfg).items():
            if k == "by_layer":
                readings.setdefault(k, []).append(v)
            else:
                readings[k] = max(v, readings.get(k, 0.0))
        first = n - len(logps)  # position t scores token t + 1
        diffs.append(np.abs(logps - want[first - 1: n - 1]))
        del kept
    readings["select_flips"], readings["select_keys_flipped"] = (
        flips.sets, flips.keys)
    diffs = np.concatenate(diffs)
    readings.update(
        logprob_mean_abs=float(diffs.mean()), logprob_max_abs=float(diffs.max()),
        n_tokens=int(diffs.size))
    cpu = jax.default_backend() == "cpu"
    return readings, rows_problems(
        readings, ROWS_TOLERANCE_FP32 if cpu else ROWS_TOLERANCE)


def _next_token_logprobs(
        params, cfg, tokens, fault=None, lower=None, flips=None):
    """-> (log-probs [T - 1], every layer's (rows, index keys or None))."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head_chunk = jax.jit(_head_chunk, static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x, kept = _hidden_and_kept(params, cfg, tokens, fault, lower, flips)
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
        return np.asarray(sum(tl_all) - lse, np.float32), kept

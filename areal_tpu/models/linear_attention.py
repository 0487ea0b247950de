"""Gated DeltaNet: the linear-attention mixer of a hybrid layer pattern
(qwen3_next: three of these to one softmax-attention block).

Per value head, with a state S [d_k, d_v] kept in fp32:

    S <- exp(g_t) S                      (decay, g_t <= 0)
    d  = beta_t (v_t - S^T k_t)          (delta rule: what S gets wrong)
    S <- S + k_t d^T
    o_t = S^T q_t

before it a causal depthwise conv + SiLU over the (q, k, v) channels, L2-
normalised q and k, and after it a per-head RMSNorm gated by silu(z).
beta_t = sigmoid(b_t), or 2 sigmoid(b_t) with `cfg.linear_neg_eigval`
(olmo_hybrid: I - beta k k^T may then flip a direction's sign); both forms
below take beta as it comes.

Two forms of the same recurrence:
- `linear_attn_forward` (training, `forward`, prefill): the chunked (WY)
  form over chunks of CHUNK tokens.  The gradient program and `forward` on
  one TPU device take the Pallas sweep `gdn_chunk`
  (`ops/pallas/delta_chunk.py`): a chunk's [C, C] blocks and the carried
  state stay in VMEM, q and k at their own 16 heads, and the backward is a
  reverse sweep of its own.  Prefill (`with_state`: it reads the final
  state), a mesh of more devices and every backend that is no TPU take
  `gated_delta_chunked`, the same rule as `jnp` ops and the sweep's oracle:
  everything that does not depend on the carried state for all chunks at
  once, then a `lax.scan` that carries S through [C, d] x [d, d] matmuls.
- `linear_attn_step` (decode): one token against the carried S and the
  conv's last K-1 inputs.  The recurrence itself has one form per backend:
  on a TPU the Pallas kernel `gdn_delta_step` (`ops/pallas/delta_step.py`),
  which keeps a head's [d_k, d_v] tile in VMEM for S^T k, S^T q and the
  update and so reads the state once — at 128 x 128 and at 96 x 192 alike
  (`delta_step.fits`: rows in whole sublane tiles, columns in whole or
  half lane tiles); elsewhere (CPU, toy head widths) the same fp32
  arithmetic as `jnp` ops, which is also the kernel's oracle.
  The code picks by what it can see (`row_kernel_form`); there is no
  switch.

Packed rows: S and the conv restart at every segment start (attention
masks by segment; a recurrence has to be told).  Inside a chunk that is a
same-segment mask on the pairwise decays; across chunks the carried state
is dropped for every token whose segment is not the one the previous chunk
ended in.  Pads (segment 0) are a segment like any other: what they
compute is never read.

Parameters (leaves of `params["blocks"]`, stacked [n_linear_layers, ...]):
    la_wqkv  [D, 2*key_dim + value_dim]   q | k | v, heads contiguous
    la_wz    [D, value_dim]               the output gate
    la_wba   [D, 2*n_v_heads]             b (-> beta) | a (-> g)
    la_conv  [K, 2*key_dim + value_dim]   depthwise taps, oldest first
    la_A_log, la_dt_bias [n_v_heads]
    la_norm  [d_v]                        gated norm's weight (plain, not 1+w)
    la_wo    [value_dim, D]
"""

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.branches import (
    Branch,
    HybridLayoutError,
    Refusal,
    nbytes,
    segment_starts,
)
from areal_tpu.models.config import ModelConfig

CHUNK = 64
Params = Dict[str, jax.Array]


def init_linear_attn(cfg: ModelConfig, key: jax.Array, n: int, dense) -> Params:
    """`n` layers' leaves.  `A_log` and `dt_bias` as the published module
    initialises them (A uniform on (0, 16), dt_bias ones), the gated norm
    at one; `dense(key, shape, fan_in)` is the caller's matrix init."""
    D, C, VD = cfg.hidden_dim, cfg.linear_conv_dim, cfg.linear_value_dim
    hv, K = cfg.linear_n_v_heads, cfg.linear_conv_kernel
    ks = jax.random.split(key, 6)
    a = jax.random.uniform(ks[5], (n, hv), jnp.float32, 1e-3, 16.0)
    return {
        "la_wqkv": dense(ks[0], (n, D, C), D),
        "la_wz": dense(ks[1], (n, D, VD), D),
        "la_wba": dense(ks[2], (n, D, 2 * hv), D),
        "la_conv": dense(ks[3], (n, K, C), K),
        "la_A_log": jnp.log(a).astype(cfg.dtype),
        "la_dt_bias": jnp.ones((n, hv), cfg.dtype),
        "la_norm": jnp.ones((n, cfg.linear_v_head_dim), cfg.dtype),
        "la_wo": dense(ks[4], (n, VD, D), VD),
    }


LINEAR_LEAVES = (
    "la_wqkv", "la_wz", "la_wba", "la_conv", "la_A_log", "la_dt_bias",
    "la_norm", "la_wo",
)


def _l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _gates(ba: jax.Array, blk: Params, cfg: ModelConfig):
    """beta = sigmoid(b) — times 2 with `cfg.linear_neg_eigval`, so that I -
    beta k k^T has an eigenvalue in (-1, 1) — and g = -exp(A_log)
    softplus(a + dt_bias), fp32."""
    hv = cfg.linear_n_v_heads
    with jax.named_scope("gates"):
        ba = ba.astype(jnp.float32)
        b, a = ba[..., :hv], ba[..., hv:]
        beta = jax.nn.sigmoid(b)
        if cfg.linear_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(blk["la_A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + blk["la_dt_bias"].astype(jnp.float32)
        )
        return beta, g


def _split_heads(qkv: jax.Array, cfg: ModelConfig, repeat: bool = True):
    """conv output [..., C] -> q, k [..., hv, dk] (normalised, q scaled, each
    key head repeated for its value heads; `repeat` False: [..., hk, dk], the
    key heads as they are) and v [..., hv, dv], all fp32."""
    hk, hv = cfg.linear_n_k_heads, cfg.linear_n_v_heads
    dk, dv, kd = cfg.linear_k_head_dim, cfg.linear_v_head_dim, cfg.linear_key_dim
    lead = qkv.shape[:-1]
    qkv = qkv.astype(jnp.float32)
    q = _l2norm(qkv[..., :kd].reshape(*lead, hk, dk)) * dk**-0.5
    k = _l2norm(qkv[..., kd: 2 * kd].reshape(*lead, hk, dk))
    v = qkv[..., 2 * kd:].reshape(*lead, hv, dv)
    rep = hv // hk
    if repeat and rep > 1:  # key head i serves value heads [i*rep, (i+1)*rep)
        q = jnp.repeat(q, rep, axis=-2)
        k = jnp.repeat(k, rep, axis=-2)
    return q, k, v


def _out(o: jax.Array, z: jax.Array, blk: Params, cfg: ModelConfig):
    """out_proj(w * rmsnorm(o) * silu(z)), the norm per head over d_v."""
    with jax.named_scope("out_norm_proj"):
        lead = o.shape[:-2]
        of = o.astype(jnp.float32)
        var = jnp.mean(jnp.square(of), axis=-1, keepdims=True)
        of = of * jax.lax.rsqrt(var + cfg.rms_norm_eps)
        of = (blk["la_norm"].astype(jnp.float32) * of).astype(z.dtype)
        zf = z.reshape(*lead, cfg.linear_n_v_heads, cfg.linear_v_head_dim)
        of = of.astype(jnp.float32) * jax.nn.silu(zf.astype(jnp.float32))
        y = of.astype(z.dtype).reshape(*lead, cfg.linear_value_dim)
        return y @ blk["la_wo"]


def causal_conv(
    x: jax.Array, taps: jax.Array, segment_ids: jax.Array
) -> jax.Array:
    """Depthwise causal conv over packed rows: out[t] = sum_j taps[j] *
    x[t - (K-1) + j], an input counted only where it lies in t's own
    segment.  x [B, S, C], taps [K, C] (oldest first), fp32 out."""
    kk = taps.shape[0]
    xf = x.astype(jnp.float32)
    tf = taps.astype(jnp.float32)
    out = xf * tf[kk - 1]
    for back in range(1, kk):
        xs = jnp.pad(xf[:, :-back], ((0, 0), (back, 0), (0, 0)))
        ss = jnp.pad(
            segment_ids[:, :-back], ((0, 0), (back, 0)), constant_values=-1
        )
        same = (ss == segment_ids)[..., None]
        out = out + jnp.where(same, xs, 0.0) * tf[kk - 1 - back]
    return out


def conv_kernel_form(
    channels: int, taps: int, kernel=None, with_state=False
) -> bool:
    """Whether a mixer's causal conv + activation runs on the Pallas
    operator `causal_conv_act` (`ops/pallas/causal_conv.py`; `causal_conv`
    is its oracle and every other caller's form): by what the code can see
    (`flash_attention.row_kernel_form`: a TPU backend; a bool forces
    either) where the channels are whole 128-lane tiles, on ONE device —
    `kernel` a Mesh: a `pallas_call` is one device's program — and without
    `with_state`: prefill's program, and what a generator samples from it,
    stay `causal_conv`'s.  The one chooser of the three kinds that run the
    conv (Gated DeltaNet, Mamba-2, the gated short convolution)."""
    from areal_tpu.ops.pallas import causal_conv as conv_kernels
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form

    fits = conv_kernels.fits(channels, taps)
    use_kernel, mesh = row_kernel_form(kernel, fits)
    return use_kernel and fits and mesh is None and not with_state


def conv_act(x, taps, bias, segment_ids, on_kernel: bool, act="silu"):
    """act(causal_conv(x, taps, segment_ids) + bias), fp32 [B, S, C]: a
    mixer's conv in the form `conv_kernel_form` chose — `on_kernel`: ONE
    operator with its own backward; else the `jnp` ops.  bias [C] | None,
    `act` "silu" | "identity"."""
    if on_kernel:
        from areal_tpu.ops.pallas.causal_conv import causal_conv_act

        return causal_conv_act(x, taps, bias, segment_ids, act)
    out = causal_conv(x, taps, segment_ids)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out) if act == "silu" else out


def conv_tail(x: jax.Array, segment_ids: jax.Array, kk: int) -> jax.Array:
    """The last K-1 conv inputs of each row, zero where they belong to
    another segment than the row's last token: what `linear_attn_step`
    carries on from.  [B, K-1, C]."""
    tail = x[:, -(kk - 1):]
    seg = segment_ids[:, -(kk - 1):]
    return jnp.where((seg == segment_ids[:, -1:])[..., None], tail, 0)


def conv_tail_at(
    x: jax.Array, segment_ids: jax.Array, last: jax.Array, kk: int
) -> jax.Array:
    """The K-1 conv inputs that end at each row's position `last` [B], zero
    where they lie before the row or in another segment than `last`'s:
    what a decode step carries on from, whichever side the row's pads lie
    on.  [B, K-1, C]."""
    pos = last[:, None] - jnp.arange(kk - 2, -1, -1)[None, :]  # [B, K-1]
    at = jnp.maximum(pos, 0)
    tail = jnp.take_along_axis(x, at[..., None], axis=1)
    seg = jnp.take_along_axis(segment_ids, at, axis=1)
    seg_last = jnp.take_along_axis(segment_ids, last[:, None], axis=1)
    return jnp.where(((pos >= 0) & (seg == seg_last))[..., None], tail, 0)


def gated_delta_chunked(
    q: jax.Array,  # [B, S, H, dk] fp32, normalised and scaled
    k: jax.Array,  # [B, S, H, dk] fp32, normalised
    v: jax.Array,  # [B, S, H, dv]
    g: jax.Array,  # [B, S, H] fp32 log-decay (<= 0)
    beta: jax.Array,  # [B, S, H]
    segment_ids: jax.Array,  # [B, S]
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """The gated delta rule over packed rows in chunked (WY) form ->
    (o [B, S, H, dv] fp32, final state [B, H, dk, dv] fp32: the state after
    each row's last token).

    Inside a chunk, with G the running sum of g and A the strictly lower
    triangle of (beta k k^T) * exp(G_i - G_j): U = (I + A)^-1 (beta v) and
    W = (I + A)^-1 (beta k exp(G)) are what the chunk would write given the
    state it starts from; the scan then needs only v_new = U - W S,
    o = (q exp(G)) S + (q k^T * decay) v_new and the state's update."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        # Neutral tokens (beta 0, g 0) of the last token's segment: the
        # state passes through them unchanged.
        def zpad(x):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

        q, k, v, g, beta = (zpad(x) for x in (q, k, v, g, beta))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge")
    n = (s + pad) // chunk

    def chunks(x):  # [B, S, H, ...] -> [B, H, N, C, ...]
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (
        chunks(x.astype(jnp.float32)) for x in (q, k, v, g, beta)
    )
    seg = segment_ids.reshape(b, 1, n, chunk)
    same = seg[..., :, None] == seg[..., None, :]  # [B, 1, N, C, C]
    idx = jnp.arange(chunk)
    tril = idx[:, None] >= idx[None, :]
    # The state a chunk starts from belongs to the segment the previous
    # chunk ended in; the first chunk starts from nothing.
    prev_last = jnp.concatenate(
        [jnp.full((b, 1, 1), -1, seg.dtype), seg[:, :, :-1, -1]], axis=2
    )
    carry_ok = (seg == prev_last[..., None]).astype(jnp.float32)  # [B,1,N,C]
    same_as_last = (seg == seg[..., -1:]).astype(jnp.float32)

    gc = jnp.cumsum(g, axis=-1)  # [B, H, N, C]
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(tril, diff, 0.0)) * (tril & same)
    kb = k * beta[..., None]
    a_mat = jnp.einsum("...id,...jd->...ij", kb, k) * decay
    a_mat = jnp.where(idx[:, None] > idx[None, :], a_mat, 0.0)
    g_in = jnp.exp(gc) * carry_ok  # decay of the incoming state per token
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * g_in[..., None]], axis=-1
    )
    sol = jax.scipy.linalg.solve_triangular(
        a_mat + jnp.eye(chunk, dtype=a_mat.dtype), rhs,
        lower=True, unit_diagonal=True,
    )
    u, w = sol[..., :dv], sol[..., dv:]
    qk = jnp.einsum("...id,...jd->...ij", q, k) * decay
    q_in = q * g_in[..., None]
    g_last = gc[..., -1:]
    k_out = k * (jnp.exp(g_last - gc) * same_as_last)[..., None]
    s_keep = jnp.exp(g_last[..., 0]) * carry_ok[..., -1]  # [B, H, N]

    def body(state, xs):
        u_i, w_i, qk_i, q_i, k_i, keep_i = xs
        v_new = u_i - w_i @ state
        o_i = q_i @ state + qk_i @ v_new
        state = state * keep_i[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_i, v_new
        )
        return state, o_i

    xs = tuple(
        jnp.moveaxis(x, 2, 0) for x in (u, w, qk, q_in, k_out, s_keep)
    )
    state, o = jax.lax.scan(
        body, jnp.zeros((b, h, dk, dv), jnp.float32), xs
    )
    o = jnp.moveaxis(o, 0, 2)  # [B, H, N, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, s + pad, h, dv)
    return o[:, :s], state


def chunk_kernel_form(cfg: ModelConfig, kernel=None, with_state=False) -> bool:
    """Whether `linear_attn_forward` runs the delta rule on the Pallas sweep
    `gdn_chunk`: by what the code can see (`flash_attention.
    row_kernel_form`: a TPU backend, head widths of whole 128-lane tiles; a
    bool forces either) on ONE device — `kernel` a Mesh: a `pallas_call`
    is one device's program — and without `with_state`: prefill is the one
    caller that reads the final state, and its three chunks a row give the
    sweep nothing to win."""
    from areal_tpu.ops.pallas import delta_chunk
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form

    use_kernel, mesh = row_kernel_form(kernel, delta_chunk.fits(
        cfg.linear_k_head_dim, cfg.linear_v_head_dim))
    return use_kernel and mesh is None and not with_state


def _conv_reads_made_input(cfg: ModelConfig) -> bool:
    """Whether `linear_attn_forward` puts the projection's output behind an
    optimization barrier, so that it is MADE before the conv reads it: on a
    TPU backend, where the heads are not whole 128-lane tiles of their own.
    There (30 heads of 96 x 192, 11,520 channels) XLA:TPU otherwise reads
    the conv's three shifted views straight off the projection's output, in
    windows of the row, and the first tokens of a window see the wrong rows
    behind them — with the rule on the sweep every 8th chunk of a 2,048-
    token row, every 7th of 4,096, every 5th of 8,192, with it on the `jnp`
    form the chunk at token 4,096 of an 8,192-token row and `la_conv`'s
    gradient 140 times off; with the barrier both forms read the plain
    reference's log-probs over the whole row, and at heads of 128 x 128
    (8,192 channels) the programs are right without it (my chip runs, PR
    59, PERF.md sections 6 and 7; rows under 1,536 never showed it)."""
    from areal_tpu.base.distributed import is_tpu_backend

    return is_tpu_backend() and bool(
        cfg.linear_k_head_dim % 128 or cfg.linear_v_head_dim % 128)


@jax.named_scope("layer/linear_attn")
def linear_attn_forward(
    h: jax.Array,  # [B, S, D] normed block input
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    with_state: bool = False,
    kernel=None,  # None | bool | Mesh: `flash_attention.row_kernel_form`
):
    """-> y [B, S, D]; `with_state` (prefill) adds the state after each
    row's last token [B, hv, dk, dv] fp32 and the conv's tail [B, K-1, C].

    The delta rule has one form per backend and caller
    (`chunk_kernel_form`): the Pallas sweep `gdn_chunk` in the gradient
    program and `forward` on one TPU device, `gated_delta_chunked`
    elsewhere; so has the conv + SiLU before it (`conv_kernel_form`)."""
    use_kernel = chunk_kernel_form(cfg, kernel, with_state)
    conv_kernel = conv_kernel_form(
        cfg.linear_conv_dim, cfg.linear_conv_kernel, kernel, with_state)
    with jax.named_scope("in_proj"):
        qkv = h @ blk["la_wqkv"]
        z = h @ blk["la_wz"]
        ba = h @ blk["la_wba"]
    # A kernel's operand is made before the kernel reads it.
    if not conv_kernel and _conv_reads_made_input(cfg):
        qkv = jax.lax.optimization_barrier(qkv)
    with jax.named_scope("conv"):
        conv = conv_act(qkv, blk["la_conv"], None, segment_ids, conv_kernel)
    beta, g = _gates(ba, blk, cfg)
    with jax.named_scope("delta_rule"):
        q, k, v = _split_heads(conv, cfg, repeat=not use_kernel)
        if use_kernel:
            from areal_tpu.ops.pallas import delta_chunk

            o = delta_chunk.gdn_chunk(q, k, v, g, beta, segment_ids)
        else:
            o, state = gated_delta_chunked(q, k, v, g, beta, segment_ids)
    y = _out(o, z, blk, cfg)
    if with_state:
        return y, state, conv_tail(qkv, segment_ids, cfg.linear_conv_kernel)
    return y


def delta_step_jnp(state, q, k, v, g, beta):
    """One token of the gated delta rule as `jnp` ops -> (state, o): state
    [B, hv, dk, dv] fp32, q, k [B, hv, dk], v [B, hv, dv], g, beta [B, hv].
    The path off a TPU backend and the oracle of `gdn_delta_step`."""
    # One pass reads S (S^T k and S^T q of the OLD state), one rewrites
    # it: o = S_new^T q with S_new = e^g S + k d^T.
    decay = jnp.exp(g)[..., None]  # [B, hv, 1]
    sk = jnp.sum(state * k[..., None], axis=-2)  # [B, hv, dv]
    sq = jnp.sum(state * q[..., None], axis=-2)
    d = beta[..., None] * (v - decay * sk)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    o = decay * sq + kq * d
    return state * decay[..., None] + k[..., None] * d[..., None, :], o


def step_kernel_form(cfg: ModelConfig, kernel=None):
    """Which form `linear_attn_step` runs the recurrence in -> (on the
    Pallas kernel `gdn_delta_step`, the mesh to `shard_map` it over or
    None): by what the code can see (`flash_attention.row_kernel_form`: a
    TPU backend, a head's tile one the kernel takes — `delta_step.fits`,
    128 x 128 and 96 x 192 among them, the toys' 12 x 24 not; a bool
    forces either)."""
    from areal_tpu.ops.pallas import delta_step
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form

    return row_kernel_form(kernel, delta_step.fits(
        cfg.linear_k_head_dim, cfg.linear_v_head_dim))


@jax.named_scope("layer/linear_attn")
def linear_attn_step(
    h: jax.Array,  # [B, 1, D]
    blk: Params,
    cfg: ModelConfig,
    states: jax.Array,  # [n_linear, B, hv, dk, dv] fp32, every linear layer's
    tails: jax.Array,  # [n_linear, B, K-1, C] the convs' last inputs
    li,  # this layer's index into both
    kernel=None,  # None | bool | Mesh: `flash_attention.row_kernel_form`
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token per row -> (y [B, 1, D], states, tails), layer
    `li` of both stepped in place.  The whole caches come in and go out so
    that the state's read and its write lie under this scope — an update
    made by the caller would be timed outside `layer/linear_attn` — and
    because that is what in place means on either backend:

    - on a TPU backend (a head's tile one `delta_step.fits` takes) the
      Pallas kernel `gdn_delta_step` takes the stack, reads layer `li`'s
      tiles where they lie, ONCE, and writes them where they lie (the
      output aliases the input, the layer is a prefetched scalar in both
      index maps): no slice, no `dynamic-update-slice`, no other layer's
      byte touched.  The stack keeps its shape [n_linear, B, hv, dk, dv]
      at every width: a d_v of 192 lies in HBM as 256 lanes, and the kernel
      moves those bytes once each way (PERF.md §6, PR 60);
    - elsewhere `delta_step_jnp`, the kernel's oracle: XLA fuses the
      update into the `dynamic-update-slice`, but the reduction that `d`
      needs is a fusion of its own, so on a TPU it streamed the state from
      HBM twice (PERF.md §6, PRs 42 and 59)."""
    from areal_tpu.ops.pallas import delta_step

    use_kernel, mesh = step_kernel_form(cfg, kernel)
    if not use_kernel:
        state = jax.lax.dynamic_index_in_dim(states, li, axis=0, keepdims=False)
    tail = jax.lax.dynamic_index_in_dim(tails, li, axis=0, keepdims=False)
    with jax.named_scope("in_proj"):
        x = h[:, 0]
        qkv = x @ blk["la_wqkv"]  # [B, C]
        z = x @ blk["la_wz"]
        ba = x @ blk["la_wba"]
    with jax.named_scope("conv"):
        taps = blk["la_conv"].astype(jnp.float32)
        window = jnp.concatenate([tail, qkv[:, None].astype(tail.dtype)], 1)
        conv = jax.nn.silu(
            jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), taps)
        )
        tails = jax.lax.dynamic_update_index_in_dim(
            tails, window[:, 1:], li, axis=0
        )
    beta, g = _gates(ba, blk, cfg)
    with jax.named_scope("delta_step"):
        q, k, v = _split_heads(conv, cfg)  # [B, hv, d]
        if use_kernel:
            if mesh is None:
                states, o = delta_step.gdn_delta_step(
                    states, li, q, k, v, g, beta)
            else:
                states, o = delta_step.gdn_delta_step_sharded(
                    states, li, q, k, v, g, beta, mesh)
        else:
            state, o = delta_step_jnp(state, q, k, v, g, beta)
            states = jax.lax.dynamic_update_index_in_dim(
                states, state, li, axis=0)
    y = _out(o, z, blk, cfg)
    return y[:, None], states, tails


# The kind's record (`models/branches.py`).


def _packed(ctx, h, blk):
    if not ctx.with_state:
        return linear_attn_forward(
            h, blk, ctx.cfg, ctx.segment_ids, kernel=ctx.row_kernel), {}
    out, state, tail = linear_attn_forward(
        h, blk, ctx.cfg, ctx.segment_ids, with_state=True,
        kernel=ctx.row_kernel)
    return out, {"state": state, "conv": tail}


def _step(ctx, h, blk, cache, li):
    """Layer li of the state and the conv tail stepped in place (carried
    like k/v)."""
    out, states, tails = linear_attn_step(
        h, blk, ctx.cfg, cache.state, cache.conv, li, ctx.row_kernel)
    return out, dataclasses.replace(cache, state=states, conv=tails), {}


_CACHE = {
    "state": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.linear_n_v_heads, cfg.linear_k_head_dim,
         cfg.linear_v_head_dim), jnp.float32),
    "conv": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim), dtype),
}


def _matmul_params(cfg: ModelConfig) -> int:
    """ONE Gated DeltaNet layer's projections plus its recurrence counted
    as the 3 * d_k * d_v multiply-adds a value head's state takes per token
    (S^T k, S^T q, k d^T)."""
    h, hv = cfg.hidden_dim, cfg.linear_n_v_heads
    return (
        h * (cfg.linear_conv_dim + cfg.linear_value_dim + 2 * hv)
        + cfg.linear_value_dim * h
        + 3 * hv * cfg.linear_k_head_dim * cfg.linear_v_head_dim
    )


def state_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """The two kinds of state of a static program's cache: k/v beside a
    recurrent branch's state and conv tails."""
    return {
        "kv_cache_bytes": nbytes(cache.k, cache.v),
        "state_cache_bytes": nbytes(cache.state, cache.conv),
    }


def _cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """`state_cache_stats` and the form the static program's decode step
    runs the recurrence in (1: the Pallas kernel; a mesh keeps the
    choice, so the engine's is the backend's)."""
    return {
        **state_cache_stats(cfg, cache, batch, s_max),
        "gdn_step_on_kernel": int(step_kernel_form(cfg)[0]),
    }


def _grad_options(cfg: ModelConfig, row_kernel):
    """Where the gradient programs run the rule on its Pallas sweep
    (`chunk_kernel_form`), XLA:TPU's scheduler is held to half of the memory
    it may spend on its own overlap.  With the rule's blocks in VMEM the
    8,192-token program's temporaries fall from 10.2 GB to 5.8, and with
    that room the scheduler writes three times the instructions for the
    same work — 284 MB where the `jnp` form's program, compiled against its
    own memory need, takes 98 — and a loaded program is resident HBM
    (`peak_hbm_gb` + 1.6%).  Held to half, the two programs take 96 + 87 MB
    and a step 0.3% longer (PERF.md section 6, PR 52)."""
    if chunk_kernel_form(cfg, row_kernel):
        return {"xla_tpu_scheduler_percent_shared_memory_limit": 50}
    return {}


def _train_stats(cfg: ModelConfig, n_layers: int, seg: jax.Array, row_kernel):
    starts = segment_starts(seg)
    return {
        "linear_attn/segments_per_row": jnp.mean(
            (seg[:, 0] > 0) + jnp.sum(starts, axis=-1)
        ).astype(jnp.float32),
        # The chunked rule's form in this gradient program (1: the Pallas
        # sweep `gdn_chunk`), a trace-time constant.
        "linear_attn/rule_on_kernel": jnp.float32(
            chunk_kernel_form(cfg, row_kernel)),
        # ... and the conv's (1: the Pallas operator `causal_conv_act`).
        "linear_attn/conv_on_kernel": jnp.float32(conv_kernel_form(
            cfg.linear_conv_dim, cfg.linear_conv_kernel, row_kernel)),
    }


BRANCH = Branch(
    leaves=LINEAR_LEAVES,
    init=init_linear_attn,
    cache=_CACHE,
    packed=_packed,
    step=_step,
    refusal=Refusal(
        HybridLayoutError,
        "a hybrid layer pattern (Gated DeltaNet layers beside attention "
        "layers) runs under data and fsdp sharding only: no tensor "
        "parallelism over DeltaNet heads, the chunked delta rule has no "
        "ring over a split sequence, and a pipeline stage would have to be "
        "whole periods (PERF.md section 7)",
        "recurrent state has no slot on the serving plane yet: a hybrid "
        "layer pattern (linear-attention layers) generates on the static "
        "decode program only (at most max_decode_batch requests, no stop "
        "sequences, no speculative decoding, max_new_tokens within "
        "static_path_max_new)",
    ),
    matmul_params=_matmul_params,
    cache_stats=_cache_stats,
    train_stats=_train_stats,
    grad_options=_grad_options,
)

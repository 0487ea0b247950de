"""Mamba-2 (SSD): the state-space mixer of a pattern of one-branch layers
(nemotron_h: `M` layers beside expert-only and attention-only layers).

Per head h of `ssm_head_dim` channels, with a state S [head_dim, N] kept in
fp32, B and C [N] shared by the heads of a group:

    dt_t = softplus(dt_t + dt_bias_h)        A_h = -exp(A_log_h)
    S_t  = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t
    y_t  = S_t C_t + D_h x_t

before it ONE input projection [z | x B C | dt] and a causal depthwise conv
WITH bias + SiLU over the x | B | C channels; after it y * silu(z), an
RMSNorm over each GROUP of channels times a weight (gate first, then norm)
and the output projection.

Four forms of the same recurrence:
- `ssm_forward` (training, `forward`, prefill): the chunked (SSD) form,
  `ssd_chunked`.  Inside a chunk of `ssm_chunk` tokens y = ((C B^T) *
  decay) (dt x); each chunk's own contribution to the state and the read of
  the state it starts from are batched matmuls over all chunks, and a
  `lax.scan` over chunks carries S through an elementwise update alone.
- the same chunked form as a Pallas sweep over a row's chunks
  (`ops/pallas/ssd_chunk.py`, kernels `ssd_chunk_fwd` / `ssd_chunk_bwd`
  under the scope `ssd_scan`): a head's [C, C] block and the carried state
  stay in VMEM, a reverse sweep is its backward.  `ssm_forward` takes it in
  the gradient program and `forward` on ONE TPU device where the widths make
  whole tiles (`ssd_kernel_form`: no knob), and keeps `ssd_chunked`, the
  sweep's oracle, off a TPU, under a Mesh and in prefill (`with_state`),
  whose programs and bits stay what they were.
- `ssm_step` (decode): one token against the carried S and the conv's last
  K-1 inputs.
- `ssm_ragged` (the serving plane's chunk): a PACKED RAGGED stream in which
  a slot owns 0 (done, parked, lane-starved), 1 (decoding) or up to W
  (prefilling) consecutive lanes of one inner step.  The lanes are
  gathered into a slab [n_slots, W] (`slot_lanes_of`) and a slot's lanes
  are ONE short SSD chunk that starts from the slot's carried S and conv
  tail and leaves both behind: y_t = C_t . decay(start -> t) S0 + the
  chunk's own lower triangle, S_new = decay(start -> end) S0 + sum_t
  decay(t -> end) dt_t x_t (x) B_t, the conv with the tail as its left
  halo.  A slot whose first lane is at position 0 starts from ZERO state
  and tail (a reused slot never sees its previous request); a slot with no
  lane, and every dead lane, leaves state and tail bit-identical.  A
  decoding slot is the one-lane case of the same code.  On a TPU backend
  on one device, where a head's [P, N] tile is whole (8, 128) tiles
  (`slab_kernel_form`), the whole chunk a slot is ONE Pallas kernel
  (`ops/pallas/ssm_slab.py`, `ssd_slab_in_place`): the layer's buffer
  aliased to itself, a live slot's tiles read once and rewritten where
  they lie, and the chunk's terms — dt x, the cumulative decays, C B^T,
  the chunk's own lower triangle and the D skip — made in the same grid
  step from x, B and C as the conv left them, for the slots that hold a
  lane alone; a slot with no lane is not visited at all and costs
  neither bytes nor terms.  Elsewhere (off a TPU, on a mesh, other
  widths) the terms are `slab_terms` over the whole [n_slots, W] slab,
  the state's part the two `einsum`s of `ssd_slab`, the kernel's oracle,
  `where(held, new, state)` keeps the slots without a lane, and the D
  skip is `jnp`.  The conv and the gather of y back to the stream are
  `jnp` in both.  `ssm_step`, the static program's decode step, keeps its
  XLA fusion: it already reads the state once.

Packed rows: S and the conv restart at every segment start — the decay
across a segment boundary is zero, the carried state is dropped for every
token whose segment is not the one the previous chunk ended in, and the
conv (`linear_attention.causal_conv`) does not reach back.  Pads (segment
0) are NEUTRAL: dt = 0 there, and a pad after a real token counts to that
token's segment, so the state passes through trailing pads unchanged and
what a row ends on is the state at its last valid token.

Parameters (leaves of `params["blocks"]`, stacked [n_ssm_layers, ...]):
    ssm_in      [D, d_inner + conv_dim + H]   z | x B C | dt
    ssm_conv    [K, conv_dim]                 depthwise taps, oldest first
    ssm_conv_b  [conv_dim]
    ssm_A_log, ssm_D, ssm_dt_bias [H]
    ssm_norm    [d_inner]                     gated norm's weight (plain)
    ssm_out     [d_inner, D]
"""

import dataclasses
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.branches import (
    Branch,
    HybridLayoutError,
    Refusal,
    segment_restarts,
)
from areal_tpu.models.config import ModelConfig
from areal_tpu.models.linear_attention import (
    conv_act,
    conv_kernel_form,
    conv_tail_at,
    state_cache_stats,
)

Params = Dict[str, jax.Array]

SSM_LEAVES = (
    "ssm_in", "ssm_conv", "ssm_conv_b", "ssm_A_log", "ssm_D", "ssm_dt_bias",
    "ssm_norm", "ssm_out",
)


def init_ssm(cfg: ModelConfig, key: jax.Array, n: int, dense) -> Params:
    """`n` layers' leaves as the Mamba-2 initialisers draw them: A uniform
    on (1, 16), dt log-uniform on [ssm_dt_min, ssm_dt_max] floored at
    `ssm_dt_floor` with dt_bias its inverse softplus, D ones, the conv's
    bias zero, the gated norm at one; `dense(key, shape, fan_in)` is the
    caller's matrix init."""
    D, C, H, K = cfg.hidden_dim, cfg.ssm_conv_dim, cfg.ssm_n_heads, cfg.ssm_conv_kernel
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[3], (n, H), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(
        jax.random.uniform(ks[4], (n, H), jnp.float32)
        * (math.log(cfg.ssm_dt_max) - math.log(cfg.ssm_dt_min))
        + math.log(cfg.ssm_dt_min)
    )
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return {
        "ssm_in": dense(ks[0], (n, D, cfg.ssm_in_dim), D),
        "ssm_conv": dense(ks[1], (n, K, C), K),
        "ssm_conv_b": jnp.zeros((n, C), cfg.dtype),
        "ssm_A_log": jnp.log(a).astype(cfg.dtype),
        "ssm_D": jnp.ones((n, H), cfg.dtype),
        "ssm_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
        "ssm_norm": jnp.ones((n, cfg.ssm_inner_dim), cfg.dtype),
        "ssm_out": dense(ks[2], (n, cfg.ssm_inner_dim, D), cfg.ssm_inner_dim),
    }


def _split_in(zxbcdt: jax.Array, cfg: ModelConfig):
    """in_proj's output [..., ssm_in_dim] -> z, xBC, dt."""
    di, c = cfg.ssm_inner_dim, cfg.ssm_conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di: di + c], zxbcdt[..., di + c:]


def _split_conv(xbc: jax.Array, cfg: ModelConfig):
    """conv output [..., conv_dim] fp32 -> x [..., H, P], B, C [..., G, N]."""
    di, g, n = cfg.ssm_inner_dim, cfg.ssm_n_groups, cfg.ssm_state_dim
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, cfg.ssm_n_heads, cfg.ssm_head_dim)
    bm = xbc[..., di: di + g * n].reshape(*lead, g, n)
    cm = xbc[..., di + g * n:].reshape(*lead, g, n)
    return x, bm, cm


def _dt_a(dt: jax.Array, blk: Params):
    """softplus(dt + dt_bias) and A = -exp(A_log), fp32."""
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + blk["ssm_dt_bias"].astype(jnp.float32)
    )
    return dt, -jnp.exp(blk["ssm_A_log"].astype(jnp.float32))


def _out(y: jax.Array, z: jax.Array, blk: Params, cfg: ModelConfig):
    """out_proj(w * rmsnorm_per_group(y * silu(z))): y fp32 [..., d_inner]."""
    with jax.named_scope("out_norm_proj"):
        lead = y.shape[:-1]
        g = cfg.ssm_n_groups
        yf = y * jax.nn.silu(z.astype(jnp.float32))
        yg = yf.reshape(*lead, g, cfg.ssm_inner_dim // g)
        var = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
        yg = yg * jax.lax.rsqrt(var + cfg.rms_norm_eps)
        yf = yg.reshape(*lead, -1) * blk["ssm_norm"].astype(jnp.float32)
        return yf.astype(z.dtype) @ blk["ssm_out"]


def _fill_pads(segment_ids: jax.Array) -> jax.Array:
    """Segment ids with every pad (0) that follows a real token counted to
    that token's segment; leading pads stay 0."""
    idx = jnp.arange(segment_ids.shape[-1], dtype=jnp.int32)
    last = jax.lax.associative_scan(
        jnp.maximum, jnp.where(segment_ids > 0, idx, -1), axis=-1
    )
    filled = jnp.take_along_axis(segment_ids, jnp.maximum(last, 0), axis=-1)
    return jnp.where(last >= 0, filled, 0)


def ssd_chunked(
    x: jax.Array,  # [B, S, H, P] fp32
    dt: jax.Array,  # [B, S, H] fp32, after softplus; 0 = a neutral token
    a: jax.Array,  # [H] fp32, negative
    bm: jax.Array,  # [B, S, G, N] fp32
    cm: jax.Array,  # [B, S, G, N] fp32
    segment_ids: jax.Array,  # [B, S]
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """The SSD recurrence over packed rows in chunked form -> (y [B, S, H,
    P] fp32 without the D skip, the state after each row's last token [B,
    H, P, N] fp32)."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g  # heads a group
    pad = -s % chunk
    if pad:
        # Neutral tokens (dt 0) of the last token's segment.
        def zpad(v):
            return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))

        x, dt, bm, cm = (zpad(v) for v in (x, dt, bm, cm))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // chunk

    # [B, S, ...] -> [B, NC, C, ...]; heads as (group, head in group).
    xd = (x * dt[..., None]).reshape(b, nc, chunk, g, r, p)
    bm = bm.reshape(b, nc, chunk, g, n)
    cm = cm.reshape(b, nc, chunk, g, n)
    seg = segment_ids.reshape(b, nc, chunk)
    la = (dt * a).reshape(b, nc, chunk, g, r)
    ga = jnp.cumsum(la, axis=2)  # log-decay from the chunk's start, inclusive

    same = seg[:, :, :, None] == seg[:, :, None, :]  # [B, NC, C, C]
    idx = jnp.arange(chunk)
    tril = idx[:, None] >= idx[None, :]
    # The state a chunk starts from belongs to the segment the previous
    # chunk ended in; the first chunk starts from nothing.
    prev_last = jnp.concatenate(
        [jnp.full((b, 1), -1, seg.dtype), seg[:, :-1, -1]], axis=1
    )
    carry_ok = (seg == prev_last[..., None]).astype(jnp.float32)  # [B,NC,C]
    same_as_last = (seg == seg[..., -1:]).astype(jnp.float32)

    # Inside a chunk: y_i = sum_{j<=i} (C_i . B_j) exp(ga_i - ga_j) dt_j x_j,
    # the [C, C] blocks with the heads leading (a head's block is a tile).
    gat = jnp.moveaxis(ga, 2, -1)  # [B, NC, G, R, C]
    keep = (tril & same)[:, :, None, None]  # [B, NC, 1, 1, Ci, Cj]
    diff = gat[..., :, None] - gat[..., None, :]
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)
    cb = jnp.einsum("bkign,bkjgn->bkgij", cm, bm)
    y = jnp.einsum("bkgrij,bkjgrp->bkigrp", cb[:, :, :, None] * decay, xd)

    # A chunk's own contribution to the state it ends on ...
    g_last = ga[:, :, -1:]  # [B, NC, 1, G, R]
    w_out = jnp.exp(g_last - ga) * same_as_last[..., None, None]
    own = jnp.einsum("bkjgrp,bkjgn->bkgrpn", xd * w_out[..., None], bm)
    s_keep = jnp.exp(g_last[:, :, 0]) * carry_ok[:, :, -1, None, None]

    # ... carried from chunk to chunk: elementwise, the scan's only work.
    def body(state, xs):
        own_k, keep_k = xs
        return state * keep_k[..., None, None] + own_k, state

    state, s_in = jax.lax.scan(
        body,
        jnp.zeros((b, g, r, p, n), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(s_keep, 1, 0)),
    )
    # ... and read by every token of the segment it belongs to.
    w_in = jnp.exp(ga) * carry_ok[..., None, None]  # [B, NC, C, G, R]
    y = y + jnp.einsum(
        "bkign,kbgrpn->bkigrp", cm, s_in
    ) * w_in[..., None]
    y = y.reshape(b, s + pad, h, p)[:, :s]
    return y, state.reshape(b, h, p, n)


def ssd_kernel_form(cfg: ModelConfig, kernel=None, with_state=False) -> bool:
    """Whether `ssm_forward` runs the recurrence on the Pallas sweep
    `ssd_chunk`: by what the code can see (`flash_attention.
    row_kernel_form`: a TPU backend, widths the kernel can cut; a bool
    forces either) on ONE device — `kernel` a Mesh: a `pallas_call` is one
    device's program — and without `with_state`: prefill reads the final
    state, and its program and what a generator samples from it stay
    `ssd_chunked`'s."""
    from areal_tpu.ops.pallas import ssd_chunk
    from areal_tpu.ops.pallas.flash_attention import row_kernel_form

    use_kernel, mesh = row_kernel_form(kernel, ssd_chunk.fits(
        cfg.ssm_n_heads, cfg.ssm_n_groups, cfg.ssm_head_dim,
        cfg.ssm_state_dim, cfg.ssm_chunk))
    return use_kernel and mesh is None and not with_state


@jax.named_scope("layer/ssm")
def ssm_forward(
    h: jax.Array,  # [B, S, D] normed layer input
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    with_state: bool = False,
    kernel=None,  # None | bool | Mesh: `flash_attention.row_kernel_form`
):
    """-> y [B, S, D]; `with_state` (prefill) adds the state at each row's
    last VALID token [B, H, P, N] fp32 and the conv's tail there [B, K-1,
    conv_dim].  The recurrence has one form per backend and caller
    (`ssd_kernel_form`): the Pallas sweep `ssd_chunk` or `ssd_chunked`; so
    has the conv + SiLU before it (`linear_attention.conv_kernel_form`)."""
    use_kernel = ssd_kernel_form(cfg, kernel, with_state)
    with jax.named_scope("in_proj"):
        z, xbc, dt = _split_in(h @ blk["ssm_in"], cfg)
    with jax.named_scope("conv"):
        conv = conv_act(
            xbc, blk["ssm_conv"], blk["ssm_conv_b"], segment_ids,
            conv_kernel_form(
                cfg.ssm_conv_dim, cfg.ssm_conv_kernel, kernel, with_state))
    with jax.named_scope("ssd_scan"):
        if use_kernel:
            # The sweep reads x, B and C where they lie in the conv's
            # output, and adds the D skip itself.
            from areal_tpu.ops.pallas import ssd_chunk

            dt, a = _dt_a(dt, blk)
            dt = jnp.where((segment_ids > 0)[..., None], dt, 0.0)
            y = ssd_chunk.ssd_chunk(
                conv, dt, a, blk["ssm_D"], _fill_pads(segment_ids),
                cfg.ssm_chunk, cfg.ssm_head_dim, cfg.ssm_n_groups)
        else:
            x, bm, cm = _split_conv(conv, cfg)
            dt, a = _dt_a(dt, blk)
            dt = jnp.where((segment_ids > 0)[..., None], dt, 0.0)
            y, state = ssd_chunked(
                x, dt, a, bm, cm, _fill_pads(segment_ids), cfg.ssm_chunk
            )
            y = y + blk["ssm_D"].astype(jnp.float32)[:, None] * x
    out = _out(y.reshape(*y.shape[:2], cfg.ssm_inner_dim), z, blk, cfg)
    if with_state:
        idx = jnp.arange(segment_ids.shape[-1])
        last = jnp.max(jnp.where(segment_ids > 0, idx, 0), axis=-1)
        return out, state, conv_tail_at(
            xbc, segment_ids, last, cfg.ssm_conv_kernel)
    return out


@jax.named_scope("layer/ssm")
def ssm_step(
    h: jax.Array,  # [B, 1, D]
    blk: Params,
    cfg: ModelConfig,
    states: jax.Array,  # [n_ssm, B, H, P, N] fp32, every Mamba layer's
    tails: jax.Array,  # [n_ssm, B, K-1, conv_dim] the convs' last inputs
    li,  # this layer's index into both
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token per row -> (y [B, 1, D], states, tails), layer `li`
    of both stepped in place.  The whole caches come in and go out so that
    the state's read and its write lie under this scope (an update made by
    the caller would be timed outside `layer/ssm`)."""
    state = jax.lax.dynamic_index_in_dim(states, li, axis=0, keepdims=False)
    tail = jax.lax.dynamic_index_in_dim(tails, li, axis=0, keepdims=False)
    with jax.named_scope("in_proj"):
        z, xbc, dt = _split_in(h[:, 0] @ blk["ssm_in"], cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], 1)
        conv = jax.nn.silu(
            jnp.einsum(
                "bkc,kc->bc", window.astype(jnp.float32),
                blk["ssm_conv"].astype(jnp.float32),
            )
            + blk["ssm_conv_b"].astype(jnp.float32)
        )
        tails = jax.lax.dynamic_update_index_in_dim(
            tails, window[:, 1:], li, axis=0
        )
    with jax.named_scope("ssm_step"):
        x, bm, cm = _split_conv(conv, cfg)  # [B, H, P], [B, G, N]
        dt, a = _dt_a(dt, blk)  # [B, H], [H]
        b, hh, p = x.shape
        g, n = bm.shape[1:]
        # One pass over S: decay, add the token's outer product, read y.
        sg = state.reshape(b, g, hh // g, p, n)
        da = jnp.exp(dt * a).reshape(b, g, hh // g, 1, 1)
        xd = (x * dt[..., None]).reshape(b, g, hh // g, p, 1)
        sg = sg * da + xd * bm[:, :, None, None, :]
        y = jnp.sum(sg * cm[:, :, None, None, :], axis=-1).reshape(b, hh, p)
        y = y + blk["ssm_D"].astype(jnp.float32)[:, None] * x
        states = jax.lax.dynamic_update_index_in_dim(
            states, sg.reshape(state.shape), li, axis=0
        )
    out = _out(y.reshape(b, cfg.ssm_inner_dim), z, blk, cfg)
    return out[:, None], states, tails


class SlotLanes(NamedTuple):
    """One stream's lanes by slot, the same for every Mamba layer of a
    step (`slot_lanes_of`)."""

    idx: jax.Array  # [R, W] int32: the stream lane of slot r's w-th lane
    valid: jax.Array  # [R, W] bool: w < the slot's lanes this step
    count: jax.Array  # [R] int32: the slot's lanes this step
    fresh: jax.Array  # [R] bool: the slot's first lane is at position 0
    rid: jax.Array  # [T] int32: a lane's slot (dead lanes clipped)
    q: jax.Array  # [T] int32: a lane's place among its slot's lanes
    # The state kernel's work list (`ssm_slab.live_slots`):
    live: jax.Array  # [R] int32: the slots that hold a lane, first
    n_live: jax.Array  # int32: their number


def slot_lanes_of(
    row_of: jax.Array,  # [T] int32: a lane's slot; >= n_slots = dead
    positions: jax.Array,  # [T] int32: a lane's position in its sequence
    n_slots: int,
    width: int,  # W: the most lanes a slot holds in one stream
) -> SlotLanes:
    """Where each slot's lanes lie in a packed stream.  A slot's lanes are
    CONTIGUOUS and in position order (the serving chunk packs row r at
    [starts[r], starts[r] + c[r])); no slot holds more than `width`."""
    t = row_of.shape[0]
    lane = jnp.arange(t, dtype=jnp.int32)
    row_of = row_of.astype(jnp.int32)
    count = jnp.zeros((n_slots,), jnp.int32).at[row_of].add(1, mode="drop")
    start = jnp.full((n_slots,), t, jnp.int32).at[row_of].min(lane, mode="drop")
    w = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.minimum(start[:, None] + w[None, :], t - 1)
    valid = w[None, :] < count[:, None]
    rid = jnp.minimum(row_of, n_slots - 1)
    fresh = valid[:, 0] & (positions[idx[:, 0]] == 0)
    q = jnp.clip(lane - start[rid], 0, width - 1)
    from areal_tpu.ops.pallas.ssm_slab import live_slots

    return SlotLanes(idx, valid, count, fresh, rid, q, *live_slots(count))


def slab_terms(
    x: jax.Array,  # [R, W, H, P] fp32
    dt: jax.Array,  # [R, W, H] fp32 after softplus; 0 = no lane
    a: jax.Array,  # [H] fp32, negative
    bm: jax.Array,  # [R, W, G, N] fp32
    cm: jax.Array,  # [R, W, G, N] fp32
    carried: jax.Array,  # [R] fp32: 1 = start from s0, 0 = from zero
):
    """What one SSD chunk a slot is made of BESIDE the carried state,
    heads as (group, head in group) -> (y [R, W, G, HG, P]: the chunk's own
    lower triangle; w_in [R, W, G, HG]: the decay from the chunk's start to
    each lane, what scales a lane's read of the state; xw [R, W, G, HG, P]:
    each lane's dt x decayed to the slot's last lane, whose outer products
    with B enter the state; s_keep [R, G, HG]: the decay of the state
    itself).  A slot's lanes are its first ones (dt = 0 behind them: the
    log-decay stops growing, so the slab's last column IS the last
    lane's).  `carried` scales the two places the old state enters (w_in
    and s_keep) instead of the state itself: zeroing [R, H, P, N] would be
    a pass over it of its own."""
    r, w, h, p = x.shape
    g = bm.shape[2]
    hg = h // g
    xd = (x * dt[..., None]).reshape(r, w, g, hg, p)
    ga = jnp.cumsum((dt * a).reshape(r, w, g, hg), axis=1)  # inclusive
    gat = jnp.moveaxis(ga, 1, -1)  # [R, G, HG, W]
    i = jnp.arange(w)
    keep = i[:, None] >= i[None, :]
    diff = gat[..., :, None] - gat[..., None, :]
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)
    cb = jnp.einsum("rign,rjgn->rgij", cm, bm)
    y = jnp.einsum("rgkij,rjgkp->rigkp", cb[:, :, None] * decay, xd)
    kept = carried[:, None, None]  # over [R, G, HG]
    w_in = jnp.exp(ga) * kept[:, None]
    g_last = ga[:, -1]  # [R, G, HG]
    w_out = jnp.exp(g_last[:, None] - ga)  # [R, W, G, HG]
    return y, w_in, xd * w_out[..., None], jnp.exp(g_last) * kept


def ssd_slab(
    x: jax.Array,  # [R, W, H, P] fp32
    dt: jax.Array,  # [R, W, H] fp32 after softplus; 0 = no lane
    a: jax.Array,  # [H] fp32, negative
    bm: jax.Array,  # [R, W, G, N] fp32
    cm: jax.Array,  # [R, W, G, N] fp32
    s0: jax.Array,  # [R, H, P, N] fp32: the state each slot carries
    carried: jax.Array,  # [R] fp32: 1 = start from s0, 0 = from zero
) -> Tuple[jax.Array, jax.Array]:
    """One SSD chunk a slot, from a carried state -> (y [R, W, H, P] fp32
    without the D skip, the state after each slot's last lane): the `jnp`
    form, and the oracle of `ops/pallas/ssm_slab.py`."""
    r, w, h, p = x.shape
    g, n = bm.shape[2:]
    y, w_in, xw, s_keep = slab_terms(x, dt, a, bm, cm, carried)
    sg = s0.reshape(r, g, h // g, p, n)
    # The read of the carried state, by every lane of its slot ...
    y = y + jnp.einsum("rign,rgkpn->rigkp", cm, sg) * w_in[..., None]
    # ... and what the slot leaves: the carried state decayed over all its
    # lanes plus each lane's outer product decayed to the last.
    new = sg * s_keep[..., None, None] + jnp.einsum(
        "rjgkp,rjgn->rgkpn", xw, bm)
    return y.reshape(r, w, h, p), new.reshape(r, h, p, n)


def _to_stream(v: jax.Array, lanes: "SlotLanes") -> jax.Array:
    """A slab [R, W, ...] back in the stream [T, ...]: lane t is its
    slot's q-th."""
    return v[lanes.rid, lanes.q]


def ssd_slab_in_place(
    conv: jax.Array,  # [R, W, H P + 2 G N] fp32: x | B | C (`_split_conv`)
    dt, a,  # as `ssd_slab`
    d: jax.Array,  # [H]: the skip's weight a head
    states: jax.Array,  # [steps, R, H, P, N] fp32: the layer's whole buffer
    li,  # the scan step that steps
    lanes: "SlotLanes",
    block_h: int = 0,  # heads a grid step (0: the kernel's own choice)
) -> Tuple[jax.Array, jax.Array]:
    """`ssd_slab` and the D skip as ONE call of the Pallas kernel
    `ssm_slab.ssm_slab_step` -> (y of the STREAM's lanes [T, H P] with the
    skip in it, states): step `li` of the slots that hold a lane read once
    and rewritten where it lies, no other byte of the buffer touched, and
    the chunk's terms (`slab_terms`' part) made inside the kernel for
    those slots alone — the conv's rows go in as they are (the kernel
    reads x, B and C where they lie in them), and the finished y comes
    back to the stream.  The rows of a slot without a lane, which the
    kernel never writes, are never read either."""
    from areal_tpu.ops.pallas import ssm_slab

    states, y = ssm_slab.ssm_slab_step(
        states, li, lanes.live, lanes.n_live, conv, dt, a, d,
        1.0 - lanes.fresh.astype(jnp.float32), block_h=block_h,
    )
    # A dead lane of the stream is clipped onto some slot's lane, written
    # or not: what lies there may not be a number.
    y = jnp.where(
        _to_stream(lanes.valid, lanes)[:, None], _to_stream(y, lanes), 0.0)
    return y, states


def slab_kernel_form(cfg: ModelConfig, kernel: Optional[bool] = None) -> bool:
    """Which form `ssm_ragged` takes for the chunk a slot, by what the code
    can see: the Pallas kernel on a TPU backend (`kernel` None; a bool
    forces either, a caller on a mesh passes False) where a head's [P, N]
    tile is whole (8, 128) tiles and the heads cut into blocks
    (`ssm_slab.fits`); the `jnp` `ssd_slab` elsewhere."""
    from areal_tpu.base.distributed import is_tpu_backend
    from areal_tpu.ops.pallas import ssm_slab

    if kernel is None:
        kernel = is_tpu_backend()
    return bool(kernel) and ssm_slab.fits(
        cfg.ssm_n_heads, cfg.ssm_n_groups, cfg.ssm_head_dim, cfg.ssm_state_dim)


def slab_lanes_made(
    count: jax.Array,  # [R] int32: each slot's lanes this inner step
    width: int,  # W
    kernel_form: bool,
) -> jax.Array:
    """Slab lanes for which one layer's inner step computes the chunk's
    terms (the serving chunk's counter `ssm_lanes_made`): the kernel makes
    them for the W lanes of each slot that holds one, `slab_terms` for
    every lane of the [R, W] slab."""
    if kernel_form:
        return width * jnp.sum(count > 0, dtype=jnp.int32)
    return jnp.int32(count.shape[0] * width)


@jax.named_scope("layer/ssm")
def ssm_ragged(
    h: jax.Array,  # [T, D] normed layer input, a packed ragged stream
    blk: Params,
    cfg: ModelConfig,
    states: jax.Array,  # [steps, R, H, P, N] fp32: this layer of the unit's
    tails: jax.Array,  # [steps, R, K-1, conv_dim] its conv's last inputs
    li,  # the scan step: this layer's place in both
    lanes: SlotLanes,
    kernel: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The serving chunk's step over a packed stream -> (y [T, D], states,
    tails), entry `li` of both stepped in place for the slots that hold a
    lane (module docstring).  The buffers are ONE Mamba layer of the
    plan's unit's, stacked over the scan's steps
    (`transformer.PagedKVCache`); they come in and go out whole so that
    the state's read and write lie under this scope, as in `ssm_step`.

    The chunk a slot takes one of two forms, picked by what the code can
    see (`slab_kernel_form`): on a TPU backend, where a head's [P, N] tile
    is whole (8, 128) tiles, the Pallas kernel `ssm_slab_step`
    (`ssd_slab_in_place`: the buffer aliased to itself, a live slot's
    tiles read once and rewritten where they lie, the chunk's terms made
    inside for the live slots alone, a slot with no lane never touched);
    elsewhere `slab_terms` + `ssd_slab`, the kernel's oracle, whose new
    state is written back under `where(held, new, state)`.  `kernel`: None,
    that choice; a bool forces either where the shapes fit (interpreted
    off a TPU) — a caller on a mesh passes False, the kernel is one
    device's program.  The conv and the gather back to the stream are
    `jnp` in both; the D skip is inside the kernel and `jnp` beside
    `ssd_slab`."""
    kk = cfg.ssm_conv_kernel
    kernel = slab_kernel_form(cfg, kernel)
    with jax.named_scope("in_proj"):
        z, xbc, dt = _split_in(h @ blk["ssm_in"], cfg)
    with jax.named_scope("ssm_ragged"):
        tail = jax.lax.dynamic_index_in_dim(tails, li, axis=0, keepdims=False)
        with jax.named_scope("conv"):
            # The slot's tail is the conv's left halo: [tail | its lanes].
            tail0 = jnp.where(lanes.fresh[:, None, None], 0, tail)
            cat = jnp.concatenate(
                [tail0, xbc[lanes.idx].astype(tail.dtype)], axis=1)
            taps = blk["ssm_conv"].astype(jnp.float32)
            width = lanes.idx.shape[1]
            conv = sum(
                cat[:, k: k + width].astype(jnp.float32) * taps[k]
                for k in range(kk)
            )
            conv = jax.nn.silu(conv + blk["ssm_conv_b"].astype(jnp.float32))
            # What the slot's last lane leaves: the last K-1 of [tail |
            # lanes]; with no lane, the tail as it was.
            at = lanes.count[:, None] + jnp.arange(kk - 1)[None, :]
            tails = jax.lax.dynamic_update_index_in_dim(
                tails, jnp.take_along_axis(cat, at[..., None], axis=1),
                li, axis=0,
            )
        with jax.named_scope("ssd_scan"):
            dts, a = _dt_a(dt[lanes.idx], blk)
            dts = jnp.where(lanes.valid[..., None], dts, 0.0)
            skip = blk["ssm_D"].astype(jnp.float32)
            if kernel:
                y, states = ssd_slab_in_place(
                    conv, dts, a, skip, states, li, lanes)
            else:
                x, bm, cm = _split_conv(conv, cfg)  # [R, W, H, P], [.., G, N]
                state = jax.lax.dynamic_index_in_dim(
                    states, li, axis=0, keepdims=False)
                y, new = ssd_slab(
                    x, dts, a, bm, cm, state,
                    1.0 - lanes.fresh.astype(jnp.float32))
                held = lanes.count > 0
                states = jax.lax.dynamic_update_index_in_dim(
                    states,
                    jnp.where(held[:, None, None, None], new, state),
                    li, axis=0,
                )
                y = _to_stream(y, lanes) + skip[:, None] * _to_stream(
                    x, lanes)
        y = y.reshape(h.shape[0], cfg.ssm_inner_dim)
    return _out(y, z, blk, cfg), states, tails


# The kind's record (`models/branches.py`).


def _packed(ctx, h, blk):
    if not ctx.with_state:
        return ssm_forward(
            h, blk, ctx.cfg, ctx.segment_ids, kernel=ctx.row_kernel), {}
    out, state, tail = ssm_forward(
        h, blk, ctx.cfg, ctx.segment_ids, with_state=True,
        kernel=ctx.row_kernel)
    return out, {"state": state, "conv": tail}


def _step(ctx, h, blk, cache, li):
    """Layer li of the state and the conv tail stepped in place (carried
    like k/v)."""
    out, states, tails = ssm_step(h, blk, ctx.cfg, cache.state, cache.conv, li)
    return out, dataclasses.replace(cache, state=states, conv=tails), {}


def _serve(ctx, h, blk, pools, li, at):
    """`at`: (the layer's place among the unit's Mamba layers, the scan
    step): its own buffers, and its place in them."""
    j, pi = at
    state, conv = list(pools.state), list(pools.conv)
    out, state[j], conv[j] = ssm_ragged(
        h[:, 0], blk, ctx.cfg, state[j], conv[j], pi, ctx.lanes,
        kernel=ctx.paged_kernel)
    return out[:, None], dataclasses.replace(
        pools, state=tuple(state), conv=tuple(conv))


_CACHE = {
    "state": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state_dim),
        jnp.float32),
    "conv": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim), dtype),
}


def _matmul_params(cfg: ModelConfig) -> int:
    """ONE Mamba-2 layer's matmul parameters, its recurrence counted as
    the multiply-adds a token takes: 2 * d_inner * N for the state (add the
    token's outer product, read y = S C) — what the decode step does; the
    chunked form's extra in-chunk products are not counted as useful."""
    h, di = cfg.hidden_dim, cfg.ssm_inner_dim
    return h * cfg.ssm_in_dim + di * h + 2 * di * cfg.ssm_state_dim


def _train_stats(cfg: ModelConfig, n_layers: int, seg: jax.Array, row_kernel):
    """What the chunked scan ran over, summed over the Mamba layers:
    chunks, those of them on the Pallas sweep (`ssd_kernel_form`: all or
    none), the conv's form (1: the Pallas operator `causal_conv_act`) and
    the restarts."""
    n_chunks = n_layers * seg.shape[0] * -(-seg.shape[1] // cfg.ssm_chunk)
    return {
        "ssm/chunks": jnp.float32(n_chunks),
        "ssm/chunks_on_kernel": jnp.float32(
            n_chunks * ssd_kernel_form(cfg, row_kernel)),
        "ssm/conv_on_kernel": jnp.float32(conv_kernel_form(
            cfg.ssm_conv_dim, cfg.ssm_conv_kernel, row_kernel)),
        "ssm/segment_restarts": n_layers * segment_restarts(seg),
    }


BRANCH = Branch(
    leaves=SSM_LEAVES,
    init=init_ssm,
    cache=_CACHE,
    packed=_packed,
    step=_step,
    serve=_serve,
    refusal=Refusal(
        HybridLayoutError,
        "Mamba-2 mixers in two-branch layers run under data and fsdp "
        "sharding only: the Mamba heads, their conv channels and their "
        "state are not split over `model`, the chunked scan has no ring "
        "over a split sequence, and the pipeline's stage scans one kind of "
        "layer (PERF.md section 7)",
        None,  # a slot of state beside the page pool (`ssm_ragged`)
    ),
    matmul_params=_matmul_params,
    cache_stats=state_cache_stats,
    train_stats=_train_stats,
)

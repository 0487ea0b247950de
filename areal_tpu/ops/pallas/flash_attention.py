"""Pallas TPU flash attention over packed rows (segment-aware, causal).

The hot op of the framework: replaces the reference's flash-attn varlen CUDA
dependency (realhf/impl/model/modules/attn.py:24) with a TPU kernel built
for the [B, S] packed-row layout (segment_ids delimit sequences; attention
is causal-within-segment).

Design (standard flash attention v2 tiling, adapted to Mosaic/TPU):
- forward: grid (B*H, nq, nk); online-softmax accumulators (m, l, acc) live
  in VMEM scratch and persist across the sequential nk dimension; output and
  logsumexp are written on the last nk step.
- backward: two kernels — dq with grid (B*H, nq, nk) and dkv with grid
  (B*H, nk, nq) — both recompute the probability tiles from the saved
  logsumexp instead of materializing [S, S] (O(S) memory).
- block-level early-out via @pl.when: tiles entirely above the causal
  diagonal AND tiles whose q/k segment-id ranges cannot overlap are
  skipped — packed rows concatenate unrelated sequences with
  non-decreasing ids, so the work is near block-diagonal in the number
  of packed sequences rather than O(row_len^2).

Interpret mode (CPU) is used automatically off-TPU, which is how the unit
tests exercise the same kernel code path hermetically.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _interpret() -> bool:
    from areal_tpu.base.distributed import is_tpu_backend

    return not is_tpu_backend()


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def named_call(name: str, kernel, **kw):
    """`pl.pallas_call` under its stable device name: the kernel's `name=`
    and a `jax.named_scope` of the same name around the call.  The scope
    is what a profile reader keys on — it is certain to reach `op_name`."""
    call = pl.pallas_call(kernel, name=name, **kw)

    def named(*args):
        with jax.named_scope(name):
            return call(*args)

    return named


def _fwd_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,  # inputs
    o_ref, lse_ref,  # outputs
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, block_q: int, block_k: int, nk: int, causal: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # Skip tiles strictly above the causal diagonal, and tiles whose q/k
    # SEGMENTS cannot overlap (packed rows concatenate unrelated sequences;
    # ids are non-decreasing along the row, so a disjoint id range means
    # the whole tile is masked — this turns O(row^2) into near
    # block-diagonal work).
    causal_ok = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    sq = seg_q_ref[0][:, 0]
    sk = seg_k_ref[0][0, :]
    overlap = (
        (jnp.min(sk) <= jnp.max(sq))
        & (jnp.max(sk) >= jnp.min(sq))
        & (jnp.max(sq) > 0)
    )
    run = causal_ok & overlap

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        # Segment ids arrive sublane/lane-broadcast (Mosaic needs >=2D tiles
        # with aligned minor dims): q ids [bq, 8] -> [bq, 1], k ids
        # [8, bk] -> [1, bk].
        seg_q = seg_q_ref[0][:, 0:1]
        seg_k = seg_k_ref[0][0:1, :]
        mask = (seg_q == seg_k) & (seg_q > 0)
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_scr[:] + jnp.log(safe_l), NEG_INF)


def _seg_layouts(seg: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[B, S] int32 -> (q ids [B, S, 8], k ids [B, 8, S]).

    Mosaic requires >=2D tiles whose minor dims are 8/128-aligned or span the
    array; broadcasting ids over 8 sublanes/lanes (the official TPU flash
    kernel's trick) satisfies that at 8x int32 cost.  Ids are per-BATCH (not
    per-head): the BlockSpec index maps divide the b*h grid index by the
    head count, so no H-fold copy is materialized.
    """
    b, s = seg.shape
    seg_q = jnp.broadcast_to(seg[:, :, None], (b, s, 8))
    seg_k = jnp.broadcast_to(seg[:, None, :], (b, 8, s))
    return seg_q, seg_k


def _kv_index(hq: int, hkv: int):
    """Grid index (batch-major b*hq) -> kv row in the UNEXPANDED [B*hkv]
    array: in-kernel GQA — q head h reads kv head h // (hq//hkv), so the
    7x repeat_kv materialization never happens."""
    n_rep = hq // hkv

    def idx(b, qi, ki):
        return (b // hq) * hkv + (b % hq) // n_rep, ki, 0

    return idx


def _fwd(
    q, k, v, seg, hq, scale, block_q, block_k, causal
) -> Tuple[jax.Array, jax.Array]:
    """q: [B*hq, S, D]; k/v: [B*hkv, S, D] (unexpanded GQA); seg: [B, S]
    int32.  Returns (o [B*hq,S,D], lse [B*hq,S,1])."""
    bh, s, d = q.shape
    hkv = k.shape[0] // seg.shape[0]
    kv_idx = _kv_index(hq, hkv)
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
    )
    seg_q, seg_k = _seg_layouts(seg)
    return named_call(
        "flash_fwd",
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 8), lambda b, qi, ki: (b // hq, qi, 0)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki: (b // hq, 0, ki)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(seg_q, seg_k, q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *, scale, block_q, block_k, nk, causal,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    causal_ok = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    sq = seg_q_ref[0][:, 0]
    sk = seg_k_ref[0][0, :]
    overlap = (
        (jnp.min(sk) <= jnp.max(sq))
        & (jnp.max(sk) >= jnp.min(sq))
        & (jnp.max(sq) > 0)
    )
    run = causal_ok & overlap

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        seg_q = seg_q_ref[0][:, 0:1]
        seg_k = seg_k_ref[0][0:1, :]
        mask = (seg_q == seg_k) & (seg_q > 0)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask &= q_pos >= k_pos
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, block_q, block_k, nq, causal,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    causal_ok = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    sq = seg_q_ref[0][:, 0]
    sk = seg_k_ref[0][0, :]
    overlap = (
        (jnp.min(sk) <= jnp.max(sq))
        & (jnp.max(sk) >= jnp.min(sq))
        & (jnp.max(sq) > 0)
    )
    run = causal_ok & overlap

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        seg_q = seg_q_ref[0][:, 0:1]
        seg_k = seg_k_ref[0][0:1, :]
        mask = (seg_q == seg_k) & (seg_q > 0)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask &= q_pos >= k_pos
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    scale, block_q, block_k, causal, res, do
) -> Tuple[jax.Array, jax.Array, jax.Array, None]:
    q, k, v, o, lse, seg = res
    bh, s, d = q.shape
    b = seg.shape[0]
    hq = bh // b
    hkv = k.shape[0] // b
    n_rep = hq // hkv
    kv_idx_q = _kv_index(hq, hkv)  # grid order (b, qi, ki)

    def kv_idx_k(bi, ki, qi):  # grid order (b, ki, qi): s-block is ki
        row, _, _ = kv_idx_q(bi, qi, ki)
        return row, ki, 0

    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, S, 1]

    seg_q, seg_k = _seg_layouts(seg)
    common_in = [seg_q, seg_k, q, k, v, do, lse, delta]

    dq = named_call(
        "flash_dq",
        functools.partial(
            _dq_kernel,
            scale=scale, block_q=block_q, block_k=block_k, nk=nk,
            causal=causal,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 8), lambda b, qi, ki: (b // hq, qi, 0)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki: (b // hq, 0, ki)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx_q),
            pl.BlockSpec((1, block_k, d), kv_idx_q),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(*common_in)

    # dk/dv come out per Q-HEAD (the grid walks q heads); the n_rep grads
    # sharing one kv head are group-summed after the kernel.
    dk_x, dv_x = named_call(
        "flash_dkv",
        functools.partial(
            _dkv_kernel,
            scale=scale, block_q=block_q, block_k=block_k, nq=nq,
            causal=causal,
        ),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, 8), lambda b, ki, qi: (b // hq, qi, 0)),
            pl.BlockSpec((1, 8, block_k), lambda b, ki, qi: (b // hq, 0, ki)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx_k),
            pl.BlockSpec((1, block_k, d), kv_idx_k),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_k, d), jnp.float32),
            _vmem((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*common_in)

    def group_sum(g):
        return (
            g.reshape(b, hkv, n_rep, s, d)
            .sum(axis=2)
            .reshape(b * hkv, s, d)
        )

    dk = group_sum(dk_x).astype(k.dtype)
    dv = group_sum(dv_x).astype(v.dtype)
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd(q, k, v, seg, scale, block_q, block_k, causal):
    hq = q.shape[0] // seg.shape[0]
    o, _ = _fwd(q, k, v, seg, hq, scale, block_q, block_k, causal)
    return o


def _flash_fwd_rule(q, k, v, seg, scale, block_q, block_k, causal):
    hq = q.shape[0] // seg.shape[0]
    o, lse = _fwd(q, k, v, seg, hq, scale, block_q, block_k, causal)
    return o, (q, k, v, o, lse, seg)


def _flash_bwd_rule(scale, block_q, block_k, causal, res, do):
    return _bwd(scale, block_q, block_k, causal, res, do)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S] int32, 0 = pad
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Segment-aware causal flash attention over packed rows.  GQA is
    native: kv stays at n_kv heads and the kernel's BlockSpec index maps
    route q head h to kv head h // n_rep — no repeat_kv materialization."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"sequence length {s} must be a multiple of block sizes "
            f"({block_q}, {block_k})"
        )

    def to_bhsd(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    o = _flash_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), segment_ids.astype(jnp.int32),
        d**-0.5, block_q, block_k, causal,
    )
    return o.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S]
    mesh,
    causal: bool = True,
) -> jax.Array:
    """The multi-chip wrapper: Pallas kernels are not GSPMD-partitionable,
    so `shard_map` pins the layout — batch over (data, fsdp), heads over
    `model`, sequence unsharded (ring attention owns the seq axis) — and
    each device runs the kernel on its local shard.  Attention is
    independent per (batch row, head), so no collectives are needed; GQA
    locality requires n_kv % model_axis == 0 (contiguous head sharding
    keeps each q-head group with its kv head)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import (
        DATA_AXIS,
        FSDP_AXIS,
        MODEL_AXIS,
        SEQ_AXIS,
    )

    if mesh.shape[SEQ_AXIS] != 1:
        raise ValueError("flash_attention_sharded: seq axis must be 1 (CP "
                         "uses ring attention)")
    m = mesh.shape[MODEL_AXIS]
    if k.shape[2] % m or q.shape[2] % m:
        raise ValueError(
            f"flash_attention_sharded: the model axis ({m}) must divide "
            f"both head counts ({q.shape[2]}q/{k.shape[2]}kv)"
        )
    batch = (DATA_AXIS, FSDP_AXIS)
    spec_qkv = P(batch, None, MODEL_AXIS, None)
    spec_seg = P(batch, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_seg),
        out_specs=spec_qkv,
        check_vma=False,  # pallas_call outputs carry no vma metadata
    )
    def inner(ql, kl, vl, segl):
        return flash_attention(ql, kl, vl, segl, causal=causal)

    return inner(q, k, v, segment_ids)

"""Pallas decode-attention kernel: fused GQA attention over the KV cache.

Decode is HBM-bound: every generated token streams the whole live KV
window.  The XLA path (`ops/attention.py decode_attention`) materializes
fp32 score tensors `[B, n_kv, rep, S]` and — when the cache is int8 —
a dequantized bf16 copy of every layer window, paying extra bandwidth
exactly where bandwidth is the bottleneck.  This kernel streams K/V
tiles once, dequantizes int8 IN REGISTERS (scales fused ahead of the
dots), and keeps the online-softmax state in VMEM scratch — the int8
cache then saves real read bandwidth, not just capacity.

Grid (B, n_kv, S/block_k); the sequential TPU grid makes the ki axis an
online-softmax accumulation, the same structure as the flash forward
(flash_attention.py).  Blocks fully outside the row's live
[valid_from, valid_to) window skip their compute.

Reference role: the decode half of flash_attn_with_kvcache
(realhf/impl/model/modules/attn.py:251) + the paged/ragged decode
kernels serving engines use.  Opt-in via AREAL_DECODE_KERNEL=1 (see
ops/attention.decode_attention); interpret mode covers CPU tests.

DOES NOT LOWER FOR TPU (jax 0.9.0): the `(1, 1)` blocks out of the `[B, 1]`
window bounds and the one-head `(1, block_k, 1)` scale blocks are neither
(8, 128)-aligned nor whole-array, so Pallas refuses them at lowering.  The
kernel has only ever run interpreted; it is queued for deletion (ROADMAP
C1/C2), not repair.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from areal_tpu.ops.pallas.flash_attention import named_call

NEG_INF = -1e30

DEFAULT_BLOCK_K = 512


def _interpret() -> bool:
    from areal_tpu.base.distributed import is_tpu_backend

    return not is_tpu_backend()


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _chunk_kernel(
    lo_ref, hi0_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,  # inputs
    o_ref,  # output
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, block_k: int, nk: int, quant: bool, rep: int,
    nq_tok: int,
):
    """Spec-chunk variant: Q queries per row, query i's live window is
    [lo, hi0 + i) — the causal extension over just-written draft slots
    (see ops/attention.decode_attention_chunk)."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    lo = lo_ref[0, 0]
    hi0 = hi0_ref[0, 0]
    # The widest query sees up to hi0 + nq_tok - 1.
    run = (ki * block_k < hi0 + nq_tok - 1) & ((ki + 1) * block_k > lo)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [Q*rep, d]
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0].astype(jnp.float32)
            v = v * vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Q*rep, bk]
        pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
        mask = (pos >= lo) & (pos < hi0 + qi)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention_chunk_kernel(
    q: jax.Array,  # [B, Q, n_q, d]
    k_cache: jax.Array,  # [B, S, n_kv, d]
    v_cache: jax.Array,
    valid_from: jax.Array,  # [B] int32
    valid_to0: jax.Array,  # [B] int32 — one past query 0's window
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    b, nq_tok, n_q, d = q.shape
    s_max, n_kv = k_cache.shape[1], k_cache.shape[2]
    rep = n_q // n_kv
    block_k = max(min(block_k, s_max), 1)
    while s_max % block_k:
        block_k //= 2
    nk = s_max // block_k
    quant = k_scale is not None
    # Row layout per (b, g): queries major, reps minor -> the kernel's
    # qi = row // rep recovers the query index.
    qh = q.reshape(b, nq_tok, n_kv, rep, d).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(b, n_kv, nq_tok * rep, d)
    lo2 = valid_from.astype(jnp.int32).reshape(b, 1)
    hi2 = jnp.broadcast_to(valid_to0, (b,)).astype(jnp.int32).reshape(b, 1)
    if quant:
        ks, vs = k_scale, v_scale
    else:
        ks = jnp.zeros((b, s_max, n_kv), jnp.bfloat16)
        vs = ks

    kern = functools.partial(
        _chunk_kernel,
        scale=d**-0.5, block_k=block_k, nk=nk, quant=quant, rep=rep,
        nq_tok=nq_tok,
    )
    qr = nq_tok * rep
    out = named_call(
        "decode_chunk",
        kern,
        grid=(b, n_kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, g, ki: (bi, 0)),
            pl.BlockSpec((1, 1), lambda bi, g, ki: (bi, 0)),
            pl.BlockSpec(
                (1, 1, qr, d), lambda bi, g, ki: (bi, g, 0, 0)
            ),
            pl.BlockSpec(
                (1, block_k, 1, d), lambda bi, g, ki: (bi, ki, g, 0)
            ),
            pl.BlockSpec(
                (1, block_k, 1, d), lambda bi, g, ki: (bi, ki, g, 0)
            ),
            pl.BlockSpec((1, block_k, 1), lambda bi, g, ki: (bi, ki, g)),
            pl.BlockSpec((1, block_k, 1), lambda bi, g, ki: (bi, ki, g)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, qr, d), lambda bi, g, ki: (bi, g, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, qr, d), jnp.float32),
        scratch_shapes=[
            _vmem((qr, 1), jnp.float32),
            _vmem((qr, 1), jnp.float32),
            _vmem((qr, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(lo2, hi2, qh, k_cache, v_cache, ks, vs)
    out = out.reshape(b, n_kv, nq_tok, rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, nq_tok, n_q, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention_kernel(
    q: jax.Array,  # [B, 1, n_q, d]
    k_cache: jax.Array,  # [B, S, n_kv, d] (bf16/f32 or int8)
    v_cache: jax.Array,
    valid_from: jax.Array,  # [B] int32
    valid_to: jax.Array,  # [B] int32 or scalar
    k_scale: Optional[jax.Array] = None,  # [B, S, n_kv] when int8
    v_scale: Optional[jax.Array] = None,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Single-token decode attention == the chunk kernel at Q=1: query
    0's window is [lo, hi0 + 0) and the tile-skip bound reduces to the
    same expression, so ONE kernel body serves both (a masking or
    numerics fix cannot diverge them)."""
    return decode_attention_chunk_kernel(
        q, k_cache, v_cache, valid_from, valid_to,
        k_scale=k_scale, v_scale=v_scale, block_k=block_k,
    )

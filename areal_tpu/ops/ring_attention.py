"""Ring attention: context parallelism over the `seq` mesh axis.

Fills the reference's long-context gap (SURVEY §2.6: AReaL has no
CP/ring/Ulysses — long CoT is handled only by packing + micro-batching,
realhf/base/datapack.py:153).  Here sequence chunks live on different
devices and K/V blocks rotate around the ring with `lax.ppermute`, so a
row of length S costs O(S/n) activation memory per device and the
K/V transfer overlaps with the per-block attention compute (XLA schedules
the ppermute concurrently with the einsums of the previous block).

Semantics match areal_tpu/ops/attention.packed_attention_reference exactly:
packed rows, causal within segment, never across segments, padding (seg 0)
fully masked.  Online-softmax accumulation in fp32 (flash-style), so the
result is independent of the number of ring steps.

Layout contract (established by `ring_packed_attention`'s shard_map):
- q/k/v: [B, S, H, d] sharded P((data, fsdp), seq, model, None)
- segment_ids: [B, S] sharded P((data, fsdp), seq)
- sequence chunks are CONTIGUOUS: device c on the seq axis holds global
  positions [c*Sc, (c+1)*Sc).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.base.topology import MODEL_AXIS, SEQ_AXIS
from areal_tpu.ops.attention import NEG_INF, repeat_kv
from areal_tpu.parallel.sharding import BATCH


def _block_update(o, m, l, q, k, v, q_seg, k_seg, q_pos, k_pos, causal):
    """One online-softmax accumulation of a K/V block into (o, m, l).

    q: [B, Sq, H, d]; k/v: [B, Sk, Hkv, d]; o: [B, H, Sq, d];
    m/l: [B, H, Sq].  All accumulation in fp32.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    mask = (q_seg[:, :, None] == k_seg[:, None, :]) & (q_seg > 0)[:, :, None]
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    # Keep fully-masked rows stable: exp(NEG_INF - NEG_INF) would be 1.
    alive = m_new > NEG_INF / 2
    corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
    p = jnp.where(
        alive[..., None], jnp.exp(logits - m_new[..., None]), 0.0
    )
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def _ring_shard(q, k, v, segment_ids, axis_name: str, axis_size: int,
                causal: bool, my_index=None):
    """shard_map body: each seq-axis member holds one contiguous chunk.

    `my_index` overrides `lax.axis_index` for the CP+PP pipeline, whose
    body already receives its seq-axis index as a sharded input.
    """
    b, sq, h, d = q.shape
    my = jax.lax.axis_index(axis_name) if my_index is None else my_index
    q_pos = my * sq + jnp.arange(sq, dtype=jnp.int32)

    o = jnp.zeros((b, h, sq, d), jnp.float32)
    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Step 0 uses the local chunk; each further step rotates K/V first, so no
    # final unused rotation is sent around the ring.
    #
    # Every device runs all axis_size steps in lockstep (the ppermute is a
    # per-step barrier), so causally-dead blocks on low ranks cannot shorten
    # wall-clock under this contiguous-chunk layout; a zigzag/striped chunk
    # assignment that balances causal work is the known follow-up.
    o, m, l = _block_update(
        o, m, l, q, k, v, segment_ids, segment_ids, q_pos, q_pos, causal
    )

    def step(carry, t):
        o, m, l, k, v, k_seg = carry
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        k_seg = jax.lax.ppermute(k_seg, axis_name, perm)
        # After t forward rotations, we hold the chunk born on rank (my - t).
        k_idx = (my - t) % axis_size
        k_pos = k_idx * sq + jnp.arange(sq, dtype=jnp.int32)
        o, m, l = _block_update(
            o, m, l, q, k, v, segment_ids, k_seg, q_pos, k_pos, causal
        )
        return (o, m, l, k, v, k_seg), None

    if axis_size > 1:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.nothing_saveable
        )
        (o, m, l, *_), _ = jax.lax.scan(
            step,
            (o, m, l, k, v, segment_ids),
            jnp.arange(1, axis_size, dtype=jnp.int32),
        )
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l[..., None], 1e-30), 0.0)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, Sq, H, d]


def _zigzag_shard(q, k, v, segment_ids, axis_name: str, axis_size: int,
                  causal: bool):
    """Zigzag shard_map body: device c holds half-chunks (c, 2n-1-c) of 2n.

    Under causal masking, contiguous chunks give rank r only r+1 live
    K/V blocks of n, but the lockstep ring makes every rank pay for n —
    nearly half the attention FLOPs are spent on fully-masked blocks.
    The zigzag assignment gives EVERY rank exactly 2n+1 live half-blocks
    (the causal total divided evenly), so each ring step computes 2
    half-block updates (3 at step 0) instead of 4: ~45% fewer attention
    FLOPs at axis_size=4, identical numerics.
    """
    n = axis_size
    b, sq, h, d = q.shape
    sh = sq // 2
    c = jax.lax.axis_index(axis_name)
    ar = jnp.arange(sh, dtype=jnp.int32)

    def halves(x):
        return x[:, :sh], x[:, sh:]

    q_lo, q_hi = halves(q)
    seg_lo, seg_hi = halves(segment_ids)
    qp_lo = c * sh + ar
    qp_hi = (2 * n - 1 - c) * sh + ar

    def acc():
        return (
            jnp.zeros((b, h, sh, d), jnp.float32),
            jnp.full((b, h, sh), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sh), jnp.float32),
        )

    lo, hi = acc(), acc()

    def upd(accum, qh, qseg, qpos, kh, vh, kseg, kpos):
        o, m, l = accum
        return _block_update(
            o, m, l, qh, kh, vh, qseg, kseg, qpos, kpos, causal
        )

    # Step 0 (the diagonal source s = c): three live half-pairs.
    k_lo, k_hi = halves(k)
    v_lo, v_hi = halves(v)
    lo = upd(lo, q_lo, seg_lo, qp_lo, k_lo, v_lo, seg_lo, qp_lo)
    hi = upd(hi, q_hi, seg_hi, qp_hi, k_lo, v_lo, seg_lo, qp_lo)
    hi = upd(hi, q_hi, seg_hi, qp_hi, k_hi, v_hi, seg_hi, qp_hi)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        lo, hi, k, v, kseg = carry
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kseg = jax.lax.ppermute(kseg, axis_name, perm)
        s = (c - t) % n  # source rank of the chunk we now hold
        k_lo, k_hi = halves(k)
        v_lo, v_hi = halves(v)
        ks_lo, ks_hi = halves(kseg)
        kp_lo = s * sh + ar
        kp_hi = (2 * n - 1 - s) * sh + ar
        # Always live: q half (2n-1-c) vs k half s.
        hi = upd(hi, q_hi, seg_hi, qp_hi, k_lo, v_lo, ks_lo, kp_lo)
        # Exactly one of the remaining pairs is live:
        #   s < c: (q half c, k half s)          -> lo accumulator
        #   s > c: (q half 2n-1-c, k half 2n-1-s) -> hi accumulator
        pred = s < c

        def sel(a, bb):
            return jnp.where(pred, a, bb)

        o_s, m_s, l_s = (
            sel(lo[0], hi[0]), sel(lo[1], hi[1]), sel(lo[2], hi[2]),
        )
        o_n, m_n, l_n = _block_update(
            o_s, m_s, l_s,
            sel(q_lo, q_hi), sel(k_lo, k_hi), sel(v_lo, v_hi),
            sel(seg_lo, seg_hi), sel(ks_lo, ks_hi),
            sel(qp_lo, qp_hi), sel(kp_lo, kp_hi), causal,
        )
        lo = (
            jnp.where(pred, o_n, lo[0]),
            jnp.where(pred, m_n, lo[1]),
            jnp.where(pred, l_n, lo[2]),
        )
        hi = (
            jnp.where(pred, hi[0], o_n),
            jnp.where(pred, hi[1], m_n),
            jnp.where(pred, hi[2], l_n),
        )
        return (lo, hi, k, v, kseg), None

    if n > 1:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.nothing_saveable
        )
        (lo, hi, *_), _ = jax.lax.scan(
            step,
            (lo, hi, k, v, segment_ids),
            jnp.arange(1, n, dtype=jnp.int32),
        )

    def finish(accum):
        o, m, l = accum
        out = jnp.where(
            l[..., None] > 0, o / jnp.maximum(l[..., None], 1e-30), 0.0
        )
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    return jnp.concatenate([finish(lo), finish(hi)], axis=1)


def zigzag_indices(s: int, n: int):
    """(permute, inverse) index arrays mapping contiguous order to the
    zigzag layout: device c's contiguous shard holds halves (c, 2n-1-c)."""
    import numpy as np

    half = s // (2 * n)
    order = []
    for c in range(n):
        order += [c, 2 * n - 1 - c]
    idx = np.concatenate(
        [np.arange(h * half, (h + 1) * half) for h in order]
    )
    return idx.astype(np.int32), np.argsort(idx).astype(np.int32)


def zigzag_ring_packed_attention_prepermuted(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    seq_axis: str = SEQ_AXIS,
) -> jax.Array:
    """Zigzag ring attention over inputs ALREADY in zigzag token order
    (`zigzag_indices`).  The model backbone permutes the sequence once per
    forward and calls this per layer — permuting inside every attention
    call would pay L x 5 cross-shard gathers per forward and eat the FLOP
    saving."""
    n = mesh.shape[seq_axis]
    qkv_spec = P(BATCH, seq_axis, MODEL_AXIS, None)
    seg_spec = P(BATCH, seq_axis)
    return shard_map(
        functools.partial(
            _zigzag_shard, axis_name=seq_axis, axis_size=n, causal=causal
        ),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, segment_ids)


def ring_packed_attention(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,  # [B, S, n_kv, d]
    segment_ids: jax.Array,  # [B, S]
    mesh: Mesh,
    causal: bool = True,
    seq_axis: str = SEQ_AXIS,
    zigzag: bool = False,
) -> jax.Array:
    """Packed varlen attention with the sequence dim sharded over `seq_axis`.

    Drop-in for packed_attention when running under a mesh whose seq axis is
    >1; identical numerics (fp32 online softmax) either way.

    `zigzag=True` (causal only, S % 2n == 0) re-permutes the sequence into
    the balanced zigzag layout, cutting the causally-dead half-blocks the
    contiguous layout pays for (~45% of attention FLOPs at seq=4).  The
    permutation costs 4 gathers in and 1 out PER CALL — model forwards
    should permute once and use the _prepermuted entry point instead.
    """
    n = mesh.shape[seq_axis]
    qkv_spec = P(BATCH, seq_axis, MODEL_AXIS, None)
    seg_spec = P(BATCH, seq_axis)
    if zigzag and causal and n > 1 and q.shape[1] % (2 * n) == 0:
        idx, inv = zigzag_indices(q.shape[1], n)
        outz = zigzag_ring_packed_attention_prepermuted(
            jnp.take(q, idx, axis=1),
            jnp.take(k, idx, axis=1),
            jnp.take(v, idx, axis=1),
            jnp.take(segment_ids, idx, axis=1),
            mesh,
            causal=causal,
            seq_axis=seq_axis,
        )
        return jnp.take(outz, inv, axis=1)
    fn = shard_map(
        functools.partial(
            _ring_shard, axis_name=seq_axis, axis_size=n, causal=causal
        ),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids)

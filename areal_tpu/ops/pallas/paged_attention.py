"""Pallas ragged paged decode-attention kernel.

Decode attention over a block-paged KV pool (`models/transformer.py
PagedKVCache`): each batch row owns an ordered list of pool pages (the
page table), and the kernel gathers K/V pages via SCALAR PREFETCH — the
page table and per-row live lengths ride ahead of the grid in SMEM, and
each grid step's BlockSpec index_map dereferences `page_table[b, pi]` to
fetch that physical page.  Pages at or past a row's live length skip
their compute (`pl.when`), so a 300-token row in a pool sized for 16k
costs 3 page-dots, not 128 — the "ragged" in ragged paged attention.

Numerics are the online-softmax accumulation shared with the dense
decode kernel (`decode_attention.py _chunk_kernel`): fp32 accumulate,
int8 dequant in registers (scales fused ahead of the dots), m/l/acc in
VMEM scratch across the sequential page axis.  One kernel body serves
the single-token (Q=1) and speculative chunk (Q>1) entry points, like
the dense pair.

Reference role: TPU "Ragged Paged Attention" (PAPERS.md) / vLLM
PagedAttention block tables.  Opt-in via AREAL_DECODE_KERNEL=1 (see
ops/attention.paged_decode_attention); interpret mode covers CPU tests.

On a TPU only `_ragged_stream_kernel` (the serving plane's) lowers and
compiles under Mosaic.  `_paged_chunk_kernel` still takes one-head
`(1, ps, 1, d)` blocks out of the `[P, ps, n_kv, d]` pool, which Pallas
refuses at lowering for n_kv > 1; it is queued for deletion (ROADMAP
C1/C2), not repair.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from areal_tpu.ops.pallas.flash_attention import named_call

NEG_INF = -1e30


def _interpret() -> bool:
    from areal_tpu.base.distributed import is_tpu_backend

    return not is_tpu_backend()


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _paged_chunk_kernel(
    pt_ref, hi_ref, ql_ref,  # scalar prefetch: [B, mp] page table,
    # [B] hi0, [B] live query counts (ragged rows)
    q_ref, k_ref, v_ref, ks_ref, vs_ref,  # inputs
    o_ref,  # output
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, page_size: int, n_pages_grid: int, quant: bool,
    rep: int, nq_tok: int,
):
    """Query i's live window is [0, hi0 + i): paged rows are left-aligned
    from flat position 0, so there is no `lo` — pages are mapped
    contiguously and page `pi` covers flat positions
    [pi*page_size, (pi+1)*page_size).

    Ragged rows: only queries i < ql_ref[bi] are live — a decoding slot
    contributes 1, an admitting slot its prompt slice, a parked slot 0.
    Dead queries output exact zeros (fully masked); rows with ql == 0
    skip every page's compute."""
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    hi0 = hi_ref[bi]
    ql = ql_ref[bi]
    # The widest LIVE query sees up to hi0 + ql - 1; later pages hold no
    # live positions for this row (contiguous mapping) and are skipped.
    run = (ql > 0) & (pi * page_size < hi0 + ql - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [Q*rep, d]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [ps, d]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0].astype(jnp.float32)
            v = v * vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Q*rep, ps]
        pos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep
        mask = (pos < hi0 + qi) & (qi < ql)
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

    @pl.when(pi == n_pages_grid - 1)
    def _finish():
        # Fully-masked rows (hi0 == 0) divide 0/1e-30 -> exact zeros,
        # matching the dense kernel and the (fixed) XLA path.
        o_ref[0, 0] = (
            acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
        ).astype(o_ref.dtype)


@jax.jit
def paged_decode_attention_chunk_kernel(
    q: jax.Array,  # [B, Q, n_q, d]
    k_pool: jax.Array,  # [P, ps, n_kv, d] — one layer's pool view
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, max_pages] int32 (sentinel >= P)
    valid_to0: jax.Array,  # [B] int32 — one past query 0's window
    k_scale: Optional[jax.Array] = None,  # [P, ps, n_kv] when int8
    v_scale: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,  # [B] int32 live queries per row
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    b, nq_tok, n_q, d = q.shape
    n_pool, ps, n_kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[1]
    rep = n_q // n_kv
    quant = k_scale is not None
    # Unmapped sentinel entries must still produce a legal index for the
    # prefetched index_map (their compute is skipped / masked anyway) —
    # the one clamp-then-mask rule shared with the XLA gather fallback.
    from areal_tpu.ops.attention import clamp_page_table

    pt = clamp_page_table(page_table, n_pool)
    hi = jnp.broadcast_to(valid_to0, (b,)).astype(jnp.int32)
    if q_lens is None:
        ql = jnp.full((b,), nq_tok, jnp.int32)
    else:
        ql = jnp.broadcast_to(q_lens, (b,)).astype(jnp.int32)
    qh = q.reshape(b, nq_tok, n_kv, rep, d).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(b, n_kv, nq_tok * rep, d)
    if quant:
        ks, vs = k_scale, v_scale
    else:
        ks = jnp.zeros((n_pool, ps, n_kv), jnp.bfloat16)
        vs = ks

    kern = functools.partial(
        _paged_chunk_kernel,
        scale=d**-0.5, page_size=ps, n_pages_grid=mp, quant=quant,
        rep=rep, nq_tok=nq_tok,
    )
    qr = nq_tok * rep
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_kv, mp),
        in_specs=[
            pl.BlockSpec(
                (1, 1, qr, d), lambda bi, g, pi, pt, hi, ql: (bi, g, 0, 0)
            ),
            pl.BlockSpec(
                (1, ps, 1, d),
                lambda bi, g, pi, pt, hi, ql: (pt[bi, pi], 0, g, 0),
            ),
            pl.BlockSpec(
                (1, ps, 1, d),
                lambda bi, g, pi, pt, hi, ql: (pt[bi, pi], 0, g, 0),
            ),
            pl.BlockSpec(
                (1, ps, 1),
                lambda bi, g, pi, pt, hi, ql: (pt[bi, pi], 0, g),
            ),
            pl.BlockSpec(
                (1, ps, 1),
                lambda bi, g, pi, pt, hi, ql: (pt[bi, pi], 0, g),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, qr, d), lambda bi, g, pi, pt, hi, ql: (bi, g, 0, 0)
        ),
        scratch_shapes=[
            _vmem((qr, 1), jnp.float32),
            _vmem((qr, 1), jnp.float32),
            _vmem((qr, d), jnp.float32),
        ],
    )
    out = named_call(
        "paged_chunk",
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, qr, d), jnp.float32),
        interpret=_interpret(),
    )(pt, hi, ql, qh, k_pool, v_pool, ks, vs)
    out = out.reshape(b, n_kv, nq_tok, rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, nq_tok, n_q, d).astype(q.dtype)


def _ragged_stream_kernel(
    pt_ref, vt_ref,  # scalar prefetch: [T * mp] flat per-token page
    # tables, [T] per-token windows (one past last visible slot; 0 = dead)
    q_ref, k_ref, v_ref, *rest,  # inputs (+ ks_ref, vs_ref when quant)
    scale: float, page_size: int, n_pages_grid: int, quant: bool,
):
    """One grid row per PACKED stream token: the serving megakernel.

    Unlike `_paged_chunk_kernel` (one grid row per slot, W query lanes
    masked per row), the stream carries only live query lanes — decode,
    chunked-prefill, episode-observation and spec-verify tokens side by
    side, each with its own page-table row and its own window
    [0, vt_ref[ti]).  A token's cost is ceil(vt/ps) page-dots over its
    query heads; there are no dead in-row lanes to mask.  Stream slack
    lanes (vt == 0) skip every page and emit exact zeros.

    Each grid step holds one WHOLE page — all `n_kv` heads — and the body
    picks head `g` out of it: Mosaic only accepts blocks whose last two
    dims are (8, 128)-aligned or span the array, which a one-head
    (ps, 1, d) block out of the [P, ps, n_kv, d] pool is not, while
    (ps, n_kv, d) is.

    Init and finish are UNCONDITIONAL: a dead lane has zero `run`
    iterations, so the final write must come from the initialized
    scratch, not from compute."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ti = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    vt = vt_ref[ti]
    run = (vt > 0) & (pi * page_size < vt)

    @pl.when(run)
    def _compute():
        if quant:
            ks = ks_ref[0].astype(jnp.float32)  # [ps, n_kv]
            vs = vs_ref[0].astype(jnp.float32)
        for g in range(k_ref.shape[2]):  # n_kv, static
            q = q_ref[0, g].astype(jnp.float32)  # [rep, d]
            k = k_ref[0, :, g, :].astype(jnp.float32)  # [ps, d]
            v = v_ref[0, :, g, :].astype(jnp.float32)
            if quant:
                k = k * ks[:, g:g + 1]
                v = v * vs[:, g:g + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rep, ps]
            pos = pi * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            mask = pos < vt
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[g] = m_new

    @pl.when(pi == n_pages_grid - 1)
    def _finish():
        # Dead lanes (vt == 0) divide 0/1e-30 -> exact zeros, matching
        # the XLA ragged fallback.
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@jax.jit
def ragged_paged_attention_kernel(
    q: jax.Array,  # [T, n_q, d] — packed token stream
    k_pool: jax.Array,  # [P, ps, n_kv, d] — one layer's pool view
    v_pool: jax.Array,
    page_table_tok: jax.Array,  # [T, max_pages] int32 (sentinel >= P)
    valid_to: jax.Array,  # [T] int32 — one past each token's window
    k_scale: Optional[jax.Array] = None,  # [P, ps, n_kv] when int8
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    t, n_q, d = q.shape
    n_pool, ps, n_kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    mp = page_table_tok.shape[1]
    rep = n_q // n_kv
    quant = k_scale is not None
    from areal_tpu.ops.attention import clamp_page_table

    # Flat [T * mp]: a 2-D int32 table in SMEM pads its minor dim to 128
    # words per row, a 1-D one only to the next KiB overall.  SMEM is the
    # bound on stream width x window: the v5e's 1 MiB holds T * mp up to
    # ~260k entries (Mosaic reports "ran out of memory in memory space
    # smem" past that).
    pt = clamp_page_table(page_table_tok, n_pool).reshape(t * mp)
    vt = jnp.broadcast_to(valid_to, (t,)).astype(jnp.int32)
    qh = q.reshape(t, n_kv, rep, d)

    def token(ti, pi, pt, vt):
        return (ti, 0, 0, 0)

    def kv_page(ti, pi, pt, vt):
        return (pt[ti * mp + pi], 0, 0, 0)

    def scale_page(ti, pi, pt, vt):
        return (pt[ti * mp + pi], 0, 0)

    kv_spec = pl.BlockSpec((1, ps, n_kv, d), kv_page)
    inputs = [qh, k_pool, v_pool]
    in_specs = [pl.BlockSpec((1, n_kv, rep, d), token), kv_spec, kv_spec]
    if quant:
        scale_spec = pl.BlockSpec((1, ps, n_kv), scale_page)
        inputs += [k_scale, v_scale]
        in_specs += [scale_spec, scale_spec]

    kern = functools.partial(
        _ragged_stream_kernel,
        scale=d**-0.5, page_size=ps, n_pages_grid=mp, quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv, rep, d), token),
        scratch_shapes=[
            _vmem((n_kv, rep, 1), jnp.float32),
            _vmem((n_kv, rep, 1), jnp.float32),
            _vmem((n_kv, rep, d), jnp.float32),
        ],
    )
    out = named_call(
        "ragged_stream",
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n_kv, rep, d), jnp.float32),
        interpret=_interpret(),
    )(pt, vt, *inputs)
    return out.reshape(t, n_q, d).astype(q.dtype)


@jax.jit
def paged_decode_attention_kernel(
    q: jax.Array,  # [B, 1, n_q, d]
    k_pool: jax.Array,  # [P, ps, n_kv, d]
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, max_pages] int32
    valid_to: jax.Array,  # [B] int32 or scalar
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token paged decode == the chunk kernel at Q=1 (one body,
    same rationale as the dense pair)."""
    return paged_decode_attention_chunk_kernel(
        q, k_pool, v_pool, page_table, valid_to,
        k_scale=k_scale, v_scale=v_scale,
    )

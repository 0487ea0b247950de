"""Pallas ragged paged attention kernel: the serving plane's stream kernel.

Attention over a block-paged KV pool (`models/transformer.py
PagedKVCache`): each packed stream token carries its owning row's ordered
list of pool pages (the page table), and the kernel gathers K/V pages via
SCALAR PREFETCH — the per-token page tables and windows ride ahead of the
grid in SMEM, and each grid step's BlockSpec index_map dereferences the
table to fetch that physical page.  Pages at or past a token's window skip
their compute (`pl.when`), so a 300-token row in a pool sized for 16k
costs 3 page-dots, not 128 — the "ragged" in ragged paged attention.

Numerics are the online-softmax accumulation shared with the dense
decode kernel (`decode_attention.py _chunk_kernel`): fp32 accumulate,
int8 dequant in registers (scales fused ahead of the dots), m/l/acc in
VMEM scratch across the sequential page axis.

Reference role: TPU "Ragged Paged Attention" (PAPERS.md) / vLLM
PagedAttention block tables.  Opt-in via AREAL_DECODE_KERNEL=1 (see
ops/attention.ragged_paged_attention); interpret mode covers CPU tests.
`_ragged_stream_kernel` lowers and compiles under Mosaic
(tests/test_flash_attention.py::TestTPULowering).
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


def _ragged_stream_kernel(
    pt_ref, vt_ref,  # scalar prefetch: [T * mp] flat per-token page
    # tables, [T] per-token windows (one past last visible slot; 0 = dead)
    q_ref, k_ref, v_ref, *rest,  # inputs (+ ks_ref, vs_ref when quant)
    scale: float, page_size: int, n_pages_grid: int, quant: bool,
):
    """One grid row per PACKED stream token: the serving megakernel.

    Instead of one grid row per slot with W query lanes masked per
    row, the stream carries only live query lanes — decode,
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

"""Pallas ragged paged attention kernel: the serving chunk's attention.

Attention of a PACKED token stream over the block-paged KV pool
(`models/transformer.py PagedKVCache`, pages `[L, P, ps, n_kv * d]`: a
page is whole (ps, n_kv * d) tiles and one head a lane-aligned slice of
it).  The STACKED pool is the kernel's operand: it stays in HBM
(`memory_space=ANY`), the layer index rides in SMEM, and the kernel
copies a lane's LIVE pages itself, addressed `(layer, page)`, into a
ring of VMEM buffers, several copies in flight — so no layer's pool is
sliced out of the stack, no per-lane window is gathered and no score
block is written.

What the kernel walks is a WORK LIST (`live_page_schedule`), made once
per forward from the page tables and the windows (they are the same for
every layer): one item per (run of lanes, page).  The stream is cut into
tiles of `TL` lanes; inside a tile, consecutive lanes whose table names
the same pool page at column j — the lanes of one prefilling or
spec-verifying row — are one run: one page copy, one pair of MXU passes.
A decode lane is a run of one.  Pages at or past a lane's window, and
every page of a dead lane, are not in the list at all: no copy, no loop
iteration.  The grid is the tiles (12 for the 96-lane cell), not
(lane, page): a grid step costs 0.3-0.5 us live or not (PERF.md, PR 34).

Every item multiplies the tile's WHOLE query block `[TL * rep, d]` into
the page (the MXU pass is bound by the 128 x 128 page it loads, not by
the rows it streams) and masks the rows outside the run, for which the
online-softmax update is then the identity.

Numerics: bf16 operands into the MXU with fp32 accumulation and an fp32
online softmax — what `ops/attention.decode_attention` (the XLA form)
states.  An int8 pool's codes go to the MXU as they are (exact in bf16)
and the per-position scales multiply the fp32 scores and probabilities.
Dead lanes (`valid_to == 0`) emit exact zeros.

Reference role: TPU "Ragged Paged Attention" (PAPERS.md) / vLLM
PagedAttention block tables.  `ops/attention.ragged_paged_attention`
takes this kernel on a TPU backend; interpret mode covers the CPU tests
(tests/test_paged_kv.py), and it lowers and compiles under Mosaic
(tests/test_flash_attention.py::TestTPULowering).
"""

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from areal_tpu.ops.pallas.flash_attention import named_call

NEG_INF = -1e30


def _interpret() -> bool:
    from areal_tpu.base.distributed import is_tpu_backend

    return not is_tpu_backend()


def lane_tile(rep: int) -> int:
    """Lanes per tile: the smallest count >= 8 whose query block
    `[TL * rep, d]` is whole bf16 tiles (16 rows)."""
    return max(8, 16 // math.gcd(rep, 16))


# VMEM the ring of page buffers may take (K and V), and its depth: a
# page copy is ~1 us of latency for 0.16 us of bandwidth (64 KB at 819
# GB/s), so one copy in flight starves the loop (PERF.md, PR 37).
_RING_BYTES = 4 << 20
_RING_MAX = 4


class PagedSchedule(NamedTuple):
    """The live (run of lanes, page) items of one packed stream, tile by
    tile.  Flat int32 arrays (a 2-D table in SMEM pads its minor dim to
    128 words a row): tile i owns items tile_lo[i] .. tile_lo[i + 1];
    item w reads pool page `page[w]` and `meta[w]` packs (table column
    j) << 16 | (first lane of the run in its tile) << 8 | (lanes in the
    run).  `valid_rows` [n_tiles, TL * rep, 1] is each query row's
    window."""

    tile_lo: jax.Array  # [n_tiles + 1]
    page: jax.Array  # [n_tiles * TL * mp]
    meta: jax.Array
    valid_rows: jax.Array


def live_page_schedule(
    page_table_tok: jax.Array,  # [T, mp] int32 (sentinel >= n_pool)
    valid_to: jax.Array,  # [T] int32 — one past each lane's window
    n_pool: int,
    page_size: int,
    rep: int,
) -> PagedSchedule:
    from areal_tpu.ops.attention import clamp_page_table

    t, mp = page_table_tok.shape
    tl = lane_tile(rep)
    nt = -(-t // tl)
    pad = nt * tl - t
    pt = clamp_page_table(page_table_tok, n_pool)
    vt = jnp.broadcast_to(valid_to, (t,)).astype(jnp.int32)
    if pad:
        pt = jnp.pad(pt, [(0, pad), (0, 0)])
        vt = jnp.pad(vt, [(0, pad)])
    pt = pt.reshape(nt, tl, mp)
    live = (jnp.arange(mp) * page_size < vt[:, None]).reshape(nt, tl, mp)
    # A lane continues the run of the lane before it when both are live
    # at column j and name the same page there.
    cont = live[:, 1:] & live[:, :-1] & (pt[:, 1:] == pt[:, :-1])
    cont = jnp.pad(cont, [(0, 0), (1, 0), (0, 0)])
    start = live & ~cont
    run = jnp.cumsum(~cont, axis=1)  # a dead lane is a run of its own
    length = jnp.sum(run[:, :, None] == run[:, None, :], axis=2)
    # Items in the order the kernel walks them: tile, column, lane.
    def flat(a):
        return a.transpose(0, 2, 1).reshape(-1)

    order = jnp.argsort(~flat(start), stable=True).astype(jnp.int32)
    col = (order // tl) % mp
    meta = (col << 16) | ((order % tl) << 8) | flat(length)[order]
    per_tile = jnp.sum(start, axis=(1, 2), dtype=jnp.int32)
    tile_lo = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(per_tile, dtype=jnp.int32)]
    )
    valid_rows = jnp.repeat(vt.reshape(nt, tl), rep, axis=1)[..., None]
    return PagedSchedule(
        tile_lo, flat(pt)[order], meta.astype(jnp.int32), valid_rows
    )


def _ragged_paged_kernel(
    layer_ref, lo_ref, page_ref, meta_ref,  # scalar prefetch (SMEM)
    q_ref, vt_ref, k_hbm, v_hbm, *rest,
    scale: float, page_size: int, rep: int, quant: bool,
):
    """One grid step per TILE of lanes; inside it, a loop over the tile's
    live (run, page) items with the next items' page copies in flight —
    they may belong to the next tile, so the copies never drain at a
    tile boundary.  Init and finish are unconditional: a tile with no
    item writes the zeros of its initialised scratch."""
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem = rest[:8]
        m_scr, l_scr, acc_scr = rest[8:]
    else:
        o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    n_tiles = pl.num_programs(0)
    li = layer_ref[0]
    n_work = lo_ref[n_tiles]
    ring = kbuf.shape[0]
    d = q_ref.shape[3]

    def copies(w, slot):
        page = page_ref[w]
        out = [
            pltpu.make_async_copy(
                k_hbm.at[li, page], kbuf.at[slot], sem.at[0, slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[li, page], vbuf.at[slot], sem.at[1, slot]
            ),
        ]
        if quant:
            out += [
                pltpu.make_async_copy(
                    ks_hbm.at[li, page], ksbuf.at[slot], sem.at[2, slot]
                ),
                pltpu.make_async_copy(
                    vs_hbm.at[li, page], vsbuf.at[slot], sem.at[3, slot]
                ),
            ]
        return out

    @pl.when(i == 0)
    def _fill():
        for w in range(ring - 1):  # static

            @pl.when(w < n_work)
            def _start():
                for c in copies(w, w):
                    c.start()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    vt = vt_ref[0]  # [TL * rep, 1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (vt.shape[0], page_size), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (vt.shape[0], page_size), 1)

    def item(w, carry):
        slot = w % ring
        for c in copies(w, slot):
            c.wait()
        ahead = w + ring - 1  # into the buffer item w - 1 is done with

        @pl.when(ahead < n_work)
        def _next():
            for c in copies(ahead, ahead % ring):
                c.start()

        meta = meta_ref[w]
        first = ((meta >> 8) & 0xFF) * rep
        last = first + (meta & 0xFF) * rep
        mask = (
            (rows >= first) & (rows < last)
            & ((meta >> 16) * page_size + cols < vt)
        )
        for g in range(q_ref.shape[1]):  # n_kv, static
            q = q_ref[0, g]  # [TL * rep, d]
            k = kbuf[slot, :, g * d:(g + 1) * d].astype(q.dtype)  # [ps, d]
            v = vbuf[slot, :, g * d:(g + 1) * d].astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [TL * rep, ps]
            if quant:
                s = s * ksbuf[slot, g:g + 1, :].astype(jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vsbuf[slot, g:g + 1, :].astype(jnp.float32)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[g] = m_new
        return carry

    jax.lax.fori_loop(lo_ref[i], lo_ref[i + 1], item, 0)
    # A row no item touched (dead lane) divides 0 / 1e-30: exact zero.
    o_ref[0] = (
        acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    ).astype(o_ref.dtype)


@jax.jit
def ragged_paged_attention_kernel(
    q: jax.Array,  # [T, n_q, d] — packed token stream
    k_pool: jax.Array,  # [L, P, ps, n_kv * d] — the STACKED pool
    v_pool: jax.Array,
    layer: jax.Array,  # int32 scalar — which layer's pages to read
    page_table_tok: jax.Array,  # [T, max_pages] int32 (sentinel >= P)
    valid_to: jax.Array,  # [T] int32 — one past each token's window
    k_scale: Optional[jax.Array] = None,  # [L, P, n_kv, ps] when int8
    v_scale: Optional[jax.Array] = None,
    schedule: Optional[PagedSchedule] = None,
) -> jax.Array:
    """`schedule` is `live_page_schedule` of the same tables and windows:
    a caller that runs many layers over one stream makes it once."""
    from jax.experimental.pallas import tpu as pltpu

    t, n_q, d = q.shape
    _, n_pool, ps, row = k_pool.shape
    n_kv = row // d
    rep = n_q // n_kv
    quant = k_scale is not None
    if schedule is None:
        schedule = live_page_schedule(page_table_tok, valid_to, n_pool, ps, rep)
    nt, tlr, _ = schedule.valid_rows.shape
    tl = tlr // rep
    # Head-major query blocks: rows (lane, r) of one KV head together.
    qh = jnp.pad(q, [(0, nt * tl - t), (0, 0), (0, 0)])
    qh = qh.reshape(nt, tl, n_kv, rep, d).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(nt, n_kv, tlr, d)

    def tile(i, *_):
        return (i, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    inputs = [qh, schedule.valid_rows, k_pool, v_pool]
    in_specs = [
        pl.BlockSpec((1, n_kv, tlr, d), tile),
        pl.BlockSpec((1, tlr, 1), lambda i, *_: (i, 0, 0)),
        hbm, hbm,
    ]
    page_bytes = 2 * ps * row * k_pool.dtype.itemsize
    ring = max(2, min(_RING_MAX, _RING_BYTES // page_bytes))
    scratch = [
        pltpu.VMEM((ring, ps, row), k_pool.dtype),
        pltpu.VMEM((ring, ps, row), v_pool.dtype),
    ]
    if quant:
        inputs += [k_scale, v_scale]
        in_specs += [hbm, hbm]
        scratch += [
            pltpu.VMEM((ring, n_kv, ps), k_scale.dtype),
            pltpu.VMEM((ring, n_kv, ps), v_scale.dtype),
        ]
    scratch += [
        pltpu.SemaphoreType.DMA((4 if quant else 2, ring)),
        pltpu.VMEM((n_kv, tlr, 1), jnp.float32),
        pltpu.VMEM((n_kv, tlr, 1), jnp.float32),
        pltpu.VMEM((n_kv, tlr, d), jnp.float32),
    ]
    kern = functools.partial(
        _ragged_paged_kernel,
        scale=d**-0.5, page_size=ps, rep=rep, quant=quant,
    )
    out = named_call(
        "ragged_paged",
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nt,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_kv, tlr, d), tile),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((nt, n_kv, tlr, d), q.dtype),
        # The page copies run ahead across tiles: the tiles are a sequence.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=_interpret(),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        schedule.tile_lo, schedule.page, schedule.meta, *inputs,
    )
    out = out.reshape(nt, n_kv, tl, rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(nt * tl, n_q, d)[:t]

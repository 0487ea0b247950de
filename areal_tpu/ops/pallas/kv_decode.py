"""Pallas decode kernel of softmax attention over the static program's
STACKED k/v cache: one token a row (or one block of tokens that share a
window, folded into the query heads) attends over the live keys of its
layer where they lie.

As XLA ops (`ops/attention.decode_attention`) the step slices the layer's K
and V out of the stacked cache — a copy, and with `(n_kv, d)` minor a
re-layout — and scores every ALLOCATED slot in fp32: 0.63 of a 5.03 ms
iteration in `q1p5b-decode-static`, 0.60 of 3.52 ms in `olmoe-decode-tail`
(ledger, PR 68).  This kernel takes the stacked caches `[L, B, S, n_kv, d]`
themselves, viewed `[L, B, S * n_kv, d]` — a slot's heads as rows of their
own, which is how the cache lies in HBM, so the view is a bitcast — the
layer a prefetched scalar in the index map, so nothing is sliced, copied or
re-laid; streams a tile of `block_s` slots of `rows` rows a grid step;
keeps the online-softmax state in VMEM; and neither fetches nor computes
the tiles outside the rows' live windows `[valid_from, valid_to)`.

A row's queries — every head, and in the block step every token — are ONE
[H, d] operand and meet a tile's [block_s * n_kv, d] rows in one product a
side; a score counts where the row's key head is the query head's.  The
products of the other key heads are wasted MXU passes (n_kv - 1 of n_kv),
which the stream of the tile hides at the cells' shapes; picking a head's
rows out of the tile would be a strided read of packed bf16 sublanes.

Grid (row tile, slot tile), the slot axis the sequential one: a row tile's
dead slot tiles repeat the block index of its nearest live one, which
Pallas does not fetch again, and skip their compute.  bf16 operands into
the MXU, fp32 accumulation, fp32 softmax state — `decode_attention`'s
arithmetic over the live keys alone; an empty window gives exact zeros.

`transformer._attention_step` / `_attention_block_step` take it where the
plan's cache is k/v alone, on one TPU device (`transformer.kv_kernel_form`);
everything else keeps `decode_attention`.  Interpret mode covers the CPU
tests (`tests/test_kv_decode.py`).  This module is imported inside the
branch that takes the kernel and nowhere else: a process whose plan keeps
more than k/v never loads it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

NEG_INF = -1e30
LANES = 128
BLOCK_S = 128  # slots a grid step: the finest cut of a live window
MAX_ROWS = 8  # rows a grid step at most
BLOCK_BYTES = 1 << 20  # of K (and as much of V) a grid step at most
HEAD_TILE = 16  # query heads a key head are padded to whole bf16 tiles

# Times the kernel's entry point was traced since the process started
# (`traced`): a program with many layers traces it once.
_TRACED = [0]


def traced() -> int:
    """Traces of `kv_decode`'s body so far — read before and after tracing
    a program: the difference is 1 however many layers call it."""
    return _TRACED[0]


def fits(s_max: int, head_dim: int) -> bool:
    """Whether the kernel can cut the shapes: a head whole 128-lane tiles,
    the window whole 128-slot tiles."""
    return head_dim % LANES == 0 and s_max % BLOCK_S == 0


def blocks_for(b: int, s_max: int, n_kv: int, d: int, itemsize: int = 2):
    """(rows, slots) a grid step: BLOCK_S slots (the whole window where it
    is no multiple: a toy) of the most rows — a divisor of the batch, at
    most MAX_ROWS — whose K tile stays within BLOCK_BYTES.  Few, large
    steps: a step costs 0.35 us live or not, and 8 rows x 128 slots of 2
    heads are 512 KB (kernel alone at the cells' shapes, us a call: 9.1 at
    (8, 128) against 11.6 at (4, 256) and 16.9 at (4, 640); chip run, PR
    69)."""
    block_s = BLOCK_S if s_max % BLOCK_S == 0 else s_max
    most = max(1, BLOCK_BYTES // (block_s * n_kv * d * itemsize))
    rows = max(r for r in range(1, min(b, MAX_ROWS, most) + 1) if b % r == 0)
    return rows, block_s


def live_tiles(lo, hi, rows: int, block_s: int, ns: int):
    """valid_from / valid_to [B] -> (first, last) [B / rows] int32: the
    slot tiles a row tile's windows touch, inclusive; `first > last` where
    every window of the tile is empty."""
    lo, hi = lo.reshape(-1, rows), hi.reshape(-1, rows)
    live = hi > lo
    first = jnp.min(jnp.where(live, lo, ns * block_s), axis=1) // block_s
    last = jnp.max(jnp.where(live, hi - 1, -block_s), axis=1) // block_s
    return first.astype(jnp.int32), last.astype(jnp.int32)


def tile_index(si, first, last, ns: int):
    """The slot tile step `si` of a row tile names: itself inside [first,
    last], else the nearest live one — the block the step before or after
    holds, which is not fetched again.  A row tile with no live window
    names tile 0 throughout."""
    return jnp.clip(si, jnp.minimum(first, ns - 1), jnp.maximum(last, 0))


def _kv_decode_kernel(
    layer_ref, lo_ref, hi_ref, first_ref, last_ref,  # prefetched scalars
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, block_s: int, ns: int, rows: int, n_kv: int, n_q: int,
):
    """One (row tile, slot tile) step.  A row's queries [H, d] — every head
    of every token of the row — meet the tile's [block_s * n_kv, d] rows in
    ONE product a side; a score counts where the row's key head is the
    query head's (`head_ok`) and its slot is live.  The inner loops are
    `lax` primitives: a `jnp` operator on a traced value is a `jit` call of
    its own (PERF.md section 6, PR 60)."""
    del layer_ref  # read by the index map alone
    ti, si = pl.program_id(0), pl.program_id(1)
    f32, i32 = jnp.float32, jnp.int32
    hp, d = q_ref.shape[1:]
    cols = block_s * n_kv

    @pl.when(si == 0)
    def _init():
        m_scr[...] = lax.full(m_scr.shape, NEG_INF, f32)
        l_scr[...] = lax.full(l_scr.shape, 0.0, f32)
        acc_scr[...] = lax.full(acc_scr.shape, 0.0, f32)

    @pl.when((si >= first_ref[ti]) & (si <= last_ref[ti]))
    def _compute():
        def full(x):
            return lax.full((hp, cols), x, i32)

        col = lax.broadcasted_iota(i32, (hp, cols), 1)  # slot * n_kv + head
        head = lax.broadcasted_iota(i32, (hp, cols), 0)  # token * n_q + head
        head_ok = lax.eq(
            lax.div(lax.rem(head, full(n_q)), full(n_q // n_kv)),
            lax.rem(col, full(n_kv)))
        pos = lax.add(
            lax.div(col, full(n_kv)), lax.broadcast(si * block_s, (hp, cols)))
        neg = lax.full((hp, cols), NEG_INF, f32)
        zero = lax.full((hp, cols), 0.0, f32)
        here = [ti * rows + r for r in range(rows)]
        # Stage by stage over the tile's rows, not row by row: Mosaic does
        # not interleave the unrolled rows' chains by itself.
        masks = [
            lax.bitwise_and(head_ok, lax.bitwise_and(
                lax.ge(pos, lax.broadcast(lo_ref[b], (hp, cols))),
                lax.lt(pos, lax.broadcast(hi_ref[b], (hp, cols)))))
            for b in here
        ]
        scores = [
            lax.select(masks[r], lax.mul(lax.dot_general(
                q_ref[r], k_ref[r], (((1,), (1,)), ((), ())),
                preferred_element_type=f32), lax.full((hp, cols), scale, f32)),
                neg)
            for r in range(rows)
        ]  # [hp, cols] fp32 a row
        m_prev = [m_scr[r] for r in range(rows)]
        m_new = [
            lax.max(m_prev[r], lax.broadcast_in_dim(
                lax.reduce_max(scores[r], (1,)), (hp, 1), (0,)))
            for r in range(rows)
        ]
        probs = [
            lax.select(masks[r], lax.exp(lax.sub(
                scores[r], lax.broadcast_in_dim(
                    m_new[r], (hp, cols), (0, 1)))), zero)
            for r in range(rows)
        ]
        for r in range(rows):
            alpha = lax.exp(lax.sub(m_prev[r], m_new[r]))  # [hp, 1]
            l_scr[r] = lax.add(
                lax.mul(alpha, l_scr[r]), lax.broadcast_in_dim(
                    lax.reduce_sum(probs[r], (1,)), (hp, 1), (0,)))
            acc_scr[r] = lax.add(
                lax.mul(acc_scr[r], lax.broadcast_in_dim(
                    alpha, (hp, d), (0, 1))),
                lax.dot_general(
                    probs[r].astype(v_ref.dtype), v_ref[r],
                    (((1,), (0,)), ((), ())), preferred_element_type=f32))
            m_scr[r] = m_new[r]

    @pl.when(si == ns - 1)
    def _finish():
        # An empty live window leaves l = 0 and acc = 0: exact zeros.
        o_ref[...] = lax.div(
            acc_scr[...], lax.broadcast_in_dim(
                lax.max(l_scr[...], lax.full(l_scr.shape, 1e-30, f32)),
                acc_scr.shape, (0, 1, 2))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kv_decode(q, k_cache, v_cache, layer, valid_from, valid_to, block,
               interpret):
    _TRACED[0] += 1
    b, n_tok, n_q, d = q.shape
    n_layers, _, s_max, n_kv, _ = k_cache.shape
    rows, block_s = block or blocks_for(
        b, s_max, n_kv, d, k_cache.dtype.itemsize)
    assert b % rows == 0 and s_max % block_s == 0, (b, s_max, rows, block_s)
    ns = s_max // block_s
    heads = n_tok * n_q
    hp = -(-heads // HEAD_TILE) * HEAD_TILE
    qp = jnp.pad(
        q.astype(k_cache.dtype).reshape(b, heads, d),
        ((0, 0), (0, hp - heads), (0, 0)))
    lo = valid_from
    hi = jnp.broadcast_to(valid_to, (b,))
    first, last = live_tiles(lo, hi, rows, block_s, ns)
    # A slot's heads as rows of their own, [slots * n_kv, d]: the cache as
    # it lies, a bitcast (`(n_kv, d)` minor, `n_kv = 2` is a tile of 2 rows
    # in HBM and would be one of 16 in VMEM: `transformer.PagedKVCache`).
    flat = (n_layers, b, s_max * n_kv, d)

    def whole(ti, si, *_):
        return ti, 0, 0

    def tile(ti, si, layer_ref, lo_ref, hi_ref, first_ref, last_ref):
        return layer_ref[0], ti, tile_index(
            si, first_ref[ti], last_ref[ti], ns), 0

    kern = functools.partial(
        _kv_decode_kernel, scale=d**-0.5, block_s=block_s, ns=ns, rows=rows,
        n_kv=n_kv, n_q=n_q,
    )
    out = named_call(
        "kv_decode",
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b // rows, ns),
            in_specs=[
                pl.BlockSpec((rows, hp, d), whole),
                pl.BlockSpec((None, rows, block_s * n_kv, d), tile),
                pl.BlockSpec((None, rows, block_s * n_kv, d), tile),
            ],
            out_specs=pl.BlockSpec((rows, hp, d), whole),
            scratch_shapes=[
                pltpu.VMEM((rows, hp, 1), jnp.float32),
                pltpu.VMEM((rows, hp, 1), jnp.float32),
                pltpu.VMEM((rows, hp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        layer.reshape(1), lo, hi, first, last, qp,
        k_cache.reshape(flat), v_cache.reshape(flat),
    )
    return out[:, :heads].reshape(q.shape)


def kv_decode(
    q: jax.Array,  # [B, T, n_q, d] — T tokens a row that share its window
    k_cache: jax.Array,  # [L, B, S_max, n_kv, d] — the STACKED cache
    v_cache: jax.Array,
    layer: jax.Array,  # scalar int32 — the layer whose keys are read
    valid_from: jax.Array,  # [B] int — first valid cache slot per row
    valid_to: jax.Array,  # scalar/[B] int — one past the last valid slot
    block=None,  # (rows, slots) a grid step; None: `blocks_for`
) -> jax.Array:
    """-> [B, T, n_q, d]: softmax(q . k / sqrt(d)) v over each row's live
    window of layer `layer`, query head h against key head h // (n_q /
    n_kv), every token of a row over the same window (T = 1: the token
    loop; the block step's Q tokens).  One `jit` entry point: a program
    traces the body once however many layers call it (the backend is asked
    out here: a cached trace must not be another backend's)."""
    with jax.named_scope("layer/attn"):
        # One type an operand, whoever calls: a Python int and an int32
        # array would be two traces.
        return _kv_decode(
            q, k_cache, v_cache, jnp.asarray(layer, jnp.int32),
            jnp.asarray(valid_from, jnp.int32),
            jnp.asarray(valid_to, jnp.int32), block, _interpret())

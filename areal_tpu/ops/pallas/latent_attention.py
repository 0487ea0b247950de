"""Pallas decode kernel of latent attention's ABSORBED form: one token a
row attends over the latent rows of its window, which are keys and values
at once.

As XLA ops the two products (scores against the rows, the weighted sum of
the rows) want the window in two layouts, and XLA:TPU carries the second
one back into the cache: two re-layouts of the WHOLE stacked cache and a
materialised copy of the layer's window every decode iteration (10.9 ms an
iteration where the bytes allow 2.3: chip run, PR 38).  This kernel reads
the stacked cache where it lies — the layer is a prefetched scalar in the
index map, so no layer is sliced out — streams each tile of a row's window
ONCE for both products, keeps the online-softmax state in VMEM, and does
not fetch the tiles past a row's live window `[valid_from, valid_to)`.

Grid (B, S / block_s): the sequential TPU grid makes the tile axis an
online-softmax accumulation (the structure of `flash_fwd`); a row's dead
tiles repeat the block index of its last live one, which Pallas does not
fetch again, and skip their compute.  `ops/attention.latent_decode_
attention` takes this kernel on a TPU backend and the XLA form elsewhere;
interpret mode covers the CPU tests.  Rows are independent, so a mesh that
spreads them over devices runs the kernel per device on its own rows
(`latent_decode_kernel_sharded`).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

NEG_INF = -1e30
MAX_BLOCK_S = 640  # window slots a grid step streams: 737 KB of bf16 rows
HEAD_TILE = 16  # query heads are padded to whole bf16 sublane tiles


def block_s_for(s_max: int) -> int:
    """Slots a grid step: the largest divisor of the window that is whole
    128-slot lanes and at most MAX_BLOCK_S; the whole window where it has
    none (a toy window)."""
    for n in range(min(MAX_BLOCK_S, s_max) // 128, 0, -1):
        if s_max % (128 * n) == 0:
            return 128 * n
    return s_max


def _latent_decode_kernel(
    layer_ref, lo_ref, hi_ref,  # prefetched scalars
    q_ref, rows_ref,  # inputs
    o_ref,  # output
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, block_s: int, ns: int, n_value: int,
):
    del layer_ref  # read by the index map alone
    b, si = pl.program_id(0), pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    lo, hi = lo_ref[b], hi_ref[b]

    @pl.when((si * block_s < hi) & ((si + 1) * block_s > lo))
    def _compute():
        q = q_ref[0]  # [H, c + r]
        rows = rows_ref[...]  # [block_s, c + r]: keys AND values
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, block_s]
        pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (pos >= lo) & (pos < hi)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :n_value],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        # An empty live window leaves l = 0 and acc = 0: exact zeros.
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("n_value", "scale"))
def latent_decode_kernel(
    q: jax.Array,  # [B, n_q, c + r] — absorbed queries over latent rows
    cache: jax.Array,  # [L, B, S_max, c + r] — the STACKED latent cache
    layer: jax.Array,  # scalar int32 — the layer whose rows are read
    valid_from: jax.Array,  # [B] int32
    valid_to: jax.Array,  # [B] int32 or scalar
    n_value: int,
    scale: float,
) -> jax.Array:
    """-> [B, n_q, n_value]: softmax(q . rows) rows[:, :n_value] over each
    row's live window of `cache[layer]`."""
    b, n_q, width = q.shape
    s_max = cache.shape[2]
    block_s = block_s_for(s_max)
    ns = s_max // block_s
    h = -(-n_q // HEAD_TILE) * HEAD_TILE
    qp = jnp.pad(q.astype(cache.dtype), ((0, 0), (0, h - n_q), (0, 0)))
    lo = valid_from.astype(jnp.int32)
    hi = jnp.broadcast_to(valid_to, (b,)).astype(jnp.int32)

    def tile(bi, si, layer_ref, lo_ref, hi_ref):
        # Tiles outside the live window take the index of the nearest live
        # one: the same block as the step before or after, not fetched.
        first = lo_ref[bi] // block_s
        last = jnp.maximum(hi_ref[bi] - 1, lo_ref[bi]) // block_s
        return layer_ref[0], bi, jnp.clip(si, first, last), 0

    kern = functools.partial(
        _latent_decode_kernel, scale=scale, block_s=block_s, ns=ns,
        n_value=n_value,
    )
    out = named_call(
        "latent_decode",
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, ns),
            in_specs=[
                pl.BlockSpec((1, h, width), lambda bi, si, *_: (bi, 0, 0)),
                pl.BlockSpec((None, None, block_s, width), tile),
            ],
            out_specs=pl.BlockSpec(
                (1, h, n_value), lambda bi, si, *_: (bi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, n_value), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, n_value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), lo, hi, qp, cache)
    return out[:, :n_q]


def latent_decode_kernel_sharded(
    q, cache, layer, valid_from, valid_to, mesh, *, n_value: int, scale: float
) -> jax.Array:
    """`latent_decode_kernel` on a mesh whose batch axes (data, fsdp)
    spread the rows: Pallas kernels are not GSPMD-partitionable, so
    `shard_map` pins the layout — q, the cache's row axis and the windows
    over the batch axes, nothing else split — and each device runs the
    kernel on its rows.  No collective: a row attends over its own
    window."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import BATCH_AXES

    b = q.shape[0]
    hi = jnp.broadcast_to(valid_to, (b,)).astype(jnp.int32)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(BATCH_AXES, None, None), P(None, BATCH_AXES, None, None), P(),
            P(BATCH_AXES), P(BATCH_AXES),
        ),
        out_specs=P(BATCH_AXES, None, None),
        check_vma=False,  # pallas_call outputs carry no vma metadata
    )
    def inner(ql, rows, li, lo, hi):
        return latent_decode_kernel(
            ql, rows, li, lo, hi, n_value=n_value, scale=scale)

    return inner(q, cache, jnp.asarray(layer, jnp.int32), valid_from, hi)

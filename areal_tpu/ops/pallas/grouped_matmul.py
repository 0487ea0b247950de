"""Pallas kernel of a decode step's grouped expert matmul: a few rows,
sorted by expert, against the experts' STACKED weights, streamed once.

`jax.lax.ragged_dot` is XLA:TPU's own grouped kernel, and its tiles follow
the divisors of the two weight dimensions: at [2,048, 1,536] it moves the
touched experts' weights at 65% of the HBM bandwidth, at [2,688, 1,856]
(21 x 128 and 14.5 x 128: nemotron_h) at 11% — 1.85 ms a call where the
bytes allow 0.195, 82% of a decode iteration (chip runs, PR 40).  A decode
step's shape is simple enough to do by hand: R = T x k rows (384) sorted by
expert, no expert with more than T of them (a token's choices are
distinct), so ONE window of T + 16 rows from a 16-aligned start covers any
group, and the work is to stream each live expert's [K, N] matrix once.

Grid (N tiles, experts of this layer, K tiles), the last two sequential:
the accumulator [R, tn] stays in VMEM across them.  A step multiplies the
expert's window of rows [W, tk] by its weight tile [tk, tn], zeroes the
rows of the window that belong to neighbours and adds into the window's
rows of the accumulator.  The layer is a prefetched scalar in the weight's
index map, so the stacked [L x E, K, N] leaf is read where it lies (no
layer is sliced out: `_experts_grouped`); an expert without rows repeats
the block index of the step before it, which Pallas does not fetch again.
Rows past every group (a rank's share: choices held elsewhere) come out
zero.  Forward only: the decode programs' kernel; training keeps
`ragged_dot`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

ROW_TILE = 16  # a window starts on a whole bf16 sublane tile
MAX_TILE = 512  # widest K or N tile: [512, 2,688] bf16 is 2.75 MB a buffer


def tile_for(dim: int) -> int:
    """The widest whole-lane divisor of `dim` up to MAX_TILE, or the whole
    dimension where it has none (1,856 = 14.5 x 128: taken whole)."""
    for n in range(min(MAX_TILE, dim) // 128, 0, -1):
        if dim % (128 * n) == 0:
            return 128 * n
    return dim


def ragged_tiles_badly(k: int, n: int) -> bool:
    """Whether XLA's ragged-dot kernel is far off its roofline at a [k, n]
    expert matrix: measured at 58-65% of the bandwidth where both are
    multiples of 512, at 11-33% where one of 1,792 and up is not (chip
    run, PR 40).  A dimension under 1,024 is a toy's or a single tile."""
    return any(d >= 1024 and d % 512 for d in (k, n))


def _kernel(
    first_ref, start_ref, size_ref, fetch_ref,  # prefetched scalars
    x_ref, w_ref,  # inputs
    o_ref,  # output
    acc_scr,  # scratch
    *, window: int, n_experts: int, nk: int,
):
    del first_ref, fetch_ref  # read by the index maps alone
    e, ki = pl.program_id(1), pl.program_id(2)

    @pl.when((e == 0) & (ki == 0))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start, size = start_ref[e], size_ref[e]

    @pl.when(size > 0)
    def _compute():
        rows = acc_scr.shape[0]
        a0 = jnp.minimum(start // ROW_TILE * ROW_TILE, rows - window)
        a0 = pl.multiple_of(a0, ROW_TILE)
        x = x_ref[pl.ds(a0, window), :]  # [W, tk]
        y = jax.lax.dot_general(
            x, w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [W, tn]
        row = a0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        y = jnp.where((row >= start) & (row < start + size), y, 0.0)
        acc_scr[pl.ds(a0, window), :] += y

    @pl.when((e == n_experts - 1) & (ki == nk - 1))
    def _finish():
        o_ref[...] = acc_scr[:].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("max_rows",))
def grouped_decode_matmul(
    xs: jax.Array,  # [R, K] rows sorted by expert; rows past the groups: any
    w: jax.Array,  # [G, K, N] the experts' matrices, STACKED over layers
    group_sizes: jax.Array,  # [E] int32: this layer's rows per expert
    layer: jax.Array,  # scalar int32: the layer's experts are [layer*E, ..)
    max_rows: int,  # no group has more rows (the step's tokens)
) -> jax.Array:
    """-> [R, N]: row r times the matrix of the expert whose group holds
    it (groups in order from row 0), zero for rows past every group."""
    r, k = xs.shape
    n = w.shape[2]
    n_experts = group_sizes.shape[0]
    pad = -r % ROW_TILE
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    rows = r + pad
    window = min(rows, -(-max_rows // ROW_TILE) * ROW_TILE + ROW_TILE)
    tk, tn = tile_for(k), tile_for(n)
    if tk == k and tn == n:  # neither splits: halve what a step holds
        tn = tile_for(n // 2) if n % 256 == 0 else n
    nk, nn = k // tk, n // tn
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    # An expert without rows fetches what the step before it did: the last
    # K tile of the nearest expert before it that has rows.
    idx = jnp.arange(n_experts, dtype=jnp.int32)
    fetch = jax.lax.associative_scan(
        jnp.maximum, jnp.where(sizes > 0, idx, -1))
    first = (jnp.asarray(layer, jnp.int32) * n_experts).reshape(1)

    def weight_tile(ni, e, ki, first_ref, start_ref, size_ref, fetch_ref):
        live = size_ref[e] > 0
        src = jnp.maximum(fetch_ref[e], 0)
        return (first_ref[0] + jnp.where(live, e, src),
                jnp.where(live | (fetch_ref[e] < 0), ki, nk - 1), ni)

    kern = functools.partial(
        _kernel, window=window, n_experts=n_experts, nk=nk)
    out = named_call(
        "grouped_decode_matmul",
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nn, n_experts, nk),
            in_specs=[
                pl.BlockSpec((rows, tk), lambda ni, e, ki, *_: (0, ki)),
                pl.BlockSpec((None, tk, tn), weight_tile),
            ],
            out_specs=pl.BlockSpec((rows, tn), lambda ni, e, ki, *_: (0, ni)),
            scratch_shapes=[pltpu.VMEM((rows, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20,
        ),
        interpret=_interpret(),
    )(first, starts, sizes, fetch, xs, w)
    return out[:r]

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

The tile is chosen by BYTES (`tiles`, PR 47), not by the divisors of a
dimension.  A grid step costs 0.33 us whatever it moves, and a step's
fetch runs under the step before it: a tile whose fetch is shorter than
that leaves the call bound by the count of its steps.  By lane divisors up
to 512, mellum's [2,304, 896] experts (896 = 7 x 128 has no wider divisor
than 128) took 672 steps of 96 KiB, 222 us a call where the bytes take 50;
in [384, 896] pieces (96 steps) and as [896, 2,304] whole (16) — both
contiguous in the row-major matrix — 70-72 us, and every contiguous tile
from 0.7 to 4 MB read within 2% of that (chip runs, PR 47).  Nemotron's
[384, 1856] / [1856, 384] tiles of 1.4 MB already were of that size, and
are what the rule picks there.  K is cut where it was (or not at all where
its pieces were single lanes' 128 rows, which a whole product adds up in
the same order), so no result changed a bit.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

ROW_TILE = 16  # a window starts on a whole bf16 sublane tile
VMEM_LIMIT = 64 << 20  # what the kernel asks Mosaic for (a v5e has 128 MiB)
# A step's weight tile, in bytes (chip runs, PR 47: `scripts/
# grouped_tile_bench.py`).  Under MIN_TILE a step's fixed cost (0.33 us)
# shows beside its fetch: [384, 128] pieces of 96 KiB ran a call at 23% of
# its bytes, [128, 2304] of 576 KiB still 3% behind the best.  Over MAX_TILE
# the first fetch, which nothing runs under, shows: [2688, 1856] whole
# (10 MB) 4% behind [384, 1856].  Between them every contiguous tile
# measured reads the same to 2%, so the smallest is taken — and MIN_TILE
# stands just under [384, 896] (672 KiB), whose K pieces are the 384 rows
# PR 40's lane divisors took: the sums add up in the order they did, and
# every cell's results keep their bits.
MIN_TILE = 640 << 10
MAX_TILE = 8 << 20


def _pieces(dim: int):
    """The whole-lane divisors of `dim`, narrowest first, then `dim`."""
    return [
        128 * m for m in range(1, dim // 128) if dim % (128 * m) == 0
    ] + [dim]


def step_bytes(tk: int, tn: int, rows: int, itemsize: int) -> int:
    """What a grid step holds in VMEM: the weight tile, the rows' K piece
    and the result block twice each (Pallas double-buffers), the sums."""
    return (2 * (tk * tn + rows * tk + rows * tn) * itemsize
            + rows * tn * 4)


def tiles(k: int, n: int, rows: int, itemsize: int):
    """(tk, tn): the tile a grid step takes of an expert's row-major [k, n]
    matrix — a function of the operands' shapes alone.  The smallest
    CONTIGUOUS piece of MIN_TILE to MAX_TILE bytes: n whole, k whole or cut
    in whole lanes ([384, 896] of [2304, 896]; all of [896, 2304];
    [384, 1856] of [2688, 1856]).  Where there is none (k = 1,856 is 14.5
    lanes and the matrix is 10 MB), the smallest piece of all k and whole
    lanes of n — strided runs — of at least MIN_TILE ([1856, 384]).  Else
    the largest piece there is (a toy's whole matrix).  A step's buffers
    take at most half the limit: the rest is Mosaic's own (the window's
    fp32 product)."""
    rows += -rows % ROW_TILE
    pieces = [(tk, n) for tk in _pieces(k)] + [(k, tn) for tn in _pieces(n)]
    pieces = [
        t for t in pieces
        if step_bytes(*t, rows, itemsize) <= VMEM_LIMIT // 2
    ]

    def size(tile):
        return tile[0] * tile[1] * itemsize

    for tile in pieces:  # contiguous ones first, each kind smallest first
        if size(tile) >= MIN_TILE and (tile[1] < n or size(tile) <= MAX_TILE):
            return tile
    return max(pieces, key=size, default=(k, n))


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


def _call(xs, w, group_sizes, layer, max_rows: int, tk: int, tn: int):
    """`grouped_decode_matmul` walking every expert in [tk, tn] tiles (the
    tile bench and the tests force one; `tiles` picks the program's)."""
    r, k = xs.shape
    n = w.shape[2]
    n_experts = group_sizes.shape[0]
    pad = -r % ROW_TILE
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    rows = r + pad
    window = min(rows, -(-max_rows // ROW_TILE) * ROW_TILE + ROW_TILE)
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
    call = named_call(
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
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=_interpret(),
    )
    with jax.named_scope(f"w{tk}x{tn}"):
        out = call(first, starts, sizes, fetch, xs, w)
    return out[:r]


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
    tk, tn = tiles(*w.shape[1:], xs.shape[0], w.dtype.itemsize)
    return _call(xs, w, group_sizes, layer, max_rows, tk, tn)

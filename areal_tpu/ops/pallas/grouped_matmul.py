"""Pallas kernels of the grouped expert matmul, in `jax.lax.ragged_dot`'s
place where XLA's kernel tiles the experts' widths badly
(`ragged_tiles_badly`): `grouped_decode_matmul`, a decode step's — a few
rows, sorted by expert, against the experts' STACKED weights, streamed once
— and `grouped_matmul`, the packed rows' (the gradient program), with a
gradient rule of its own: forward, dx and dw.

The decode step's kernel
------------------------

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
zero.  Forward only: the decode programs' kernel.

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

import dataclasses
import functools
from typing import Optional, Tuple

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


# ---------------------------------------------------------------------------
# The packed rows' kernel (the gradient program): thousands of rows a group,
# and a gradient rule — forward, dx and dw.
#
# `ragged_dot`'s tiles at these widths are a thirtieth of an MXU-sized step
# (a [2688, 1856] expert ran at 6.5-9.7% of the bf16 peak over live rows, a
# [2304, 896] one at 13-22%: my chip runs, PR 50), and its Mosaic kernels lose
# the program's scope on the way.  Here all three walk (row tile, group)
# pairs from prefetched scalar tables (`_visits`; the structure of
# `jax.experimental.pallas.ops.tpu.megablox`): a row tile is visited once by
# every group with a row in it, the rows of a neighbour masked out at the
# store (forward, dx) or out of one operand (dw).  The expert's matrix is
# WHOLE in VMEM wherever it fits, so consecutive visits of a group fetch
# nothing but their rows, and a visit without rows fetches nothing at all.
#   forward  out[M, N] = lhs[M, K] . w[g]      grid (N tiles, visits, K tiles)
#   dx       out[M, K] = dy[M, N] . w[g]^T     the same kernel, the weight
#            tile read as it lies and contracted over its second dimension:
#            no [E, N, K] copy of the weights in HBM
#   dw       out[g] = lhs[g]^T . dy[g]         grid (K tiles, N tiles, visits),
#            fp32 sums in VMEM over the group's row tiles, written once in
#            the weights' type; a group without rows writes zeros
# Row tiles past the last held row (a slab holds twice a balanced share) are
# not multiplied: the forward and dx write them zeros without a fetch, dw
# never sees them.
# ---------------------------------------------------------------------------

# Rows a grid step multiplies.  Chip runs, PR 49 (`scripts/
# grouped_tile_bench.py --train`, the three share cells' slabs): with the
# expert's matrix WHOLE in VMEM a group's weights are fetched once however
# many row tiles walk them, so the row tile is free to follow the waste — a
# row tile that straddles two groups is multiplied once for each — and 256
# rows read best or within 2% of the best in 17 of 18 (shape, kernel) pairs:
# against 512 rows 22-27% faster where a group holds 384 rows (nemotron),
# 3-8% where it holds 1,024; 128 rows within +-7% of 256 (one pair, lfm2's
# dx, 10% faster).  Any cut of the matrix reads slower than the whole (K in
# two: +10-27% forward; dw of [2688, 1856] in three K pieces +11%, of
# [1856, 2688] in three N pieces +2%), so it is cut only where a step's
# buffers pass half of what the kernel asks Mosaic for — which dw's at
# nemotron's widths do (45 MB whole).  Asking for more to keep them whole
# costs HBM: compiled for a described v5e with a 96 MiB limit, an expert
# layer's forward and backward took 73 MB more temporaries than with 64.
ROW_BLOCK = 256


def _cuts(dim: int):
    """`dim` whole, then its whole-lane divisors, widest first."""
    return _pieces(dim)[::-1]


def _fit(k: int, n: int, step):
    """The largest [tk, tn] piece of a [k, n] matrix — one dimension whole,
    the other whole or cut in whole lanes (a dimension that is not whole
    lanes has no cut) — whose step fits half of what the kernel asks Mosaic
    for; K is cut before N (its pieces are contiguous)."""
    options = [(tk, n) for tk in _cuts(k)] + [(k, tn) for tn in _cuts(n)[1:]]
    fits = [t for t in options if step(*t) <= VMEM_LIMIT // 2]
    return max(fits, key=lambda t: t[0] * t[1], default=options[-1])


def _vmem_ask(step: int) -> int:
    """What a packed rows' kernel asks Mosaic for: its step's buffers and a
    quarter more (the fp32 product, the masks), not the whole VMEM_LIMIT —
    XLA plans the program around what a custom call asks for, and a
    deviceless v5e compile of mellum's prefill wave took 131 MiB more HBM
    temporaries with 64 MiB asked for than with 32 (PR 49)."""
    return min(VMEM_LIMIT, step + step // 4 + (2 << 20))


def _dw_step_bytes(tk: int, tn: int, rows: int, itemsize: int) -> int:
    """What a dw step holds in VMEM: the fp32 sums, the result block and
    the two row blocks twice each."""
    return tk * tn * (4 + 2 * itemsize) + 2 * rows * (tk + tn) * itemsize


def _row_block(rows: int) -> int:
    """ROW_BLOCK rows, or all of them, padded to a sublane tile, where
    there are fewer."""
    return min(ROW_BLOCK, rows + -rows % ROW_TILE)


def matmul_tiles(rows: int, k: int, n: int, itemsize: int, tm=None):
    """(tm, tk, tn) of `grouped_matmul`'s forward and dx steps over an
    expert's [k, n] matrix, from the shapes alone: a row block against the
    largest piece of the matrix that fits — the whole of it at every width
    the benchmark runs ([2304, 896], [2688, 1856], [2048, 1792])."""
    tm = tm or _row_block(rows)
    return (tm, *_fit(k, n, lambda tk, tn: step_bytes(tk, tn, tm, itemsize)))


def dw_tiles(rows: int, k: int, n: int, itemsize: int, tm=None):
    """(tm, tk, tn) of the dw kernel: the piece of an expert's [k, n]
    gradient a step adds ROW_BLOCK rows into — its fp32 sums, the result
    block and the two row blocks twice each: the whole of [2304, 896] and
    [2048, 1792], [896, 1856] of [2688, 1856]."""
    tm = tm or _row_block(rows)
    return (tm, *_fit(
        k, n, lambda tk, tn: _dw_step_bytes(tk, tn, tm, itemsize)))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("rows", "groups"), meta_fields=("tm",))
@dataclasses.dataclass(frozen=True)
class Visits:
    """The tables of one slab's calls (`visits`): `rows` the forward's and
    dx's, `groups` dw's — five int32 vectors each (`_visits`) — and the
    row tile they were made for, which travels with them as a STATIC part
    (a `jit` or a gradient rule that takes the tables is keyed on it)."""

    rows: Tuple[jax.Array, ...]
    groups: Tuple[jax.Array, ...]
    tm: int


def visits(group_sizes: jax.Array, m: int) -> Visits:
    """The visit tables of every `grouped_matmul` over `m` rows in groups
    of `group_sizes`, forward and backward: they depend on the sizes, the
    row count and the row tile alone, so a slab's nine to twelve kernel
    calls (two or three matrices x forward, the remat's forward, dx, dw)
    read two tables made once — a cumsum and three dozen selects otherwise
    traced, lowered and run at every one of them."""
    return _visit_tables(group_sizes, m, _row_block(m))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _visit_tables(group_sizes, m: int, tm: int) -> Visits:
    sizes = group_sizes.astype(jnp.int32)
    rows = m + -m % tm
    return Visits(_visits(sizes, rows, tm, False),
                  _visits(sizes, rows, tm, True), tm)


def _visits(sizes, rows: int, tm: int, every_group: bool):
    """The (row tile, group) pairs a call walks, as prefetched tables of
    `rows // tm + E - 1` entries (the megablox structure: a row tile is
    visited once by every group with a row in it) -> (group, src, dst, lo,
    hi): the visit's group; the row tile it reads; the row tile it writes;
    the rows [lo, hi) of the whole slab that are the group's in that tile.
    Then, for the forward and dx (`every_group` False), one visit a row
    tile past the last held row: lo = hi, `src` and `group` those of the
    visit before it (nothing is fetched), `dst` the tile, which gets
    zeros.  For dw (`every_group`) a group without rows has one visit with
    lo = hi instead, and writes zeros.  Entries past the last visit repeat
    it with lo = hi: no fetch, no work, no write."""
    e = sizes.shape[0]
    tiles_m = rows // tm
    n = tiles_m + e - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    count = jnp.where(
        sizes > 0, (ends - 1) // tm - first_tile + 1, int(every_group))
    upto = jnp.cumsum(count)
    grouped = upto[-1]  # visits with a group
    live_tiles = -(-ends[-1] // tm)
    total = grouped if every_group else grouped + tiles_m - live_tiles
    i = jnp.arange(n, dtype=jnp.int32)
    j = jnp.clip(i, 0, jnp.maximum(grouped, 1) - 1)  # its group visit
    # (the groups whose visits end at or before j: `searchsorted` from the
    # right, as one comparison — its loop costs a trace more than a run)
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= j[:, None], axis=1, dtype=jnp.int32), e - 1)
    src = jnp.minimum(first_tile[group] + j - (upto - count)[group],
                      tiles_m - 1)
    real = i < grouped
    lo = jnp.where(real, jnp.maximum(starts[group], src * tm), 0)
    hi = jnp.where(real, jnp.minimum(ends[group], (src + 1) * tm), 0)
    hi = jnp.maximum(hi, lo)
    dead = jnp.minimum(live_tiles + i - grouped, tiles_m - 1)  # or the last
    dst = jnp.where(real | every_group, src, dead)
    dst = jnp.where(i < total, dst, dst[jnp.maximum(total, 1) - 1])
    return tuple(a.astype(jnp.int32) for a in (group, src, dst, lo, hi))


def _rows_mask(tile, lo, hi, shape):
    row = tile * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= lo) & (row < hi)


# Rows of an expert's matrix one trip of a kernel's inner loop takes.  A
# product against the whole [K, N] matrix, written as one `dot_general`,
# unrolls in Mosaic into hundreds of MXU passes, and the CODE is what costs:
# 0.48 MiB a kernel, 96 kernels a gradient program, eight gradient programs
# loaded in mellum's cell — `peak_hbm_gb` + 5.5% there, + 8.9% in lfm2's
# (chip runs, PR 49; `generated_code_size_in_bytes` of a deviceless v5e
# compile 222 MiB against `ragged_dot`'s 176).  So the kernels walk the
# matrix's rows in a `fori_loop`, a chunk a trip.
CHUNK = 128
ROW_CHUNK = 64  # rows of a row tile one trip of an elementwise pass takes


def _over_chunks(dim: int, body, size: int = CHUNK):
    """`body(start, rows)` over `dim` rows: a loop of whole chunks of
    `size`, then what is left over (1,856 = 14 x 128 + 64) at a static
    offset."""
    trips, rest = divmod(dim, size)

    def trip(c, _):
        body(pl.multiple_of(c * size, size), size)
        return 0

    if trips:
        jax.lax.fori_loop(0, trips, trip, 0)
    if rest:
        body(trips * size, rest)


def _matmul_kernel(
    group_ref, src_ref, dst_ref, lo_ref, hi_ref,  # prefetched scalars
    x_ref, w_ref,  # inputs
    o_ref,  # output
    acc_scr,  # scratch: the sums (forward), unused by dx
    *, nk: int, transposed: bool,
):
    del group_ref, src_ref  # read by the index maps alone
    i, ki = pl.program_id(1), pl.program_id(2)
    lo, hi = lo_ref[i], hi_ref[i]
    live = hi > lo
    # The first visit of a row tile writes all of it (zeros outside the
    # group's rows); a later one — a group boundary inside the tile — only
    # its own rows.
    first = (i == 0) | (dst_ref[i] != dst_ref[jnp.maximum(i - 1, 0)])

    def store(y, cols, r0=0):
        """Columns `cols` of the row tile from row `r0`: the group's rows
        of `y`, zeros (the tile's first visit) or what is there in the
        others."""
        rows = pl.ds(r0, y.shape[0])
        row = dst_ref[i] * o_ref.shape[0] + r0 + jax.lax.broadcasted_iota(
            jnp.int32, y.shape, 0)
        mine = (row >= lo) & (row < hi)
        y = y.astype(o_ref.dtype)

        @pl.when(first)
        def _all():
            o_ref[rows, cols] = jnp.where(mine, y, jnp.zeros_like(y))

        @pl.when(jnp.logical_not(first))
        def _mine():
            o_ref[rows, cols] = jnp.where(mine, y, o_ref[rows, cols])

    if transposed:
        # out[:, rows of w] = x . w[rows]^T, a chunk of w's rows a trip;
        # the sums over contraction tiles (nk > 1) ride in `acc_scr`.
        def chunk(start, size):
            cols = pl.ds(start, size)
            y = jax.lax.dot_general(
                x_ref[...], w_ref[cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if nk == 1:
                store(y, cols)
                return

            @pl.when(ki == 0)
            def _start():
                acc_scr[:, cols] = y

            @pl.when(ki > 0)
            def _add():
                acc_scr[:, cols] += y

            @pl.when(ki == nk - 1)
            def _finish():
                store(acc_scr[:, cols], cols)

        @pl.when(live)
        def _rows():
            _over_chunks(w_ref.shape[0], chunk)
    else:
        # acc += x[:, rows of w] . w[rows], a chunk of w's rows a trip.
        def chunk(start, size):
            rows = pl.ds(start, size)
            acc_scr[...] += jax.lax.dot_general(
                x_ref[:, rows], w_ref[rows, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(live)
        def _rows():
            def zero(r0, size):
                acc_scr[pl.ds(r0, size), :] = jnp.zeros(
                    (size, acc_scr.shape[1]), acc_scr.dtype)

            def out(r0, size):
                store(acc_scr[pl.ds(r0, size), :], slice(None), r0)

            @pl.when(ki == 0)
            def _start():
                _over_chunks(acc_scr.shape[0], zero, ROW_CHUNK)

            _over_chunks(w_ref.shape[0], chunk)

            @pl.when(ki == nk - 1)
            def _finish():
                _over_chunks(acc_scr.shape[0], out, ROW_CHUNK)

    @pl.when(jnp.logical_not(live) & first & (ki == nk - 1))
    def _past_the_groups():
        def zero(r0, size):
            o_ref[pl.ds(r0, size), :] = jnp.zeros(
                (size, o_ref.shape[1]), o_ref.dtype)

        _over_chunks(o_ref.shape[0], zero, ROW_CHUNK)


def _pad_rows(a, tm: int):
    pad = -a.shape[0] % tm
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


def _matmul_call(lhs, w, group_sizes, transposed: bool, tiles=None,
                 tables=None, interpret=None):
    """lhs [M, K] x w [E, K, N] -> [M, N], or (`transposed`) lhs [M, N] x
    w [E, K, N] -> [M, K] with every weight tile read as it lies and
    contracted over its second dimension.  `tiles`: (tm, tk, tn) forced
    (the tile bench, the tests); `matmul_tiles` picks the program's.
    `tables`: the slab's `visits(...)`, made here where a caller has none.
    `interpret`: whether the kernel is interpreted — the backend's answer
    (`_interpret`), asked OUTSIDE every `jit` of this file and handed down
    as a static argument, so that a cached trace is never another
    backend's."""
    m = lhs.shape[0]
    if tables is None:
        tables = _visit_tables(
            group_sizes, m, tiles[0] if tiles else _row_block(m))
    tiles = tiles or matmul_tiles(
        m, *w.shape[1:], w.dtype.itemsize, tables.tm)
    return _matmul(tables.rows, lhs, w, transposed=transposed, tiles=tiles,
                   interpret=_interpret() if interpret is None else interpret)


# A `jit` entry point: every call of one (shapes, tiles) in a program is ONE
# traced jaxpr and one private function of the lowered module, its kernel
# body traced and lowered to a Mosaic module once.  Called bare from an
# unrolled layer, a `pallas_call` is traced and lowered again at every site
# — 45 ms each on a CPU host, 96 sites a gradient program, + 93% of a warm
# set-up (the driver's runs of PR 49; PERF.md section 6, PR 50).
@functools.partial(
    jax.jit, static_argnames=("transposed", "tiles", "interpret"))
def _matmul(tables, lhs, w, *, transposed: bool, tiles, interpret: bool):
    m = lhs.shape[0]
    k, n = w.shape[1:]
    tm, tk, tn = tiles
    lhs = _pad_rows(lhs, tm)
    rows = lhs.shape[0]
    # (tc, to): the tile's contracted and result widths; grid (result
    # tiles, visits, contraction tiles)
    tc, to = (tn, tk) if transposed else (tk, tn)
    nc, no = lhs.shape[1] // tc, (k if transposed else n) // to

    def weight_tile(oi, i, ci, group_ref, *_):
        return (group_ref[i], oi, ci) if transposed else (group_ref[i], ci, oi)

    name = "grouped_matmul_dx" if transposed else "grouped_matmul"
    call = named_call(
        name,
        functools.partial(_matmul_kernel, nk=nc, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(no, tables[0].shape[0], nc),
            in_specs=[
                pl.BlockSpec(
                    (tm, tc), lambda oi, i, ci, g, src, *_: (src[i], ci)),
                pl.BlockSpec((None, tk, tn), weight_tile),
            ],
            out_specs=pl.BlockSpec(
                (tm, to), lambda oi, i, ci, g, src, dst, *_: (dst[i], oi)),
            scratch_shapes=[pltpu.VMEM((tm, to), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, no * to), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_ask(
                step_bytes(tk, tn, tm, w.dtype.itemsize)),
        ),
        interpret=interpret,
    )
    with jax.named_scope(f"w{tm}x{tk}x{tn}"):
        return call(*tables, lhs, w)[:m]


def _dw_kernel(
    group_ref, src_ref, dst_ref, lo_ref, hi_ref,  # prefetched scalars
    x_ref, dy_ref,  # inputs
    o_ref,  # output
    acc_scr,  # scratch
    *, n_visits: int,
):
    del dst_ref
    i = pl.program_id(2)
    g, lo, hi = group_ref[i], lo_ref[i], hi_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
    def _init():
        def zero(r0, size):
            acc_scr[pl.ds(r0, size), :] = jnp.zeros(
                (size, acc_scr.shape[1]), acc_scr.dtype)

        _over_chunks(acc_scr.shape[0], zero)

    @pl.when(hi > lo)
    def _add():
        # Rows of the tile that are a neighbour's are zeroed in dy, which
        # takes them out of every product; then a chunk of lhs's columns —
        # of the gradient's rows — a trip.
        dy = dy_ref[...]
        dy = jnp.where(
            _rows_mask(src_ref[i], lo, hi, dy.shape), dy, jnp.zeros_like(dy))

        def chunk(start, size):
            rows = pl.ds(start, size)
            acc_scr[rows, :] += jax.lax.dot_general(
                x_ref[:, rows], dy, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _over_chunks(x_ref.shape[1], chunk)

    @pl.when((i == n_visits - 1)
             | (group_ref[jnp.minimum(i + 1, n_visits - 1)] != g))
    def _finish():
        def out(r0, size):
            rows = pl.ds(r0, size)
            o_ref[rows, :] = acc_scr[rows, :].astype(o_ref.dtype)

        _over_chunks(acc_scr.shape[0], out)


def _dw_call(lhs, dy, group_sizes, dtype, tiles=None, tables=None,
             interpret=None):
    """lhs [M, K], dy [M, N] -> [E, K, N] in `dtype`: group e's rows of
    `lhs`, transposed, times its rows of `dy`; zeros for a group without
    rows.  `tiles`: (tm, tk, tn) forced; `dw_tiles` picks the program's.
    `tables`: the slab's `visits(...)`."""
    m, k = lhs.shape
    dtype = jnp.dtype(dtype)
    if tables is None:
        tables = _visit_tables(
            group_sizes, m, tiles[0] if tiles else _row_block(m))
    tiles = tiles or dw_tiles(m, k, dy.shape[1], dtype.itemsize, tables.tm)
    return _dw(tables.groups, lhs, dy, dtype=dtype, tiles=tiles,
               interpret=_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "tiles", "interpret"))
def _dw(tables, lhs, dy, *, dtype, tiles, interpret: bool):
    k, n = lhs.shape[1], dy.shape[1]
    tm, tk, tn = tiles
    lhs, dy = _pad_rows(lhs, tm), _pad_rows(dy, tm)
    n_visits = tables[0].shape[0]
    e = n_visits + 1 - lhs.shape[0] // tm
    call = named_call(
        "grouped_matmul_dw",
        functools.partial(_dw_kernel, n_visits=n_visits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, n_visits),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda ki, ni, i, g, src, *_: (src[i], ki)),
                pl.BlockSpec(
                    (tm, tn), lambda ki, ni, i, g, src, *_: (src[i], ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ki, ni, i, g, *_: (g[i], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_ask(
                _dw_step_bytes(tk, tn, tm, dtype.itemsize)),
        ),
        interpret=interpret,
    )
    with jax.named_scope(f"w{tm}x{tk}x{tn}"):
        return call(*tables, lhs, dy)


def grouped_matmul(
    lhs: jax.Array,  # [M, K] rows sorted by group; rows past the groups: any
    w: jax.Array,  # [E, K, N] one matrix a group
    group_sizes: jax.Array,  # [E] int32: rows per group, in order from row 0
    tables: Optional[Visits] = None,  # `visits(group_sizes, M)`, made once
    interpret: Optional[bool] = None,  # as `_matmul_call`'s
) -> jax.Array:
    """-> [M, N]: row r times the matrix of the group that holds it, ZERO
    for rows past every group — `jax.lax.ragged_dot(lhs, w, group_sizes)`
    with a gradient rule of its own (dx and dw are kernels of this file),
    at the precision `ragged_dot` runs at: the operands' type into the MXU,
    fp32 sums, the result in the operands' type.  A caller with several
    calls over the same groups (an expert's two or three matrices) makes
    the `tables` once and hands them to each."""
    if tables is None:
        tables = visits(group_sizes, lhs.shape[0])
    if interpret is None:
        interpret = _interpret()
    return _grouped_matmul(interpret, lhs, w, tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _with_rule(interpret, lhs, w, tables):
    return _matmul_call(
        lhs, w, None, False, tables=tables, interpret=interpret)


def _grouped_matmul_fwd(interpret, lhs, w, tables):
    # What autodiff of `ragged_dot` keeps: the operands.
    return _with_rule.fun(interpret, lhs, w, tables), (lhs, w, tables)


def _grouped_matmul_bwd(interpret, res, dy):
    lhs, w, tables = res
    dy = dy.astype(lhs.dtype)
    return (
        _matmul_call(
            dy, w, None, True, tables=tables, interpret=interpret),
        _dw_call(
            lhs, dy, None, w.dtype, tables=tables,
            interpret=interpret),
        None,
    )


_with_rule.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
# The rule under a `jit` of its own: a call site binds one cached jaxpr,
# and its linearisation and transpose are found in JAX's caches by it.
_grouped_matmul = jax.jit(_with_rule, static_argnums=0)

"""Pallas kernels of Mamba-2's chunked (SSD) recurrence over packed rows —
the training form of `models/mamba.py`: a sweep over a row's chunks that
keeps a head's `[C, C]` block and the carried state in VMEM, with a reverse
sweep as its own backward.  The second instance of `delta_chunk.py`'s
design (a decay, an outer-product update, a read over a carried fp32
state), without the delta rule's solve.

Per head h of P channels and chunk of C tokens, with S [P, N] the state the
chunk starts from, G the running sum of dt A_h inside the chunk (computed
outside, a product with a triangle of ones), B and C the chunk's [C, N]
operands of the head's GROUP and L_ij = exp(G_i - G_j) [i >= j, same
segment]:

    y   = ((C B^T) * L) (dt x) + e_in (C S^T) + D_h x
                                                 e_in_i = exp(G_i) [carried]
    S  <- keep S + (e_out dt x)^T B              e_out_i = exp(G_last - G_i)
                                                 [segment of the last token]

which is `mamba.ssd_chunked` with the mixer's D skip, token for token.
`[carried]`: the token's segment is the one the previous chunk ended in;
`keep` = exp(G_last) where the chunk's last token is carried, else 0.  Pads
are neutral (dt = 0) and count to the segment of the token before them
(`mamba._fill_pads`, the caller's).

The backward walks the chunks in reverse carrying dS, rebuilds a chunk's
blocks from its operands and reads the state the chunk STARTED from out of
the forward's residuals ([NC, N, H P] fp32 a row: what the `jnp` form's
scan keeps as `s_in`).  Inside a chunk the gradient of G_i is token i's
ROW of dM * M less its COLUMN, both sums over ONE array, as autodiff of the
`jnp` form has them: what a rounding adds to a row it takes from a column,
so the running sums of dG that make dA and d dt cancel it.  (The shorter
dG_i = sum_p dy_ip y_ip - dt_i sum_p dxd_ip x_ip, which needs no [C, C]
reduction, is the same number in exact arithmetic and was 7 to 43% off in
dA on bf16 operands where this form and the `jnp` form are 1% off:
PERF.md section 6, PR 58.)  dB and dC are summed over a group's heads
inside the kernel, dD over a chunk's tokens; the sum over G for A, dt's
part through G and dD's sum over chunks are `jnp` outside.

Precision, the parent's: S, G, every `exp`, dt and every sum fp32; every
product's operands rounded to bf16 with fp32 sums, as XLA lowers the `jnp`
form's fp32 products on a TPU (`operands=jnp.float32`: the tests' exact
form); x, y and every gradient go in and out in fp32.  Nothing is stored,
carried or summed in fewer bits than in `ssd_chunked`.

Layout: x, B and C are read WHERE THE CONV LEFT THEM, as three windows of
its output [B, S, H P | G N | G N] (a head is a run of P lanes, 128 / P
heads a 128-lane TILE: no slice, no transpose), and their gradient leaves
as ONE array of that shape; y [B, S, H P].  dt and G come a chunk and a
TRIP of heads at a time with the tokens on sublanes ([C, 8]), G also with
the tokens on lanes ([8, C]: a `[C, C]` block needs both).  The state is
held TRANSPOSED, [N, H P] fp32 in VMEM scratch across the sequential chunk
axis of the grid (row, chunk): the two products that touch it — the read
C S^T and the update B^T (e_out dt x) — then run ONCE A GROUP over all its
heads' lanes, the MXU's output full.  The one product a head has to
itself, block @ (dt x), runs against the head's whole TILE (the
neighbour's lanes are computed and dropped: a [C, C] @ [C, 64] product
fills half the MXU's columns whichever way it is laid, PERF.md section 6,
PR 58).  A grid step holds ALL heads of a chunk and is a loop over TRIPS of
eight, written STAGE BY STAGE over the trip's heads, never head by head
(Mosaic does not interleave unrolled heads' dependent chains, PERF.md
section 6, PR 52).
"""

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.delta_chunk import _NT, _TN, _dot, _iota, _masks
from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES = 128
TRIP_H = 8  # heads unrolled side by side in a trip of a grid step's loop
_VMEM_LIMIT = 64 * 1024 * 1024  # a step's blocks, twice, and the state
MAX_LANES = 8192  # of x a grid step: all heads' (the backward holds seven
# [C, H P] fp32 blocks twice and two states: 36 MB at 8,192 lanes)


def fits(h: int, g: int, p: int, n: int, chunk: int) -> bool:
    """Whether the kernel can cut these widths: heads in whole 128-lane
    tiles and whole trips, a group's heads whole trips or a trip whole
    groups of whole tiles, the state's columns and the chunk whole lanes."""
    if g <= 0 or h % g or h % TRIP_H or p <= 0:
        return False
    r = h // g
    return (
        LANES % p == 0 and TRIP_H % (LANES // p) == 0
        and n % LANES == 0 and chunk % LANES == 0
        and (r % TRIP_H == 0 or (TRIP_H % r == 0 and r * p % LANES == 0))
        and (h * p) % (g * n) == 0  # B and C are blocks behind x
        and h * p <= MAX_LANES
    )


def _tiles(z):
    return [z[:, k: k + LANES] for k in range(0, z.shape[1], LANES)]


def _cat(xs):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)


def _own_lanes(tiles, p: int):
    """Per-head [M, 128] arrays, each meant for its head's p lanes of the
    head's tile -> [M, T p]: every head's own lanes of its array."""
    hp = LANES // p
    lane = _iota(tiles[0].shape, 1)
    out = []
    for k in range(0, len(tiles), hp):
        tile = tiles[k + hp - 1]
        for q in range(hp - 2, -1, -1):
            tile = jnp.where(lane < (q + 1) * p, tiles[k + q], tile)
        out.append(tile)
    return _cat(out)


def _expand(col, p: int):
    """[M, T], a head a lane -> [M, T p], a head a run of p lanes."""
    m, t = col.shape
    return _own_lanes(
        [jnp.broadcast_to(col[:, h: h + 1], (m, LANES)) for h in range(t)], p)


def _fold(z, p: int):
    """[M, T p] -> [M, T]: the sum over each head's p lanes, fp32."""
    hp = LANES // p
    lane = _iota((z.shape[0], LANES), 1)
    cols = []
    for tile in _tiles(z):
        for q in range(hp):
            part = tile if hp == 1 else jnp.where(
                (lane >= q * p) & (lane < (q + 1) * p), tile, 0.0)
            cols.append(jnp.sum(part, axis=1, keepdims=True))
    return _cat(cols)


class Trip(NamedTuple):
    """What both sweeps make first of a trip of a grid step's heads."""

    lanes: Any  # the trip's lanes of x | y | the state, a dynamic slice
    x: Any  # [C, T P] fp32
    gac: Any  # [C, T]: G with the tokens on sublanes ...
    gar: Any  # [T, C]: ... and on lanes
    groups: Any  # a group of the trip: (B, C in the products' type, its
    # heads' lanes of the trip's [.., T P] arrays)
    e_in: Any  # [C, T]
    e_out: Any  # [C, T]
    keep: Any  # [1, T]
    dt_x: Any  # [C, T P]: dt over each head's lanes
    xd: Any  # [C, T P]: dt x


def _trip(i, refs, masks, mxu, *, r: int, p: int, n: int) -> Trip:
    x_ref, dtc_ref, gac_ref, gar_ref, b_ref, c_ref = refs
    carried, in_last, keep_ok = masks
    t = TRIP_H
    rt = min(r, t)  # heads of one group inside a trip
    h0 = i * t
    sl = pl.ds(pl.multiple_of(h0 * p, LANES), t * p)
    gsl = pl.ds(pl.multiple_of(h0 // r * n, LANES), t // rt * n)
    b, c = b_ref[:, gsl].astype(mxu), c_ref[:, gsl].astype(mxu)
    x, gac = x_ref[:, sl], gac_ref[i]
    g_last = gac[gac.shape[0] - 1:, :]  # [1, T]
    dt_x = _expand(dtc_ref[i], p)
    return Trip(
        sl, x, gac, gar_ref[i],
        [(b[:, j * n: (j + 1) * n], c[:, j * n: (j + 1) * n],
          slice(j * rt * p, (j + 1) * rt * p)) for j in range(t // rt)],
        e_in=jnp.exp(gac) * carried,
        e_out=jnp.exp(g_last - gac) * in_last,
        keep=jnp.exp(g_last) * keep_ok,
        dt_x=dt_x, xd=x * dt_x)


def _blocks(cbs, gac, gar, keep_blk, rt: int):
    """L of every head of a trip and (C B^T) * L, fp32."""
    ls = [
        jnp.where(
            keep_blk, jnp.exp(gac[:, h: h + 1] - gar[h: h + 1, :]), 0.0)
        for h in range(TRIP_H)
    ]
    return ls, [cbs[h // rt] * ls[h] for h in range(TRIP_H)]


def _fwd_kernel(
    last_ref,  # prefetched: the segment each chunk ends in, flat [B * NC]
    x_ref, b_ref, c_ref,  # three windows of ONE array, the conv's output
    dtc_ref, gac_ref, gar_ref, skip_ref, srow_ref, scol_ref,
    *refs,
    h: int, r: int, p: int, n: int, mxu, save: bool,
):
    if save:
        y_ref, sin_ref, s_scr = refs
    else:
        y_ref, s_scr = refs
    bi, ci, nc = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    @pl.when(ci == 0)
    def _start():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    same, carried, in_last, keep_ok = _masks(
        last_ref, srow_ref, scol_ref, bi * nc + ci, ci == 0)
    c = same.shape[0]
    keep_blk = same & (_iota((c, c), 0) >= _iota((c, c), 1))
    t, hp = TRIP_H, LANES // p
    rt = min(r, t)

    def trip(i, _):
        tr = _trip(
            i, (x_ref, dtc_ref, gac_ref, gar_ref, b_ref, c_ref),
            (carried, in_last, keep_ok), mxu, r=r, p=p, n=n)
        sl = tr.lanes
        st = s_scr[:, sl]  # [N, T P]: the state, transposed
        if save:
            sin_ref[:, sl] = st
        xdb = tr.xd.astype(mxu)
        xwb = (tr.xd * _expand(tr.e_out, p)).astype(mxu)
        stb = st.astype(mxu)
        # A group's products over all its heads' lanes at once.
        cbs, css, news = [], [], []
        for bj, cj, of in tr.groups:
            cbs.append(_dot(cj, bj, _NT))
            css.append(_dot(cj, stb[:, of]))
            news.append(_dot(bj, xwb[:, of], _TN))
        # A head's own: its block against its tile.
        _, ms = _blocks(cbs, tr.gac, tr.gar, keep_blk, rt)
        xts = _tiles(xdb)
        ys = [_dot(ms[k].astype(mxu), xts[k // hp]) for k in range(t)]
        y_ref[:, sl] = (
            _own_lanes(ys, p) + _expand(tr.e_in, p) * _cat(css)
            + skip_ref[:, sl] * tr.x)
        s_scr[:, sl] = st * _expand(tr.keep, p) + _cat(news)
        return 0

    jax.lax.fori_loop(0, h // t, trip, 0)


def _layouts(dt, ga, seg, chunk: int):
    """dt and G a chunk and a trip of heads at a time, tokens on sublanes
    ([B, NC, H / T, C, T]), G also with the tokens on lanes ([B, NC, H / T,
    T, C]); the segment ids both ways and the segment each chunk ends in."""
    b, s, h = dt.shape
    nc, t = s // chunk, TRIP_H

    def col(x):
        return jnp.moveaxis(x.reshape(b, nc, chunk, h // t, t), 3, 2)

    seg = seg.astype(jnp.int32).reshape(b, nc, chunk)
    gac = col(ga)
    return (
        seg[:, :, -1].reshape(b * nc), col(dt), gac,
        jnp.swapaxes(gac, 3, 4), seg[:, :, None, :], seg[..., None],
    )


def _specs(h: int, g: int, p: int, n: int, chunk: int, nc: int, flip: bool):
    """Block specs of the operands both sweeps share: the three windows of
    the conv's output [B, S, H P + 2 G N] (x: its first H P lanes; B and C:
    blocks of G N lanes behind them — H P is whole such blocks, `fits`),
    y | dy, a column layout, the row layout, the skip, segments by row and
    by column, a chunk's state, the whole width of the conv (its
    gradient).  `flip`: the sweep walks the chunks from the last (the
    backward)."""

    def at(ci):
        return nc - 1 - ci if flip else ci

    t, di, gn = TRIP_H, h * p, g * n
    x = pl.BlockSpec((None, chunk, di), lambda bi, ci, _: (bi, at(ci), 0))
    bm = pl.BlockSpec(
        (None, chunk, gn), lambda bi, ci, _: (bi, at(ci), di // gn))
    cm = pl.BlockSpec(
        (None, chunk, gn), lambda bi, ci, _: (bi, at(ci), di // gn + 1))
    col = pl.BlockSpec(
        (None, None, h // t, chunk, t),
        lambda bi, ci, _: (bi, at(ci), 0, 0, 0))
    row = pl.BlockSpec(
        (None, None, h // t, t, chunk),
        lambda bi, ci, _: (bi, at(ci), 0, 0, 0))
    skip = pl.BlockSpec((1, di), lambda bi, ci, _: (0, 0))
    srow = pl.BlockSpec(
        (None, None, 1, chunk), lambda bi, ci, _: (bi, at(ci), 0, 0))
    scol = pl.BlockSpec(
        (None, None, chunk, 1), lambda bi, ci, _: (bi, at(ci), 0, 0))
    state = pl.BlockSpec(
        (None, None, n, di), lambda bi, ci, _: (bi, at(ci), 0, 0))
    conv = pl.BlockSpec(
        (None, chunk, di + 2 * gn), lambda bi, ci, _: (bi, at(ci), 0))
    per_chunk = pl.BlockSpec(
        (None, None, 1, di), lambda bi, ci, _: (bi, at(ci), 0, 0))
    return x, bm, cm, col, row, skip, srow, scol, state, conv, per_chunk


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


@functools.partial(
    jax.jit, static_argnames=("form", "operands", "save", "interpret"))
def _fwd(conv, dt, ga, skip, seg, *, form, operands, save: bool,
         interpret: bool):
    """conv [B, S, H P + 2 G N] = x | B | C, dt, ga [B, S, H], all fp32,
    skip [1, H P] (D over each head's lanes), seg [B, S]; S whole chunks ->
    y [B, S, H P] fp32, the D skip in it, and, `save`, the state each chunk
    started from, transposed [B, NC, N, H P]."""
    g, p, chunk = form
    b, s, h = dt.shape
    di = h * p
    nc, n = s // chunk, (conv.shape[-1] - di) // (2 * g)
    f32 = jnp.float32
    last, dtc, gac, gar, srow, scol = _layouts(dt, ga, seg, chunk)
    xs, bs, cs, col, row, sk, srow_s, scol_s, state, _, _ = _specs(
        h, g, p, n, chunk, nc, False)
    out_shape = [jax.ShapeDtypeStruct((b, s, di), f32)]
    out_specs = [xs]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, n, di), f32))
        out_specs.append(state)
    return named_call(
        "ssd_chunk_fwd",
        functools.partial(
            _fwd_kernel, h=h, r=h // g, p=p, n=n, mxu=operands, save=save),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nc),
            in_specs=[xs, bs, cs, col, col, row, sk, srow_s, scol_s],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, di), f32)],
        ),
        out_shape=out_shape,
        compiler_params=_params(),
        interpret=interpret,
    )(last, conv, conv, conv, dtc, gac, gar, skip, srow, scol)


def _bwd_kernel(
    last_ref,
    x_ref, b_ref, c_ref, dtc_ref, gac_ref, gar_ref, skip_ref, srow_ref,
    scol_ref, dy_ref, sin_ref,
    dconv_ref, ddt_ref, dgc_ref, dgr_ref, dskip_ref,
    ds_scr,
    *, h: int, r: int, p: int, n: int, mxu,
):
    """The chunk's blocks again from its operands and the state it started
    from; dS, the gradient of the state the chunk left, comes in from the
    chunk after it and goes on to the one before.  The gradient of the
    conv's output leaves as ONE block: dx | dB | dC side by side."""
    bi, step, nc = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    ci = nc - 1 - step

    @pl.when(step == 0)
    def _start():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    same, carried, in_last, keep_ok = _masks(
        last_ref, srow_ref, scol_ref, bi * nc + ci, ci == 0)
    c = same.shape[0]
    keep_blk = same & (_iota((c, c), 0) >= _iota((c, c), 1))
    at_last = _iota((c, 1), 0) == c - 1
    t, hp, di, gn = TRIP_H, LANES // p, h * p, h // r * n
    rt = min(r, t)
    lane = _iota((c, LANES), 1)
    # A group's trips add to one run of lanes of dB and of dC.
    dconv_ref[:, di:] = jnp.zeros((c, 2 * gn), dconv_ref.dtype)

    def bf(v):
        return v.astype(mxu)

    def trip(i, _):
        tr = _trip(
            i, (x_ref, dtc_ref, gac_ref, gar_ref, b_ref, c_ref),
            (carried, in_last, keep_ok), mxu, r=r, p=p, n=n)
        sl, x, xd = tr.lanes, tr.x, tr.xd
        dy, st, dsn = dy_ref[:, sl], sin_ref[:, sl], ds_scr[:, sl]
        e_in_x, e_out_x, keep_x = (
            _expand(v, p) for v in (tr.e_in, tr.e_out, tr.keep))
        xw = xd * e_out_x
        xdb, xwb, stb, dyb, dsnb = bf(xd), bf(xw), bf(st), bf(dy), bf(dsn)
        dcsb = bf(dy * e_in_x)
        # A group's products over all its heads' lanes at once:
        # y = ... + e_in (C S^T);  S' = keep S + (e_out dt x)^T B
        cbs, css, dxws, dsts, dbs, dcs = [], [], [], [], [], []
        for bj, cj, of in tr.groups:
            cbs.append(_dot(cj, bj, _NT))
            css.append(_dot(cj, stb[:, of]))
            dxws.append(_dot(bj, dsnb[:, of]))
            dsts.append(_dot(cj, dcsb[:, of], _TN))
            dcs.append(_dot(dcsb[:, of], stb[:, of], _NT))
            dbs.append(_dot(xwb[:, of], dsnb[:, of], _NT))
        # A head's own: y = ((C B^T) * L) (dt x)
        ls, ms = _blocks(cbs, tr.gac, tr.gar, keep_blk, rt)
        msb = [bf(m) for m in ms]
        xts, dyts, dyfs = _tiles(xdb), _tiles(dyb), _tiles(dy)
        own = [dyts[k // hp] if hp == 1 else bf(jnp.where(
            (lane >= k % hp * p) & (lane < (k % hp + 1) * p),
            dyfs[k // hp], 0.0)) for k in range(t)]
        dms = [_dot(own[k], xts[k // hp], _NT) for k in range(t)]
        dxs = [_dot(msb[k], dyts[k // hp], _TN) for k in range(t)]
        for j, (bj, cj, _) in enumerate(tr.groups):
            dcb = bf(sum(dms[k] * ls[k] for k in range(j * rt, (j + 1) * rt)))
            dcs[j] = dcs[j] + _dot(dcb, bj)
            dbs[j] = dbs[j] + _dot(dcb, cj, _TN)
        dxw = _cat(dxws)
        dxd = _own_lanes(dxs, p) + e_out_x * dxw
        # y = ... + D x: the skip's part of dx, and dD a chunk at a time
        dconv_ref[:, sl] = dxd * tr.dt_x + skip_ref[:, sl] * dy
        dskip_ref[:, sl] = jnp.sum(dy * x, axis=0, keepdims=True)
        ddt_ref[i] = _fold(dxd * x, p)
        # G: inside the chunk a token's row of dM * M less its column (ONE
        # array both ways: what a rounding adds to a row it takes from a
        # column, and the sum over a chunk's tokens stays zero to the bit);
        # through the state as a query (e_in), as a key (e_out) and, the
        # last token's, through e_out and keep.
        mds = [dms[k] * ms[k] for k in range(t)]
        d_last = _fold(
            jnp.sum(dxw * xw, axis=0, keepdims=True)
            + jnp.sum(dsn * st, axis=0, keepdims=True) * keep_x, p)  # [1, T]
        dgc_ref[i] = (
            _cat([jnp.sum(m, axis=1, keepdims=True) for m in mds])
            + _fold(dy * (e_in_x * _cat(css)) - dxw * xw, p)
            + jnp.where(at_last, d_last, 0.0))
        dgr_ref[i] = -jnp.concatenate(
            [jnp.sum(m, axis=0, keepdims=True) for m in mds], axis=0)
        ds_scr[:, sl] = dsn * keep_x + _cat(dsts)
        g0 = pl.multiple_of((i * t) // r * n, LANES)
        width = t // rt * n
        dconv_ref[:, pl.ds(pl.multiple_of(di + g0, LANES), width)] += _cat(dbs)
        dconv_ref[:, pl.ds(
            pl.multiple_of(di + gn + g0, LANES), width)] += _cat(dcs)
        return 0

    jax.lax.fori_loop(0, h // t, trip, 0)


@functools.partial(jax.jit, static_argnames=("form", "operands", "interpret"))
def _bwd(conv, dt, ga, skip, seg, dy, states, *, form, operands,
         interpret: bool):
    """-> d conv [B, S, H P + 2 G N], d dt (through dt x alone), dG [B, S,
    H], d skip [1, H P], all fp32."""
    g, p, chunk = form
    b, s, h = dt.shape
    di = h * p
    nc, n = s // chunk, (conv.shape[-1] - di) // (2 * g)
    f32 = jnp.float32
    last, dtc, gac, gar, srow, scol = _layouts(dt, ga, seg, chunk)
    xs, bs, cs, col, row, sk, srow_s, scol_s, state, whole, per_chunk = (
        _specs(h, g, p, n, chunk, nc, True))
    dconv, ddt, dgc, dgr, dskip = named_call(
        "ssd_chunk_bwd",
        functools.partial(
            _bwd_kernel, h=h, r=h // g, p=p, n=n, mxu=operands),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nc),
            in_specs=[xs, bs, cs, col, col, row, sk, srow_s, scol_s, xs,
                      state],
            out_specs=[whole, col, col, row, per_chunk],
            scratch_shapes=[pltpu.VMEM((n, di), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(conv.shape, f32),
            jax.ShapeDtypeStruct(dtc.shape, f32),
            jax.ShapeDtypeStruct(gac.shape, f32),
            jax.ShapeDtypeStruct(gar.shape, f32),
            jax.ShapeDtypeStruct((b, nc, 1, di), f32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(last, conv, conv, conv, dtc, gac, gar, skip, srow, scol, dy, states)

    def tokens(col):  # [B, NC, H / T, C, T] -> [B, S, H]
        return jnp.moveaxis(col, 2, 3).reshape(b, s, h)

    return (dconv, tokens(ddt), tokens(dgc) + tokens(jnp.swapaxes(dgr, 3, 4)),
            jnp.sum(dskip, axis=(0, 1)))


# ---------------------------------------------------------------------------
# The rule: y = f(x | B | C, dt, G, D) with its own gradient
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rule(form, operands, interpret, conv, dt, ga, skip, seg):
    """`form`: (groups, a head's width, the chunk)."""
    return _fwd(conv, dt, ga, skip, seg, form=form, operands=operands,
                save=False, interpret=interpret)[0]


def _rule_fwd(form, operands, interpret, conv, dt, ga, skip, seg):
    y, states = _fwd(conv, dt, ga, skip, seg, form=form, operands=operands,
                     save=True, interpret=interpret)
    return y, (conv, dt, ga, skip, seg, states)


def _rule_bwd(form, operands, interpret, res, dy):
    *ops, states = res
    grads = _bwd(*ops, dy.astype(jnp.float32), states, form=form,
                 operands=operands, interpret=interpret)
    return (*grads, None)


_rule.defvjp(_rule_fwd, _rule_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("head_dim", "groups", "chunk", "operands", "interpret"))
def _ssd_chunk(conv, dt, a, d, segment_ids, *, head_dim, groups, chunk,
               operands, interpret):
    b, s, h = dt.shape
    pad = -s % chunk
    f32 = jnp.float32
    conv, dt = conv.astype(f32), dt.astype(f32)
    if pad:
        # Neutral tokens (dt 0) of the last token's segment, as
        # `ssd_chunked` pads: the state passes through unchanged.
        conv, dt = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (conv, dt))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge")
    sp = s + pad
    # The running sum of dt A inside a chunk as a product with a triangle
    # of ones on fp32 operands: XLA's `cumsum` over [NC, C, H] took 1.9 ms a
    # call where the sweep's two kernels take 1.8 (PERF.md section 6, PR 58).
    ga = jnp.einsum(
        "ij,bkjh->bkih", jnp.tril(jnp.ones((chunk, chunk), f32)),
        (dt * a.astype(f32)).reshape(b, sp // chunk, chunk, h),
        precision=jax.lax.Precision.HIGHEST)
    y = _rule(
        (groups, head_dim, chunk), operands, interpret,
        conv, dt, ga.reshape(b, sp, h),
        jnp.repeat(d.astype(f32), head_dim)[None], segment_ids)
    return y[:, :s]


def ssd_chunk(
    conv: jax.Array,  # [B, S, H P + 2 G N] fp32: x | B | C, the conv's output
    dt: jax.Array,  # [B, S, H] fp32, after softplus; 0 = a neutral token
    a: jax.Array,  # [H] fp32, negative
    d: jax.Array,  # [H]: the skip
    segment_ids: jax.Array,  # [B, S], pads filled (`mamba._fill_pads`)
    chunk: int,
    head_dim: int,  # P
    groups: int,  # G
    operands=jnp.bfloat16,  # what the products' operands round to
    interpret=None,
) -> jax.Array:
    """The SSD recurrence over packed rows WITH the D skip -> y [B, S, H P]
    fp32: `mamba.ssd_chunked`'s first result + D x, with a gradient rule of
    its own.  x, B and C are read where the conv left them, as three
    windows of its output, and their gradient leaves as one array of its
    shape: a slice of x is a copy of 134 MB a row and layer (0.4 ms), the
    turn to [.., H, 64] another (a TPU tiles an array's last two
    dimensions), and putting dx, dB and dC back together a third.  One
    `jit` entry point: every layer of a program binds one traced function
    and its kernels are lowered once."""
    h = dt.shape[-1]
    n = (conv.shape[-1] - h * head_dim) // (2 * groups)
    assert conv.shape[-1] == h * head_dim + 2 * groups * n, conv.shape
    assert fits(h, groups, head_dim, n, chunk), (h, groups, head_dim, n, chunk)
    if interpret is None:
        interpret = _interpret()
    return _ssd_chunk(
        conv, dt, a, d, segment_ids, head_dim=int(head_dim),
        groups=int(groups), chunk=int(chunk), operands=jnp.dtype(operands),
        interpret=bool(interpret))

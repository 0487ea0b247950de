"""Pallas kernels of the Gated DeltaNet's chunked (WY) delta rule over
packed rows — the training form of `models/linear_attention.py`: a sweep
over a row's chunks of CHUNK tokens that keeps a chunk's `[C, C]` blocks
and the carried state in VMEM, with a reverse sweep as its own backward.

Per value head and chunk, with S [d_k, d_v] the state the chunk starts from,
G the running sum of g inside the chunk, K, Q the chunk's keys and queries
(of the value head's KEY head: q and k come in at their own head count, no
repeat) and D_ij = exp(G_i - G_j) [i >= j, same segment]:

    A   = beta_i (K K^T)_ij D_ij [i > j]
    T   = (I + A)^-1
    r_i = beta_i (v_i - e_in_i (K S)_i)      e_in_i = exp(G_i) [carried]
    vn  = T r                                (what the chunk writes)
    o   = e_in (Q S) + (Q K^T * D) vn
    S  <- keep S + K^T (e_out vn)            e_out_i = exp(G_last - G_i)
                                             [segment of the last token]

which is `gated_delta_chunked` with `u - w S` folded into one solve
(T (beta v) - T (beta k e_in) S = T r).  `[carried]`: the token's segment
is the one the previous chunk ended in; `keep` = exp(G_last) where the
chunk's last token is carried, else 0.

Precision, the parent's: S, G and every sum fp32; every product's operands
bf16 with fp32 sums, as XLA lowers the `jnp` form's fp32 products on a TPU;
the in-chunk inverse and its product with r — the `jnp` form's
`solve_triangular` — at fp32.  T is built by block merges, exact in exact
arithmetic and as stable as substitution: on 2 x 2 diagonal blocks
(I + A)^-1 = I - A, and a block twice the size is [[T1, 0], [-T2 A21 T1,
T2]] = T - T A_off T with T the block-diagonal inverse so far; the five
merges run on three bf16 passes a product (16 bits of each operand) and
one Newton step, T + T (I - (I + A) T) with the residual on fp32 operands
(`Precision.HIGHEST`), squares what they left: against float64 T is within
2e-7 of its largest entry, as far as the ten products on fp32 operands are
(`tests/test_delta_chunk_kernel.py`), for two thirds of their passes.  T r
and, in the backward, T^T dvn and the outer product that is dA are
`HIGHEST` products.

The kernels walk the heads of a group STAGE BY STAGE (every head's
K K^T, then every head's merges level by level, ...), never head by head:
a head's products are one dependent chain, and written head by head the
forward took twice as long (9.2 ms against 4.5, PERF.md section 6, PR 52).

Layout: q, k [B, S, h_k d_k] and v, o [B, S, h_v d_v] as the conv leaves
them (a head is a run of 128 lanes: no transpose anywhere); G and beta a
chunk and a group of heads at a time, once with the tokens on lanes and
once on sublanes (a `[C, C]` block needs both and Mosaic has no cheap turn
of a 64-wide tile).  Grid (row, block of value heads, chunk), the chunk
axis sequential and S [heads, d_k, d_v] fp32 in VMEM scratch across it; a
grid step is a loop over GROUPS of its heads (`group_for`: four side by
side a trip; all 32 heads a step in the cell, eight trips).  The backward
walks the chunks in reverse carrying dS.  It reads the state each chunk
started from and its T from the forward's residuals (`save=True`: 268 +
67 MB a row and layer, alive inside one layer's backward), or rebuilds
them by a forward sweep of its own (`save=False`: 11.8 ms a call against
8.9, PERF.md section 6, PR 52).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES = 128
CHUNK = 64
_VMEM_LIMIT = 64 * 1024 * 1024  # a step's blocks, twice, and the state
GROUP_H = 4  # value heads unrolled side by side in a trip of a step's loop
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _tiles(d: int) -> int:
    """d rounded up to whole 128-lane tiles."""
    return d + -d % LANES


def fits(dk: int, dv: int) -> bool:
    """Whether the sweep takes a head: one of whole 128-lane tiles both ways
    (a head is cut out of its operand as a run of lanes), or one that zero
    columns make so (`gdn_chunk` appends them: 96 x 192 runs as 128 x 256)
    at under twice the state's products — past that the `jnp` form's own
    widths are the cheaper."""
    return _tiles(dk) * _tiles(dv) < 2 * dk * dv


def run_heads(hk: int, hv: int) -> int:
    """Value heads the sweep runs: `hv`, or with as many key as value heads
    the next whole groups of GROUP_H (30 run as 32: zero heads put out
    zeros, and 30 side by side a trip would be one trip of 30 unrolled
    chains)."""
    return hv + -hv % GROUP_H if hk == hv else hv


def group_for(hb: int, rep: int) -> int:
    """Heads a trip of a grid step's loop: GROUP_H where that is whole key
    heads and a divisor of the step's heads, else all of them."""
    return GROUP_H if hb % GROUP_H == 0 and GROUP_H % rep == 0 else hb


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b, dims=None, precision=None):
    dims = dims or (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32)


def _split(x, n: int):
    """x fp32 -> n bf16 pieces that sum to x to 8 n bits."""
    pieces = []
    for _ in range(n):
        p = x.astype(jnp.bfloat16)
        pieces.append(p)
        x = x - p.astype(jnp.float32)
    return pieces


def _dot3(a, b):
    """a @ b to 16 bits of each operand: three bf16 passes."""
    (ah, am), (bh, bm) = _split(a, 2), _split(b, 2)
    return _dot(ah, bh) + (_dot(ah, bm) + _dot(am, bh))


def _inverses(blocks):
    """(I + a)^-1 of every strictly lower triangular [C, C] fp32 block of
    the list, at fp32 (module docstring), level by level over the whole
    list: a block's merges are one dependent chain, the blocks' chains are
    independent, and side by side they hide each other's latency."""
    c = blocks[0].shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    eye = jnp.where(row == col, 1.0, 0.0)
    ts = [eye - jnp.where(row // 2 == col // 2, a, 0.0) for a in blocks]
    m = 2
    while m < c:
        below = (row // (2 * m) == col // (2 * m)) & (row // m != col // m)
        xs = [_dot3(t, jnp.where(below, a, 0.0)) for t, a in zip(ts, blocks)]
        ts = [t - _dot3(x, t) for t, x in zip(ts, xs)]
        m *= 2
    # One Newton step, the residual at fp32: T <- T + T (I - (I + a) T).
    rs = [(eye - t) - _dot(a, t, precision=_HIGHEST)
          for t, a in zip(ts, blocks)]
    return [t + _dot3(t, r) for t, r in zip(ts, rs)]


def _group_blocks(q, k, grow, gcol, bcol, same, g: int, rep: int, dk: int):
    """What both sweeps make first of a group of g value heads in a chunk
    -> per head: its key head's q and k, G and beta as [C, 1] columns, D,
    K K^T and Q K^T as the MXU gives them (ONE product a key head, shared
    by the value heads it serves) and K K^T * D below the diagonal."""
    c = gcol.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    low = row >= col
    qs = [q[:, j * dk:(j + 1) * dk] for j in range(g // rep)]
    ks = [k[:, j * dk:(j + 1) * dk] for j in range(g // rep)]
    grams = [_dot(kb, kb, _NT) for kb in ks]
    qks = [_dot(qb, kb, _NT) for qb, kb in zip(qs, ks)]
    heads = []
    for h in range(g):
        gc = gcol[:, h:h + 1]
        d = jnp.where(
            low & same,
            jnp.exp(jnp.where(low, gc - grow[h:h + 1, :], 0.0)), 0.0)
        gram = grams[h // rep]
        heads.append((
            qs[h // rep], ks[h // rep], gc, bcol[:, h:h + 1], d, gram,
            qks[h // rep], jnp.where(row > col, gram * d, 0.0)))
    return [list(x) for x in zip(*heads)]


def _last(col):
    """A [C, 1] column's last entry, [1, 1]."""
    c = col.shape[0]
    return jnp.sum(
        jnp.where(_iota((c, 1), 0) == c - 1, col, 0.0), axis=0, keepdims=True)


def _masks(last_ref, srow_ref, scol_ref, at, first):
    """A chunk's masks from its segment ids and the prefetched table of the
    segment each chunk ends in (`at`: this chunk's entry; `first`: the
    row's first chunk, which carries nothing in) -> same segment [C, C],
    and as fp32: carried [C, 1], in the last token's segment [C, 1],
    whether the last token is carried (a scalar)."""
    seg_last = last_ref[at]
    prev_last = jnp.where(first, -1, last_ref[jnp.maximum(at - 1, 0)])
    scol, srow = scol_ref[...], srow_ref[...]  # [C, 1], [1, C]
    f32 = jnp.float32
    return (scol == srow, (scol == prev_last).astype(f32),
            (scol == seg_last).astype(f32),
            (seg_last == prev_last).astype(f32))


def _fwd_kernel(
    last_ref,  # prefetched: the segment each chunk ends in, flat [B * N]
    q_ref, k_ref, v_ref, grow_ref, gcol_ref, bcol_ref, srow_ref, scol_ref,
    *refs,
    hb: int, g: int, rep: int, dk: int, dv: int, save: bool,
):
    if save:
        o_ref, s_in_ref, t_ref, s_scr = refs
    else:
        o_ref, s_scr = refs
    bi, ci, n = pl.program_id(0), pl.program_id(2), pl.num_programs(2)

    @pl.when(ci == 0)
    def _start():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    same, carried, in_last, keep_ok = _masks(
        last_ref, srow_ref, scol_ref, bi * n + ci, ci == 0)
    mxu = q_ref.dtype

    def group(i, _):
        # Stage by stage over the group's heads, never head by head: a
        # head's products are one dependent chain, the heads' chains are
        # independent.
        heads = range(g)
        h0 = i * g
        keys = pl.ds(pl.multiple_of(h0 // rep * dk, LANES), g // rep * dk)
        vals = pl.ds(pl.multiple_of(h0 * dv, LANES), g * dv)
        v = v_ref[:, vals]
        qq, kk, gcols, bcols, ds, _, qks, gds = _group_blocks(
            q_ref[:, keys], k_ref[:, keys], grow_ref[i], gcol_ref[i],
            bcol_ref[i], same, g, rep, dk)
        ts = _inverses([bcols[h] * gds[h] for h in heads])
        states = [s_scr[h0 + h] for h in heads]
        sbs = [x.astype(mxu) for x in states]
        e_ins = [jnp.exp(gcols[h]) * carried for h in heads]
        rs = [bcols[h] * (v[:, h * dv:(h + 1) * dv]
                          - e_ins[h] * _dot(kk[h], sbs[h]))
              for h in heads]
        vns = [_dot(ts[h], rs[h], precision=_HIGHEST) for h in heads]
        o_ref[:, vals] = jnp.concatenate([
            e_ins[h] * _dot(qq[h], sbs[h])
            + _dot((qks[h] * ds[h]).astype(mxu), vns[h].astype(mxu))
            for h in heads], axis=1)
        for h in heads:
            g_last = _last(gcols[h])  # [1, 1]
            e_out = jnp.exp(g_last - gcols[h]) * in_last
            if save:
                s_in_ref[h0 + h] = states[h]
                t_ref[h0 + h] = ts[h]
            s_scr[h0 + h] = states[h] * (jnp.exp(g_last) * keep_ok) + _dot(
                kk[h], (e_out * vns[h]).astype(mxu), _TN)
        return 0

    jax.lax.fori_loop(0, hb // g, group, 0)


def _layouts(gc, beta, seg, g: int):
    """g's running sum and beta a chunk and a group of g heads at a time,
    tokens on lanes (`row`: [B, N, hv / g, g, C]) and on sublanes (`col`:
    [B, N, hv / g, C, g], the group's heads on lanes), the segment ids both
    ways and the segment each chunk ends in."""
    b, s, hv = gc.shape
    n = s // CHUNK

    def col(x):
        return jnp.moveaxis(x.reshape(b, n, CHUNK, hv // g, g), 3, 2)

    seg = seg.astype(jnp.int32).reshape(b, n, CHUNK)
    return (
        seg[:, :, -1].reshape(b * n), jnp.swapaxes(col(gc), 3, 4), col(gc),
        col(beta), seg[:, :, None, :], seg[..., None],
    )


def _specs(hb: int, g: int, rep: int, dk: int, dv: int, n: int, flip: bool):
    """Block specs of the operands every sweep shares: q | k, v | o | do,
    g by row, g | beta by column, segments by row and by column.  `flip`:
    the sweep walks the chunks from the last (the backward)."""

    def at(ci):
        return n - 1 - ci if flip else ci

    qk = pl.BlockSpec(
        (None, CHUNK, hb // rep * dk), lambda bi, hi, ci, _: (bi, at(ci), hi))
    v = pl.BlockSpec(
        (None, CHUNK, hb * dv), lambda bi, hi, ci, _: (bi, at(ci), hi))
    row = pl.BlockSpec(
        (None, None, hb // g, g, CHUNK),
        lambda bi, hi, ci, _: (bi, at(ci), hi, 0, 0))
    col = pl.BlockSpec(
        (None, None, hb // g, CHUNK, g),
        lambda bi, hi, ci, _: (bi, at(ci), hi, 0, 0))
    srow = pl.BlockSpec(
        (None, None, 1, CHUNK), lambda bi, hi, ci, _: (bi, at(ci), 0, 0))
    scol = pl.BlockSpec(
        (None, None, CHUNK, 1), lambda bi, hi, ci, _: (bi, at(ci), 0, 0))

    def tile(d1, d2):  # [B, hv, N, d1, d2]: per head and chunk
        return pl.BlockSpec(
            (None, hb, None, d1, d2),
            lambda bi, hi, ci, _: (bi, hi, at(ci), 0, 0))

    return qk, v, row, col, srow, scol, tile


@functools.partial(
    jax.jit, static_argnames=("hb", "g", "hk", "save", "interpret"))
def _fwd(q, k, v, gc, beta, seg, *, hb: int, g: int, hk: int, save: bool,
         interpret: bool):
    """q, k [B, S, hk dk] (the products' operand type), v [B, S, hv dv]
    fp32, gc, beta [B, S, hv] fp32, seg [B, S]; S whole chunks ->
    o [B, S, hv dv] fp32 and, `save`, the state each chunk started from
    [B, hv, N, dk, dv] and its T [B, hv, N, C, C]."""
    b, s, hv = gc.shape
    n, rep = s // CHUNK, hv // hk
    dk, dv = q.shape[-1] // hk, v.shape[-1] // hv
    f32 = jnp.float32
    last, grow, gcol, bcol, srow, scol = _layouts(gc, beta, seg, g)
    qk, vs, row, col, srow_s, scol_s, tile = _specs(
        hb, g, rep, dk, dv, n, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, f32)]
    out_specs = [vs]
    if save:
        out_shape += [
            jax.ShapeDtypeStruct((b, hv, n, dk, dv), f32),
            jax.ShapeDtypeStruct((b, hv, n, CHUNK, CHUNK), f32),
        ]
        out_specs += [tile(dk, dv), tile(CHUNK, CHUNK)]
    return named_call(
        "gdn_chunk_fwd",
        functools.partial(
            _fwd_kernel, hb=hb, g=g, rep=rep, dk=dk, dv=dv, save=save),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hv // hb, n),
            in_specs=[qk, qk, vs, row, col, col, srow_s, scol_s],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(last, q, k, v, grow, gcol, bcol, srow, scol)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _bwd_kernel(
    last_ref,
    q_ref, k_ref, v_ref, grow_ref, gcol_ref, bcol_ref, srow_ref, scol_ref,
    do_ref, s_in_ref, t_ref,
    dq_ref, dk_ref, dv_ref, dgrow_ref, dgcol_ref, dbcol_ref,
    ds_scr,
    *, hb: int, g: int, rep: int, dk: int, dv: int,
):
    """The chunk's blocks again from its inputs, the state it started from
    and its T; dS, the gradient of the state the chunk left, comes in from
    the chunk after it and goes on to the one before."""
    bi, step, n = pl.program_id(0), pl.program_id(2), pl.num_programs(2)
    ci = n - 1 - step

    @pl.when(step == 0)
    def _start():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    same, carried, in_last, keep_ok = _masks(
        last_ref, srow_ref, scol_ref, bi * n + ci, ci == 0)
    c = same.shape[0]
    strict = _iota((c, c), 0) > _iota((c, c), 1)
    at_last = _iota((c, 1), 0) == c - 1
    mxu = q_ref.dtype

    def bf(x):
        return x.astype(mxu)

    def group(i, _):
        # Stage by stage over the group's heads, as the forward.
        heads = range(g)
        h0 = i * g
        keys = pl.ds(pl.multiple_of(h0 // rep * dk, LANES), g // rep * dk)
        lanes = pl.ds(pl.multiple_of(h0 * dv, LANES), g * dv)
        v, do = v_ref[:, lanes], do_ref[:, lanes]
        vals = [slice(h * dv, (h + 1) * dv) for h in heads]
        qq, kk, gcols, bcols, ds, grams, qks, gds = _group_blocks(
            q_ref[:, keys], k_ref[:, keys], grow_ref[i], gcol_ref[i],
            bcol_ref[i], same, g, rep, dk)
        ts = [t_ref[h0 + h] for h in heads]
        ss = [s_in_ref[h0 + h] for h in heads]
        sbs = [bf(s) for s in ss]
        e_ins = [jnp.exp(gcols[h]) * carried for h in heads]
        ps = [_dot(kk[h], sbs[h]) for h in heads]
        us = [v[:, vals[h]] - e_ins[h] * ps[h] for h in heads]
        vns = [_dot(ts[h], bcols[h] * us[h], precision=_HIGHEST)
               for h in heads]
        g_lasts = [_last(gc) for gc in gcols]
        e_outs = [jnp.exp(g_lasts[h] - gcols[h]) * in_last for h in heads]
        keeps = [jnp.exp(gl) * keep_ok for gl in g_lasts]  # [1, 1]
        dsns = [ds_scr[h0 + h] for h in heads]
        dsnbs = [bf(x) for x in dsns]
        dos = [do[:, vals[h]] for h in heads]
        # S' = keep S + K^T (e_out vn)
        dzs = [_dot(kk[h], dsnbs[h]) for h in heads]
        d_ks = [_dot(bf(e_outs[h] * vns[h]), dsnbs[h], _NT) for h in heads]
        d_eouts = [_rowsum(dzs[h] * vns[h]) for h in heads]
        d_keeps = [jnp.sum(dsns[h] * ss[h], keepdims=True) for h in heads]
        # o = e_in (Q S) + (Q K^T * D) vn
        dvns = [e_outs[h] * dzs[h] + _dot(bf(qks[h] * ds[h]), bf(dos[h]), _TN)
                for h in heads]
        dqss = [bf(e_ins[h] * dos[h]) for h in heads]
        d_qs = [_dot(dqss[h], sbs[h], _NT) for h in heads]
        d_ss = [_dot(qq[h], dqss[h], _TN) for h in heads]
        d_eins = [_rowsum(dos[h] * _dot(qq[h], sbs[h])) for h in heads]
        dws = [_dot(bf(dos[h]), bf(vns[h]), _NT) for h in heads]
        dqks = [bf(dws[h] * ds[h]) for h in heads]
        d_qs = [d_qs[h] + _dot(dqks[h], kk[h]) for h in heads]
        d_ks = [d_ks[h] + _dot(dqks[h], qq[h], _TN) for h in heads]
        # vn = T r, T = (I + A)^-1, r = beta u
        drs = [_dot(ts[h], dvns[h], _TN, precision=_HIGHEST) for h in heads]
        das = [jnp.where(
            strict, -_dot(drs[h], vns[h], _NT, precision=_HIGHEST), 0.0)
            for h in heads]
        dus = [bcols[h] * drs[h] for h in heads]
        dps = [bf(-e_ins[h] * dus[h]) for h in heads]
        d_ks = [d_ks[h] + _dot(dps[h], sbs[h], _NT) for h in heads]
        d_ss = [d_ss[h] + _dot(kk[h], dps[h], _TN) for h in heads]
        # A = beta (K K^T) D below the diagonal
        dgrams = [bf(bcols[h] * das[h] * ds[h]) for h in heads]
        d_ks = [d_ks[h] + _dot(dgrams[h], kk[h]) + _dot(dgrams[h], kk[h], _TN)
                for h in heads]
        dbcols, dgcols, dgrows = [], [], []
        for h in heads:
            dbcols.append(_rowsum(drs[h] * us[h]) + _rowsum(das[h] * gds[h]))
            m = (dws[h] * qks[h] + bcols[h] * das[h] * grams[h]) * ds[h]
            d_ein = d_eins[h] - _rowsum(dus[h] * ps[h])
            d_last = (jnp.sum(d_eouts[h] * e_outs[h], keepdims=True)
                      + d_keeps[h] * keeps[h])
            dgcols.append(
                _rowsum(m) + d_ein * e_ins[h] - d_eouts[h] * e_outs[h]
                + jnp.where(at_last, d_last, 0.0))
            dgrows.append(-jnp.sum(m, axis=0, keepdims=True))
            ds_scr[h0 + h] = dsns[h] * keeps[h] + d_ss[h]
        dbcol_ref[i] = jnp.concatenate(dbcols, axis=1)
        dgcol_ref[i] = jnp.concatenate(dgcols, axis=1)
        dgrow_ref[i] = jnp.concatenate(dgrows, axis=0)
        dv_ref[:, lanes] = jnp.concatenate(dus, axis=1)
        per_key = [range(j * rep, (j + 1) * rep) for j in range(g // rep)]
        dq_ref[:, keys] = jnp.concatenate(
            [sum(d_qs[h] for h in of) for of in per_key], axis=1)
        dk_ref[:, keys] = jnp.concatenate(
            [sum(d_ks[h] for h in of) for of in per_key], axis=1)
        return 0

    jax.lax.fori_loop(0, hb // g, group, 0)


@functools.partial(jax.jit, static_argnames=("hb", "g", "hk", "interpret"))
def _bwd(q, k, v, gc, beta, seg, do, states, ts, *, hb: int, g: int, hk: int,
         interpret: bool):
    """-> dq, dk [B, S, hk dk], dv [B, S, hv dv], dgc, dbeta [B, S, hv],
    all fp32."""
    b, s, hv = gc.shape
    n, rep = s // CHUNK, hv // hk
    dk, dv = q.shape[-1] // hk, v.shape[-1] // hv
    f32 = jnp.float32
    last, grow, gcol, bcol, srow, scol = _layouts(gc, beta, seg, g)
    qk, vs, row, col, srow_s, scol_s, tile = _specs(
        hb, g, rep, dk, dv, n, True)
    dq, dkk, dvv, dgrow, dgcol, dbcol = named_call(
        "gdn_chunk_bwd",
        functools.partial(_bwd_kernel, hb=hb, g=g, rep=rep, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hv // hb, n),
            in_specs=[
                qk, qk, vs, row, col, col, srow_s, scol_s, vs,
                tile(dk, dv), tile(CHUNK, CHUNK),
            ],
            out_specs=[qk, qk, vs, row, col, col],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, f32),
            jax.ShapeDtypeStruct(k.shape, f32),
            jax.ShapeDtypeStruct(v.shape, f32),
            jax.ShapeDtypeStruct(grow.shape, f32),
            jax.ShapeDtypeStruct(gcol.shape, f32),
            jax.ShapeDtypeStruct(bcol.shape, f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(last, q, k, v, grow, gcol, bcol, srow, scol, do, states, ts)

    def tokens(col):  # [B, N, hv / g, C, g] -> [B, S, hv]
        return jnp.moveaxis(col, 2, 3).reshape(b, s, hv)

    dgc = tokens(dgcol) + tokens(jnp.swapaxes(dgrow, 3, 4))
    return dq, dkk, dvv, dgc, tokens(dbcol)


# ---------------------------------------------------------------------------
# The rule: o = f(q, k, v, G, beta) with its own gradient
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rule(form, save, interpret, q, k, v, gc, beta, seg):
    """`form`: (heads a grid step, heads a group, key heads)."""
    hb, g, hk = form
    return _fwd(q, k, v, gc, beta, seg, hb=hb, g=g, hk=hk, save=False,
                interpret=interpret)[0]


def _rule_fwd(form, save, interpret, q, k, v, gc, beta, seg):
    hb, g, hk = form
    o, *kept = _fwd(q, k, v, gc, beta, seg, hb=hb, g=g, hk=hk, save=save,
                    interpret=interpret)
    return o, (q, k, v, gc, beta, seg, *kept)


def _rule_bwd(form, save, interpret, res, do):
    hb, g, hk = form
    q, k, v, gc, beta, seg, *kept = res
    if not kept:  # rebuilt by a forward sweep of the backward's own
        kept = _fwd(q, k, v, gc, beta, seg, hb=hb, g=g, hk=hk, save=True,
                    interpret=interpret)[1:]
    dq, dk, dv, dgc, dbeta = _bwd(
        q, k, v, gc, beta, seg, do.astype(jnp.float32), *kept, hb=hb, g=g,
        hk=hk, interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv, dgc, dbeta, None


_rule.defvjp(_rule_fwd, _rule_bwd)


def _to_tiles(q, k, v, g, beta, heads: int):
    """Zero columns appended to q, k and v up to whole 128-lane tiles and
    zero heads (q, k, v, g, beta 0: they write and read nothing) up to
    `heads`: no output of the rule changes, and a head's S [d_k, d_v] is
    whole tiles with zeros past the head's own rows and columns."""
    more = heads - v.shape[2]

    def grown(x, width=None):
        last = () if width is None else ((0, width - x.shape[-1]),)
        return jnp.pad(x, ((0, 0), (0, 0), (0, more)) + last)

    dk, dv = _tiles(q.shape[-1]), _tiles(v.shape[-1])
    return grown(q, dk), grown(k, dk), grown(v, dv), grown(g), grown(beta)


@functools.partial(
    jax.jit,
    static_argnames=("heads", "hb", "group", "save", "operands", "interpret"))
def _gdn_chunk(q, k, v, g, beta, segment_ids, *, heads, hb, group, save,
               operands, interpret):
    b, s = q.shape[:2]
    hv_out, dv_out = v.shape[-2:]
    if (heads, _tiles(q.shape[-1]), _tiles(dv_out)) != (
            hv_out, q.shape[-1], dv_out):
        q, k, v, g, beta = _to_tiles(q, k, v, g, beta, heads)
    hk, dk = q.shape[-2:]
    hv, dv = v.shape[-2:]
    pad = -s % CHUNK
    f32 = jnp.float32
    q, k = q.astype(operands), k.astype(operands)
    v, g, beta = v.astype(f32), g.astype(f32), beta.astype(f32)
    if pad:
        # Neutral tokens (beta 0, g 0) of the last token's segment, as
        # `gated_delta_chunked` pads: the state passes through unchanged.
        def zpad(x):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

        q, k, v, g, beta = (zpad(x) for x in (q, k, v, g, beta))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge")
    sp = s + pad
    gc = jnp.cumsum(g.reshape(b, sp // CHUNK, CHUNK, hv), axis=2)
    o = _rule(
        (hb, group, hk), save, interpret,
        q.reshape(b, sp, hk * dk), k.reshape(b, sp, hk * dk),
        v.reshape(b, sp, hv * dv), gc.reshape(b, sp, hv), beta, segment_ids)
    return o.reshape(b, sp, hv, dv)[:, :s, :hv_out, :dv_out]


def gdn_chunk(
    q: jax.Array,  # [B, S, hk, dk] fp32, normalised and scaled
    k: jax.Array,  # [B, S, hk, dk] fp32, normalised — KEY heads, no repeat
    v: jax.Array,  # [B, S, hv, dv]; key head i serves value heads
    g: jax.Array,  # [B, S, hv] fp32 log-decay (<= 0)   [i rep, (i+1) rep)
    beta: jax.Array,  # [B, S, hv]
    segment_ids: jax.Array,  # [B, S]
    block_h: int = 0,  # value heads a grid step (0: all of them)
    group: int = 0,  # of them a trip of the step's loop (0: `group_for`)
    save: bool = True,  # the backward's states and T: kept, or rebuilt
    operands=jnp.bfloat16,  # what the products outside the solve round to
    interpret=None,
) -> jax.Array:
    """The gated delta rule over packed rows -> o [B, S, hv, dv] fp32:
    `gated_delta_chunked`'s first result (on q and k repeated to hv heads),
    with a gradient rule of its own.  One `jit` entry point: every layer of
    a program binds one traced function and its kernels are lowered once."""
    hk, hv = q.shape[2], v.shape[2]
    heads = run_heads(hk, hv)
    hb = block_h or heads
    group = group or group_for(hb, hv // hk)
    assert heads % hb == 0 and hb % group == 0, (heads, hb, group)
    assert group % (hv // hk) == 0, (hv, hk, group)
    if interpret is None:
        interpret = _interpret()
    return _gdn_chunk(
        q, k, v, g, beta, segment_ids, heads=heads, hb=hb, group=group,
        save=bool(save), operands=jnp.dtype(operands),
        interpret=bool(interpret))

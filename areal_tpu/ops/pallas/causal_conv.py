"""Pallas kernels of the depthwise causal conv + activation over packed rows
— the training form of `linear_attention.causal_conv`, which stays the one
`jnp` definition of the operator, the path of prefill, of a mesh and of
every backend that is no TPU, and these kernels' oracle:

    pre[t] = sum_j taps[j] x[t - (K-1) + j]  (+ bias),  out = act(pre)

an input counted only where it lies in t's own segment, fp32 inside, fp32
out, `act` SiLU or the identity.  As XLA ops the operator is K - 1 pads of
the whole fp32 row, as many padded compares and selects, the SiLU and — in
the backward — the transposes of all of it with two reductions over the row
for the taps: six to nine times the seconds its bytes take (PERF.md section
6, PR 65).  Here a block of x is read once in its own type, the shifted
views are loads off VMEM, and the only fp32 [B, S, C] arrays are the
operator's result and its cotangent.

Layout: channels on lanes, tokens on sublanes, a grid over (row, token
block, channel block).  A block of x comes with the `HALO` rows before it —
the same array under a second `BlockSpec` — and, in the backward, with the
`HALO` rows after it.  Which of a token's K - 1 predecessors count is decided
OUTSIDE, once a call, as one int32 a token (`_mask_bits`: bit back-1 set
where token t - back lies in t's segment, bit 8+back-1 where t + back lies in
it), so the kernels never compare a halo's ids: a first block's or a last
block's halo is whatever the clamped index map read, and every use of it is
a `select` the bits turn off.  The bits come a token a sublane ([B, S, 1]:
512 B a token in HBM, read once a (row, token block): the channel axis is
the grid's last, and a block whose index holds is not fetched again).

Both kernels are bound by the vector unit's slots, not by the stream (11
bundles an fp32 register forward, 20 backward, by Mosaic's own dump; 67-78%
and 54-59% of the bytes' floor on the chip: PERF.md section 6, PR 65), so
what a tile does NOT do is the design:
- a loop's tile is `TILE` elements (ROWS tokens of the whole channel block,
  sixteen fp32 registers): Mosaic does not overlap a loop's iterations, and
  a shorter tile is bound by its own chain of dependent operations;
- the K shifted views of a tile are ONE aligned load of the tile and the
  eight rows beside it, cut by static slices (a load may not start off a
  sublane tile's edge at a row only the loop knows);
- the taps and the bias are spread over eight sublanes ONCE a grid step,
  into scratch the loops load as it is;
- a tile in whose reach no segment starts or ends — all but one or two a
  segment — takes a path with NO select: a flag a tile, prefetched scalars
  (`_mask_bits`), picks the path.

Forward `causal_conv_fwd`: x to fp32 in VMEM scratch behind its halo, then
the loop over tiles: the K views, the sum in the `jnp` form's own order (x
t[K-1], then back = 1 .. K-1), the bias, the activation — the `jnp` form's
result to the bit on the chip at the five cells' widths.

Backward `causal_conv_bwd`: no residual besides the operands.  The
pre-activation is made again in VMEM for the block's tokens AND the eight
after it (d_pre of the first K - 1 of them reaches back into this block),
d_pre = d_out act'(pre) goes to scratch, the taps' and the bias's gradients
are summed over the block's own tokens, in the loop's carry, into ONE fp32
output block [8 (K + 1), C] a row (eight sublanes of partial sums a tap,
then the bias's; their sum is `jnp` outside) that stays resident over the
two reduction axes, the grid's last; the input's gradient is d_pre's K
views against the taps, written as one array in x's type.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES = 128
SUB = 8  # sublanes of an fp32 tile
HALO = 16  # rows: a packed bf16 tile, two fp32 tiles; K - 1 <= 7 of them count
TILE = 32 * 512  # elements of a loop's tile: sixteen fp32 registers
MAX_TAPS = 8  # a token's bits hold K - 1 <= 7 predecessors and followers
TOKEN_BLOCKS = (1024, 512, 256, 128)
CHANNEL_BLOCKS = (512, 384, 256, 128)
_FWD_BIT = 8  # where the bits of the tokens AFTER t start
_VMEM_LIMIT = 48 * 1024 * 1024
_F32 = jnp.float32

ACTS = ("silu", "identity")


def fits(channels: int, taps: int) -> bool:
    """Whether the kernels can cut these widths: channels in whole 128-lane
    tiles, a token's predecessors within its bits (and the halo)."""
    return channels > 0 and channels % LANES == 0 and 2 <= taps <= MAX_TAPS


def _blocks(s: int, c: int):
    """-> (token block, padded row length, channel block, a tile's rows)."""
    bc = next(b for b in CHANNEL_BLOCKS if c % b == 0)
    rows = TILE // bc // 32 * 32
    unit = TOKEN_BLOCKS[-1] if s > TOKEN_BLOCKS[-1] else rows
    sp = -(-s // unit) * unit
    bt = next((b for b in TOKEN_BLOCKS if sp % b == 0), sp)
    return bt, sp, bc, rows


def _mask_bits(segment_ids: jax.Array, kk: int, rows: int):
    """[B, S] ids, S whole tiles of `rows` -> (bits [B, S, 1] int32: bit
    back-1 where token t - back exists and lies in t's segment — what
    `causal_conv` counts — and bit 8+back-1 where token t + back does;
    flags [B * S / rows] int32, a tile of `rows` tokens each: bit 0 where
    every token of the tile counts all its K - 1 predecessors, bit 1 where
    every one counts all its followers: no segment starts, respectively
    ends, in reach of the tile, and the kernels skip the selects)."""
    seg = segment_ids.astype(jnp.int32)
    b, s = seg.shape
    bits = jnp.zeros_like(seg)
    for back in range(1, kk):
        same = (seg[:, back:] == seg[:, :-back]).astype(jnp.int32)
        bits = bits | (jnp.pad(same, ((0, 0), (back, 0))) << (back - 1))
        bits = bits | (
            jnp.pad(same, ((0, 0), (0, back))) << (_FWD_BIT + back - 1))
    full = (1 << (kk - 1)) - 1
    tiles = bits.reshape(b, s // rows, rows)
    before = jnp.all((tiles & full) == full, axis=-1)
    after = jnp.all((tiles >> _FWD_BIT) == full, axis=-1)
    flags = before.astype(jnp.int32) | (after.astype(jnp.int32) << 1)
    return bits[..., None], flags.reshape(-1)


def _f32(v):
    return lax.convert_element_type(v, _F32)


def _tile_rows(i, rows: int):
    """(the first row of a loop's i-th tile, its rows as a slice)."""
    r0 = pl.multiple_of(lax.mul(i, rows), rows)
    return r0, pl.ds(r0, rows)


def _tall(row8, rows: int):
    """[8, lanes], a row over its sublanes -> [rows, lanes]."""
    return row8 if rows == SUB else lax.concatenate([row8] * (rows // SUB), 0)


def _counts(bits, back: int, lanes: int, after: bool = False):
    """Whether the token `back` before (`after`: behind) each of a tile's
    tokens counts, over the tile's lanes: bits [R, 1] -> [R, lanes]."""
    bit = 1 << (back - 1 + (_FWD_BIT if after else 0))
    on = lax.ne(lax.bitwise_and(bits, jnp.int32(bit)), jnp.int32(0))
    return lax.broadcast_in_dim(on, (bits.shape[0], lanes), (0, 1))


def _to_scratch(dst, at: int, src, rows: int):
    """`rows` rows of `src` (a ref, any float type) -> fp32 rows of `dst`
    from row `at`, HALO rows at a time."""

    def put(i, _):
        r0 = pl.multiple_of(lax.mul(i, HALO), HALO)
        dst[pl.ds(lax.add(r0, at), HALO), :] = _f32(src[pl.ds(r0, HALO), :])
        return 0

    lax.fori_loop(0, rows // HALO, put, 0)


def _spread(dst, taps_ref, bias_ref):
    """Each tap's (and the bias's) row of channels over eight sublanes of
    fp32 scratch, once a grid step: the loops load them as they are."""
    rows = [taps_ref[j: j + 1, :] for j in range(taps_ref.shape[0])]
    if bias_ref is not None:
        rows.append(bias_ref[...])
    for j, row in enumerate(rows):
        dst[j * SUB: (j + 1) * SUB, :] = lax.broadcast_in_dim(
            _f32(row), (SUB, row.shape[1]), (0, 1))


def _shifted(ref, at, rows: int, kk: int, bits, after: bool = False):
    """The K tiles of `rows` rows of `ref` that start 0 .. K-1 rows before
    (`after`: behind) row `at`, a multiple of 8: ONE aligned load of the
    tile and the eight rows beside it, cut where each shift says (a load may
    not start off a sublane tile's edge at a row only the loop knows).
    `bits`: the tile's tokens' [rows, 1], a shifted row kept where its bit
    says it counts and zero elsewhere; None: every row counts."""
    lo = at if after else lax.sub(at, SUB)
    win = ref[pl.ds(pl.multiple_of(lo, SUB), rows + SUB), :]
    views = []
    for back in range(kk):
        k = back if after else SUB - back
        v = lax.slice_in_dim(win, k, k + rows, axis=0)
        if back and bits is not None:
            v = lax.select(
                _counts(bits, back, v.shape[1], after), v,
                jnp.zeros(v.shape, _F32))
        views.append(v)
    return views


def _against_taps(views, taps8, rows: int, kk: int, has_bias: bool = False):
    """sum over back of views[back] * taps[K-1-back] (+ the bias), in
    `causal_conv`'s own order (x t[K-1], then back = 1 .. K-1): a tile's
    pre-activation of views[back] = x[t - back], and the input's gradient of
    views[back] = d_pre[t + back]."""
    out = lax.mul(views[0], _tall(taps8[kk - 1], rows))
    for back in range(1, kk):
        out = lax.add(
            out, lax.mul(views[back], _tall(taps8[kk - 1 - back], rows)))
    if has_bias:
        out = lax.add(out, _tall(taps8[kk], rows))
    return out


def _first_flag(bt: int, rows: int):
    """Where this grid step's token block's flags start (asked at the
    kernel's top: the interpreter knows no `program_id` inside a loop)."""
    b, t = pl.program_id(0), pl.program_id(1)
    per_row = lax.mul(pl.num_programs(1), bt // rows)
    return lax.add(lax.mul(b, per_row), lax.mul(t, bt // rows))


def _either(flag, bit: int, run):
    """run(False) where the tile's flag has `bit` (no select), else
    run(True)."""
    clean = lax.ne(lax.bitwise_and(flag, jnp.int32(bit)), jnp.int32(0))
    return lax.cond(clean, lambda: run(False), lambda: run(True))


def _fwd_kernel(flags_ref, *refs, kk: int, act: str, has_bias: bool,
                rows: int):
    refs = list(refs)
    bias_ref = refs.pop(4) if has_bias else None
    xh_ref, x_ref, m_ref, taps_ref, o_ref, xe, tb = refs
    bt, _ = x_ref.shape
    _to_scratch(xe, 0, xh_ref, HALO)
    _to_scratch(xe, HALO, x_ref, bt)
    _spread(tb, taps_ref, bias_ref)
    f0 = _first_flag(bt, rows)
    taps8 = [tb[j * SUB: (j + 1) * SUB, :] for j in range(kk + has_bias)]

    def tile(i, _):
        r0, at = _tile_rows(i, rows)

        def run(masked):
            views = _shifted(
                xe, lax.add(r0, HALO), rows, kk,
                m_ref[at, :] if masked else None)
            pre = _against_taps(views, taps8, rows, kk, has_bias)
            if act == "silu":
                pre = lax.mul(pre, lax.logistic(pre))
            o_ref[at, :] = pre
            return 0

        return _either(flags_ref[lax.add(f0, i)], 1, run)

    lax.fori_loop(0, bt // rows, tile, 0)


def _bwd_kernel(flags_ref, *refs, kk: int, act: str, has_bias: bool,
                rows: int):
    refs = list(refs)
    bias_ref = refs.pop(8) if has_bias else None
    (xh_ref, x_ref, xn_ref, do_ref, don_ref, m_ref, mn_ref, taps_ref,
     dx_ref, dw_ref, xe, dp, tb) = refs
    bt, bc = x_ref.shape
    ti, ci = pl.program_id(1), pl.program_id(2)
    n_sums = kk + has_bias

    @pl.when(lax.bitwise_and(lax.eq(ti, 0), lax.eq(ci, 0)))
    def _start():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    _to_scratch(xe, 0, xh_ref, HALO)
    _to_scratch(xe, HALO, x_ref, bt)
    _to_scratch(xe, HALO + bt, xn_ref, HALO)
    _spread(tb, taps_ref, bias_ref)
    f0 = _first_flag(bt, rows)
    taps8 = [tb[j * SUB: (j + 1) * SUB, :] for j in range(n_sums)]

    def d_pre(do_tile, at, n, bits):
        views = _shifted(xe, at, n, kk, bits)
        if act == "silu":
            pre = _against_taps(views, taps8, n, kk, has_bias)
            one = jnp.ones((n, bc), _F32)
            sg = lax.logistic(pre)
            # silu'(p) = s (1 + p (1 - s))
            do_tile = lax.mul(do_tile, lax.mul(sg, lax.add(
                lax.mul(pre, lax.sub(one, sg)), one)))
        return do_tile, views

    def fold(v):  # [rows, lanes] -> [8, lanes]: a sum over sublane tiles
        parts = [
            lax.slice_in_dim(v, k, k + SUB, axis=0)
            for k in range(0, rows, SUB)
        ]
        while len(parts) > 1:
            parts = [lax.add(a, b) for a, b in zip(parts[::2], parts[1::2])]
        return parts[0]

    # d_pre of the block's tokens, and over them the taps' and the bias's
    # gradients, in the loop's carry ...
    def tile(i, acc):
        r0, at = _tile_rows(i, rows)

        def run(masked):
            d, views = d_pre(
                do_ref[at, :], lax.add(r0, HALO), rows,
                m_ref[at, :] if masked else None)
            dp[at, :] = d
            sums = [
                lax.add(acc[j], fold(lax.mul(d, views[kk - 1 - j])))
                for j in range(kk)
            ]
            if has_bias:
                sums.append(lax.add(acc[kk], fold(d)))
            return tuple(sums)

        return _either(flags_ref[lax.add(f0, i)], 1, run)

    acc = lax.fori_loop(
        0, bt // rows, tile,
        tuple(jnp.zeros((SUB, bc), _F32) for _ in range(n_sums)))
    mine = pl.ds(pl.multiple_of(lax.mul(ci, bc), LANES), bc)
    for j, a in enumerate(acc):
        dw_ref[j * SUB: (j + 1) * SUB, mine] += a
    # ... and of the K - 1 tokens after the block (eight of them).
    d, _ = d_pre(don_ref[...], HALO + bt, SUB, mn_ref[...])
    dp[pl.ds(bt, SUB), :] = d

    # The input's gradient: d_pre's K shifted tiles against the taps.
    def tile_dx(i, _):
        r0, at = _tile_rows(i, rows)

        def run(masked):
            ds = _shifted(
                dp, r0, rows, kk, m_ref[at, :] if masked else None,
                after=True)
            dx_ref[at, :] = lax.convert_element_type(
                _against_taps(ds, taps8, rows, kk), dx_ref.dtype)
            return 0

        return _either(flags_ref[lax.add(f0, i)], 2, run)

    lax.fori_loop(0, bt // rows, tile_dx, 0)


def _specs(bt: int, bc: int, nt: int):
    """Block specs over the grid (row, token block, channel block), the
    prefetched flags behind the indices: a block of [B, S, C], the HALO rows
    before it and after it (clamped at the row's ends: the bits turn every
    use of those off), the eight rows after it (fp32), the bits, their eight
    rows after, a [J, C] operand's block."""
    hb, sb = bt // HALO, bt // SUB

    def prev(t):
        return lax.max(lax.sub(lax.mul(t, hb), 1), 0)

    def nxt(t, per):
        return lax.min(lax.mul(lax.add(t, 1), per), nt * per - 1)

    blk = pl.BlockSpec((None, bt, bc), lambda b, t, c, _: (b, t, c))
    before = pl.BlockSpec(
        (None, HALO, bc), lambda b, t, c, _: (b, prev(t), c))
    after = pl.BlockSpec(
        (None, HALO, bc), lambda b, t, c, _: (b, nxt(t, hb), c))
    after8 = pl.BlockSpec(
        (None, SUB, bc), lambda b, t, c, _: (b, nxt(t, sb), c))
    bits = pl.BlockSpec((None, bt, 1), lambda b, t, c, _: (b, t, 0))
    bits_after = pl.BlockSpec(
        (None, SUB, 1), lambda b, t, c, _: (b, nxt(t, sb), 0))

    def per_channel(rows):
        return pl.BlockSpec((rows, bc), lambda b, t, c, _: (0, c))

    return blk, before, after, after8, bits, bits_after, per_channel


def _bias_row(bias):
    return () if bias is None else (bias.reshape(1, -1),)


def _fwd(x, taps, bias, bits, flags, *, act: str, interpret: bool):
    """x [B, S, C], S whole token blocks -> act(conv + bias) fp32."""
    b, s, c = x.shape
    kk = taps.shape[0]
    bt, _, bc, rows = _blocks(s, c)
    blk, before, _, _, mbits, _, per_channel = _specs(bt, bc, s // bt)
    has_bias = bias is not None
    return named_call(
        "causal_conv_fwd",
        functools.partial(
            _fwd_kernel, kk=kk, act=act, has_bias=has_bias, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // bt, c // bc),
            in_specs=[before, blk, mbits, per_channel(kk)]
            + [per_channel(1)] * has_bias,
            out_specs=blk,
            scratch_shapes=[
                pltpu.VMEM((HALO + bt, bc), _F32),
                pltpu.VMEM((SUB * (kk + has_bias), bc), _F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, c), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(flags, x, x, bits, taps, *_bias_row(bias))


def _bwd(x, taps, bias, bits, flags, dout, *, act: str, interpret: bool):
    """-> d x [B, S, C] in x's type, d taps [K, C] and d bias [C] fp32."""
    b, s, c = x.shape
    kk = taps.shape[0]
    bt, _, bc, rows = _blocks(s, c)
    blk, before, after, after8, mbits, mbits_after, per_channel = _specs(
        bt, bc, s // bt)
    has_bias = bias is not None
    n_sums = kk + has_bias
    dx, dw = named_call(
        "causal_conv_bwd",
        functools.partial(
            _bwd_kernel, kk=kk, act=act, has_bias=has_bias, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // bt, c // bc),
            in_specs=[before, blk, after, blk, after8, mbits, mbits_after,
                      per_channel(kk)] + [per_channel(1)] * has_bias,
            out_specs=[
                blk,
                # A row's sums, eight sublanes of partial sums each:
                # resident over the token and the channel axes.
                pl.BlockSpec(
                    (None, SUB * n_sums, c), lambda b, t, c, _: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((HALO + bt + HALO, bc), _F32),
                pltpu.VMEM((bt + SUB, bc), _F32),
                pltpu.VMEM((SUB * n_sums, bc), _F32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, s, c), x.dtype),
            jax.ShapeDtypeStruct((b, SUB * n_sums, c), _F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(flags, x, x, x, dout, dout, bits, bits, taps, *_bias_row(bias))
    dw = jnp.sum(dw.reshape(b, n_sums, SUB, c), axis=(0, 2))
    return dx, dw[:kk], dw[kk] if has_bias else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rule(act, interpret, x, taps, bias, bits, flags):
    return _fwd(x, taps, bias, bits, flags, act=act, interpret=interpret)


def _rule_fwd(act, interpret, x, taps, bias, bits, flags):
    out = _fwd(x, taps, bias, bits, flags, act=act, interpret=interpret)
    return out, (x, taps, bias, bits, flags)


def _rule_bwd(act, interpret, res, dout):
    x, taps, bias, bits, flags = res
    dx, dtaps, dbias = _bwd(
        x, taps, bias, bits, flags, dout.astype(_F32), act=act,
        interpret=interpret)
    return (dx, dtaps.astype(taps.dtype),
            None if bias is None else dbias.astype(bias.dtype), None, None)


_rule.defvjp(_rule_fwd, _rule_bwd)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _causal_conv_act(x, taps, bias, segment_ids, *, act, interpret):
    s = x.shape[1]
    _, sp, _, rows = _blocks(s, x.shape[2])
    if sp != s:
        # Tokens of a segment of their own (-1) behind the row: nothing
        # reads them, and their cotangent is zero.
        x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))
        segment_ids = jnp.pad(
            segment_ids, ((0, 0), (0, sp - s)), constant_values=-1)
    out = _rule(
        act, interpret, x, taps, bias,
        *_mask_bits(segment_ids, taps.shape[0], rows))
    return out[:, :s]


def causal_conv_act(
    x: jax.Array,  # [B, S, C]
    taps: jax.Array,  # [K, C], oldest first
    bias,  # [C] | None
    segment_ids: jax.Array,  # [B, S]
    act: str = "silu",  # | "identity"
    interpret=None,
) -> jax.Array:
    """act(`linear_attention.causal_conv`(x, taps, segment_ids) + bias) ->
    fp32 [B, S, C], with a gradient rule of its own (kernels
    `causal_conv_fwd` / `causal_conv_bwd`).  One `jit` entry point: every
    layer of a program binds one traced function and its kernels are
    lowered once."""
    assert act in ACTS, act
    assert fits(x.shape[-1], taps.shape[0]), (x.shape, taps.shape)
    if interpret is None:
        interpret = _interpret()
    return _causal_conv_act(
        x, taps, bias, segment_ids, act=act, interpret=bool(interpret))

"""Pallas kernel of the serving plane's Mamba-2 recurrence: one short SSD
chunk a slot, from the slot's carried state, for the slots that hold a
lane (`models/mamba.ssd_slab` over `slab_terms` is the `jnp` form, the path
off a TPU backend and this kernel's oracle).

A slot's lanes of one inner step are one short SSD chunk from the slot's
carried state S0 [H, P, N] fp32.  Two things touch S0: the lanes' READ of
it, y_w = C_w . S0 (scaled by the decay from the chunk's start to lane w),
and the REWRITE, S_new = keep S0 + sum_j (w_out_j dt_j x_j) (x) B_j.  As
XLA operations the read is a matmul fusion of its own and the rewrite an
elementwise fusion, and each streams a layer's [64, 64, 64, 128] state
(134 MB) from HBM — over every slot, although only the slots that hold a
lane change (0.23 + 0.41 ms a layer: PERF.md section 6, PR 53).  Here a
head block's tiles are read into VMEM ONCE, both products run on them
there, and the tiles are written back where they lie:

    y [W, hb P]     = w_in * C [W, N] . S0^T  +  triangle      (NT on the MXU)
    S_new [hb P, N] = keep_h S0 + XW^T [hb P, W] . B [W, N]    (TN)

Everything else the chunk is made of — xd = dt x, the inclusive cumulative
log-decay ga over the slot's lanes, w_in = exp(ga) (0 for a restarted
slot), w_out = exp(ga_last - ga), XW = w_out xd, cb = C . B^T and the
chunk's own lower triangle sum_{j <= i} cb_ij exp(ga_i - ga_j) xd_j — is
made in the same grid step from operands that are in VMEM there, for the
W lanes of the slot and the block's channels alone (since PR 63: as XLA
fusions over the whole [R, W] slab these terms cost 0.79 ms an inner step
of `granite4hm-serving-waves` where a ninth of the slab's lanes exist;
PERF.md section 6, PR 63):

- The layer's buffer `[steps, R, H, P, N]` goes in whole and comes out
  ALIASED to itself, the scan step a prefetched scalar in the index maps
  of the state's in- and out-block (`delta_step.gdn_delta_step`'s form):
  no slice, no `dynamic-update-slice`, no `where(held, new, state)`.
- The grid's first axis walks a prefetched LIST of the slots that hold a
  lane (`live`, `n_live`).  Steps past the last live slot name the block
  of the step before them, so Pallas issues no copy, and their body is
  skipped: a slot with no lane moves no bytes, costs no term, and its
  state stays bit-identical because it is never touched.  A restarted
  (`fresh`) slot is `keep` = 0 and w_in = 0: no zeroing pass.
- x, B and C are read WHERE THE CONV LEFT THEM: three block specs on its
  one output `[R, W, H P + 2 G N]` (x | B | C; a block of x is `hb P`
  whole lane tiles of a row, B and C of the block's group N columns
  behind them — `fits` asks that they start at whole N-column blocks), so
  no slice of it is copied.  dt `[R, W, H]` and A `[1, H]` come as they
  are; `keep` `[R H]` (the one number a head that meets the state and not
  a lane: exp of the slot's whole log-decay, 0 for a restart) and the
  `carried` flags `[R]` are scalars in SMEM.
- A head's dt and ga, `[W, H]`, reach the head's P channels `[W, hb P]`
  through the MXU: a 0/1 matrix `[H, hb P]` a head block (made once a
  call, in scratch) against the values split into three bf16 pieces whose
  fp32 sum is the value to the last bit (`_per_channel`) — an exact
  spread at one MXU pass, where a lane gather or 64 lane broadcasts a
  grid step would be the alternative.  The triangle runs on the VPU, a
  source lane j at a time over `[W, hb P]`: [W, W] a head is no MXU tile
  and the decay differs by head, and exp(ga_i - ga_j) is taken of the
  DIFFERENCE (a product exp(ga_i) exp(-ga_j) overflows at large dt A).
- The kernel writes the slot's FINISHED y, the D skip in it (x is in
  VMEM there; as `jnp` the skip alone turned the slab's x to `[.., H, P]`
  tiles and gathered it to the stream, 0.23 ms an inner step: PERF.md
  section 6, PR 63); what is left to `mamba.ssd_slab_in_place` is the
  gather of y back to the stream.  The rows of a slot with no lane are
  not written.

Precision: the state stays fp32 in HBM and in VMEM; `keep S0`, the sums,
the decays and every exponential are fp32 VPU / EUP operations; the three
products with data (C . S0^T, C . B^T, XW^T . B) go to the MXU at the
default precision with fp32 accumulation — what XLA does with the `jnp`
form's `einsum`s on a TPU (operands rounded to bf16 in front of a
`convolution`: compiled text, PR 54), so the numbers differ from the XLA
form's by the order of the sums alone.  Interpreted off a TPU, fp32
throughout.  One lane or W cost the same grid step, so there is one form
for every lane count; the call is bound by its DMA.  The body binds `lax`
primitives, not `jnp` functions (`delta_step.py`: a `jnp` operator on a
traced value is a `jit` call of its own, and the unit unrolls nine
layers).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES, SUBLANES = 128, 8
MAX_BLOCK_H = 32  # heads a grid step: 1 MB of tiles in, 1 MB out at [64, 128]


def block_h_for(h: int, g: int, p: int) -> int:
    """Heads a grid step: the most, up to MAX_BLOCK_H, that divide a
    group's heads (a block reads ONE group's B and C) and whose `hb * P`
    channels are whole 128-lane tiles of the lane-dense operands (a block
    of x is a run of the conv's columns).  0: none."""
    hg = h // g
    for hb in range(min(hg, MAX_BLOCK_H), 0, -1):
        if hg % hb == 0 and (hb * p) % LANES == 0:
            return hb
    return 0


def fits(h: int, g: int, p: int, n: int) -> bool:
    """Whether a head's [P, N] tile is whole (8, 128) fp32 tiles, the heads
    cut into blocks, and B and C start at whole N-column blocks behind x in
    the conv's row (x | B | C)."""
    return (p % SUBLANES == 0 and n % LANES == 0 and (h * p) % n == 0
            and block_h_for(h, g, p) > 0)


def _row(v, j: int):
    """Row j of a [rows, lanes] value on every one of `rows` sublanes."""
    return lax.broadcast_in_dim(
        lax.slice(v, (j, 0), (j + 1, v.shape[1])), v.shape, (0, 1))


def _bf16_pieces(v):
    """An fp32 value as three values whose sum it is, each one a bfloat16
    number exactly (the top 16 bits, then the top 16 of what is left,
    twice): what passes the MXU's bf16 operands without a rounding."""
    pieces = []
    for _ in range(3):
        top = lax.bitcast_convert_type(
            lax.bitwise_and(
                lax.bitcast_convert_type(v, jnp.int32), jnp.int32(-65536)),
            jnp.float32)
        pieces.append(top)
        v = lax.sub(v, top)
    return pieces


def _per_channel(v, spread):
    """A number a head [rows, H] -> on each of the P channels of a block's
    heads [rows, hb * P], to the last bit: the three bf16 pieces of `v`
    through the block's 0/1 matrix `spread` [H, hb * P] on the MXU, summed
    in fp32."""
    rows = v.shape[0]
    out = lax.dot_general(
        lax.convert_element_type(
            lax.concatenate(_bf16_pieces(v), 0), jnp.bfloat16),
        spread, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    part = [lax.slice(out, (k * rows, 0), ((k + 1) * rows, out.shape[1]))
            for k in range(3)]
    return lax.add(lax.add(part[0], part[1]), part[2])


def _slab_kernel(
    li_ref, live_ref, n_live_ref,  # prefetched scalars
    keep_ref,  # [R * H] fp32 in SMEM: the carried state's decay; 0 = restart
    carried_ref,  # [R] fp32 in SMEM: 1 = the slot starts from its state
    a_ref,  # [1, H]
    d_ref,  # [nh, hb * P]: D on each head's channels, every block
    dt_ref,  # [W, H]: the slot's dt
    cm_ref, bm_ref,  # [W, N]: the block's group, where the conv left them
    x_ref,  # [W, hb * P]: the block's channels, where the conv left them
    s_ref,  # [hb, P, N]
    s_out_ref, y_ref,  # outputs: [hb, P, N], [W, hb * P]
    spread_ref,  # scratch [nh, H, hb * P] bf16: a block's heads -> channels
    *, hb: int,
):
    del li_ref
    i, hi = pl.program_id(0), pl.program_id(1)
    p, n = s_ref.shape[-2:]
    w, c = x_ref.shape
    nh, h = spread_ref.shape[:2]
    f32 = jnp.float32
    mul, add, sub = lax.mul, lax.add, lax.sub

    @pl.when((i == 0) & (hi == 0))
    def _():
        head = lax.broadcasted_iota(jnp.int32, (h, c), 0)
        chan = lax.broadcasted_iota(jnp.int32, (h, c), 1)
        for b in range(nh):
            first = lax.mul(lax.sub(head, b * hb), p)
            own = lax.bitwise_and(
                lax.ge(chan, first), lax.lt(chan, lax.add(first, p)))
            spread_ref[b] = lax.convert_element_type(
                lax.select(own, lax.full((h, c), 1.0, f32),
                           lax.full((h, c), 0.0, f32)), jnp.bfloat16)

    @pl.when(i < n_live_ref[0])
    def _():
        slot = live_ref[i]
        # A head's numbers: dt and the inclusive cumulative log-decay over
        # the slot's lanes (dt = 0 behind them: the last row IS the last
        # lane's), then both on the channels of the block's heads.
        dt = dt_ref[...]  # [W, H]
        da = mul(dt, lax.broadcast_in_dim(a_ref[...], (w, h), (0, 1)))
        lane = lax.broadcasted_iota(jnp.int32, (w, h), 0)
        ga = zero = lax.full((w, h), 0.0, f32)
        for j in range(w):
            ga = add(ga, lax.select(lax.ge(lane, j), _row(da, j), zero))
        both = _per_channel(lax.concatenate([dt, ga], 0), spread_ref[hi])
        dt = lax.slice(both, (0, 0), (w, c))
        ga = lax.slice(both, (w, 0), (2 * w, c))
        x = x_ref[...]
        xd = mul(x, dt)  # [W, hb P]
        cm, bm = cm_ref[...], bm_ref[...]
        s0 = s_ref[...]
        # The lanes' read of the carried state, scaled by the decay from
        # the chunk's start to each lane ...
        y = mul(
            lax.dot_general(
                cm, lax.reshape(s0, (hb * p, n)), (((1,), (1,)), ((), ())),
                preferred_element_type=f32),
            mul(lax.exp(ga), lax.full((w, c), carried_ref[slot], f32)))
        # ... and the chunk's own lower triangle, a source lane at a time
        # on the VPU: [W, W] a head is no MXU tile, and the decay differs
        # by head.
        cb = lax.dot_general(
            cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        lane = lax.broadcasted_iota(jnp.int32, (w, c), 0)
        zero = lax.full((w, c), 0.0, f32)
        for j in range(w):
            decay = lax.select(
                lax.ge(lane, j), lax.exp(sub(ga, _row(ga, j))), zero)
            cbj = lax.broadcast_in_dim(
                lax.slice(cb, (0, j), (w, j + 1)), (w, c), (0, 1))
            y = add(y, mul(mul(decay, cbj), _row(xd, j)))
        # ... and the D skip, x being here.
        y_ref[...] = add(y, mul(x, lax.broadcast_in_dim(
            d_ref[pl.ds(hi, 1), :], (w, c), (0, 1))))
        # What the slot leaves: the state decayed over all its lanes plus
        # each lane's outer product decayed to the last.
        xw = mul(xd, lax.exp(sub(_row(ga, w - 1), ga)))
        own = lax.reshape(
            lax.dot_general(
                xw, bm, (((0,), (0,)), ((), ())),
                preferred_element_type=f32), (hb, p, n))
        base = slot * h + hi * hb
        for k in range(hb):
            # The last block of a head count that hb does not divide reads
            # past the heads: its rows are dropped on the way out.
            keep = keep_ref[lax.min(base + k, keep_ref.shape[0] - 1)]
            s_out_ref[k] = add(
                mul(s0[k], lax.full((p, n), keep, f32)), own[k])

    # With no live slot the one block the grid names is still written
    # back: hand it over as it came.
    @pl.when((n_live_ref[0] == 0) & (i == 0) & (hi == 0))
    def _():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def _slab_step(
        states, li, live, n_live, conv, dt, a, d, carried, block_h,
        interpret):
    _, r, h, p, n = states.shape
    w = conv.shape[1]
    g = (conv.shape[2] - h * p) // (2 * n)
    assert conv.shape[2] == h * p + 2 * g * n and fits(h, g, p, n), (
        conv.shape, states.shape)
    hb = block_h or block_h_for(h, g, p)
    hg = h // g
    assert (hb * p) % LANES == 0 and (g == 1 or hg % hb == 0), (h, g, hb)
    nh = pl.cdiv(h, hb)
    f32 = jnp.float32
    dt, a, carried = dt.astype(f32), a.astype(f32), carried.astype(f32)
    # The one number a head that meets the state and not a lane: its decay
    # over all the slot's lanes.
    keep = jnp.exp(jnp.sum(dt * a, axis=1)) * carried[:, None]  # [R, H]
    # D on each head's channels, by block (zeros behind the heads where hb
    # does not divide h): H P numbers of weights.
    d = jnp.repeat(jnp.pad(d.astype(f32), (0, nh * hb - h)), p).reshape(
        nh, hb * p)

    def slot(i, hi, li_ref, live_ref, n_ref):
        """(slot, head block) of grid step (i, hi): past the last live
        slot, the block of the last live step — no new copy."""
        return live_ref[i], jnp.where(i < n_ref[0], hi, nh - 1)

    def tiles(i, hi, li_ref, live_ref, n_ref):
        s, b = slot(i, hi, li_ref, live_ref, n_ref)
        return li_ref[0], s, b, 0, 0

    def group_at(first):
        """B (C) of the block's group: N columns of the conv's row, in
        N-column blocks from `first`."""
        def index(i, hi, *refs):
            s, b = slot(i, hi, *refs)
            return s, 0, first + (b * hb) // hg
        return pl.BlockSpec((None, w, n), index)

    def channels(i, hi, *refs):
        s, b = slot(i, hi, *refs)
        return s, 0, b

    def lanes_of(i, hi, li_ref, live_ref, n_ref):
        return live_ref[i], 0, 0

    state_spec = pl.BlockSpec((None, None, hb, p, n), tiles)
    chan_spec = pl.BlockSpec((None, w, hb * p), channels)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    conv = conv.astype(f32)
    return named_call(
        "ssm_slab_step",
        functools.partial(_slab_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r, nh),
            in_specs=[
                smem, smem,
                pl.BlockSpec((1, h), lambda i, hi, *refs: (0, 0)),
                pl.BlockSpec((nh, hb * p), lambda i, hi, *refs: (0, 0)),
                pl.BlockSpec((None, w, h), lanes_of),
                group_at(h * p // n + g), group_at(h * p // n),
                chan_spec, state_spec,
            ],
            out_specs=[state_spec, chan_spec],
            scratch_shapes=[pltpu.VMEM((nh, h, hb * p), jnp.bfloat16)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, f32),
            jax.ShapeDtypeStruct((r, w, h * p), f32),
        ],
        # Operand 11 counting the three prefetched scalars: the state is
        # its own output, updated where it lies.
        input_output_aliases={11: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(
        jnp.asarray(li, jnp.int32).reshape(1), live.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        keep.reshape(r * h), carried, a.reshape(1, h), d, dt,
        conv, conv, conv, states,
    )


def ssm_slab_step(
    states: jax.Array,  # [steps, R, H, P, N] fp32 — one layer's buffer
    li,  # scalar int32 — the scan step that steps
    live: jax.Array,  # [R] int32: the slots with a lane, first (`live_slots`)
    n_live,  # scalar int32: how many
    conv: jax.Array,  # [R, W, H P + 2 G N] fp32: x | B | C, the conv's rows
    dt: jax.Array,  # [R, W, H] fp32 after softplus; 0 = no lane
    a: jax.Array,  # [H] fp32, negative
    d: jax.Array,  # [H]: the skip's weight a head
    carried: jax.Array,  # [R] fp32: 1 = start from the state, 0 = from zero
    block_h: int = 0,
):
    """One short SSD chunk a live slot, its terms made in the kernel ->
    (states with step `li` of the live slots stepped in place, y [R, W,
    H * P] fp32 WITH the D skip; the rows of a slot with no lane are
    NOT written, and a live slot's rows behind its lanes hold no lane's
    y).  x, B and C are read where the conv left them: blocks of columns
    of its rows.  `block_h`: heads a grid step (0: `block_h_for`)."""
    return _slab_step(
        states, li, live, n_live, conv, dt, a, d, carried,
        block_h=block_h, interpret=_interpret())


def live_slots(count: jax.Array):
    """The kernel's work list from each slot's lane count [R] -> (the
    slots that hold a lane in slot order, then the LAST of them repeated;
    their number)."""
    held = count > 0
    n_live = jnp.sum(held, dtype=jnp.int32)
    order = jnp.argsort(~held, stable=True).astype(jnp.int32)
    at = jnp.minimum(
        jnp.arange(count.shape[0], dtype=jnp.int32),
        jnp.maximum(n_live - 1, 0))
    return order[at], n_live

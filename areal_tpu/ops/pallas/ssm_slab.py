"""Pallas kernel of the serving plane's Mamba-2 recurrence: the part of a
slab step that touches the carried state (`models/mamba.ssd_slab` is the
`jnp` form, the path off a TPU backend and this kernel's oracle).

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

    y_raw [W, hb P] = C [W, N] . S0^T            (NT on the MXU)
    S_new [hb P, N] = keep_h S0 + XW^T [hb P, W] . B [W, N]   (TN)

- The layer's buffer `[steps, R, H, P, N]` goes in whole and comes out
  ALIASED to itself, the scan step a prefetched scalar in the index maps
  of the state's in- and out-block (`delta_step.gdn_delta_step`'s form):
  no slice, no `dynamic-update-slice`, no `where(held, new, state)`.
- The grid's first axis walks a prefetched LIST of the slots that hold a
  lane (`live`, `n_live`).  Steps past the last live slot name the block
  of the step before them, so Pallas issues no copy, and their body is
  skipped: a slot with no lane moves no bytes, and its state stays
  bit-identical because it is never touched.  A restarted (`fresh`) slot
  is `keep` = 0: no zeroing pass.
- Everything that does not touch the state (the cumulative decays, the
  chunk's own [W, W] lower triangle, the D skip, the gather back to the
  stream, the scale of `y_raw`) stays `jnp` in `mamba.ssm_ragged`; the
  per-slot operands are small: C, B `[R, G, W, N]`, XW `[R, W, H P]` (a
  lane's dt x decayed to the slot's last lane, lane-dense), `keep`
  `[R H]` in SMEM.

Precision: the state stays fp32 in HBM and in VMEM; `keep S0` and the sum
are fp32 VPU operations; the two products go to the MXU at the default
precision with fp32 accumulation — what XLA does with the `jnp` form's
two `einsum`s on a TPU (operands rounded to bf16 in front of a
`convolution`: compiled text, PR 54), so the numbers differ from the XLA
form's by the order of the sums alone.  Interpreted off a TPU, fp32
throughout.  One lane or W cost the MXU the same pass, so there is one
form for every lane count; the call is bound by its DMA.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES, SUBLANES = 128, 8
MAX_BLOCK_H = 32  # heads a grid step: 1 MB of tiles in, 1 MB out at [64, 128]


def block_h_for(h: int, g: int, p: int) -> int:
    """Heads a grid step: the most, up to MAX_BLOCK_H, that divide a
    group's heads (a block reads ONE group's B and C) and whose `hb * P`
    channels are whole 128-lane tiles of the lane-dense operands; every
    head where there is one group and no such count.  0: none."""
    hg = h // g
    for hb in range(min(hg, MAX_BLOCK_H), 0, -1):
        if hg % hb == 0 and (hb * p) % LANES == 0:
            return hb
    return h if g == 1 else 0


def fits(h: int, g: int, p: int, n: int) -> bool:
    """Whether a head's [P, N] tile is whole (8, 128) fp32 tiles and the
    heads cut into blocks."""
    return p % SUBLANES == 0 and n % LANES == 0 and block_h_for(h, g, p) > 0


def _slab_kernel(
    li_ref, live_ref, n_live_ref,  # prefetched scalars
    keep_ref,  # [R * H] fp32 in SMEM
    cm_ref, bm_ref,  # [W, N]: the block's group
    xw_ref,  # [W, hb * P]
    s_ref,  # [hb, P, N]
    s_out_ref, y_ref,  # outputs: [hb, P, N], [W, hb * P]
    *, hb: int, h: int,
):
    del li_ref
    i, hi = pl.program_id(0), pl.program_id(1)
    p, n = s_ref.shape[-2:]
    f32 = jnp.float32

    @pl.when(i < n_live_ref[0])
    def _():
        s0 = s_ref[...]
        flat = s0.reshape(hb * p, n)
        y_ref[...] = jax.lax.dot_general(
            cm_ref[...], flat, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        own = jax.lax.dot_general(
            xw_ref[...], bm_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=f32).reshape(hb, p, n)
        base = live_ref[i] * h + hi * hb
        for k in range(hb):
            # The last block of a head count that hb does not divide reads
            # past the heads: its rows are dropped on the way out.
            keep = keep_ref[jnp.minimum(base + k, keep_ref.shape[0] - 1)]
            s_out_ref[k] = s0[k] * keep + own[k]

    # With no live slot the one block the grid names is still written
    # back: hand it over as it came.
    @pl.when((n_live_ref[0] == 0) & (i == 0) & (hi == 0))
    def _():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def _slab_step(states, li, live, n_live, cm, bm, xw, keep, block_h, interpret):
    _, r, h, p, n = states.shape
    g, w = cm.shape[1:3]
    hb = block_h or block_h_for(h, g, p)
    hg = h // g
    assert hb and (g == 1 or hg % hb == 0), (h, g, hb)
    nh = pl.cdiv(h, hb)
    f32 = jnp.float32

    def slot(i, hi, li_ref, live_ref, n_ref):
        """(slot, head block) of grid step (i, hi): past the last live
        slot, the block of the last live step — no new copy."""
        return live_ref[i], jnp.where(i < n_ref[0], hi, nh - 1)

    def tiles(i, hi, li_ref, live_ref, n_ref):
        s, b = slot(i, hi, li_ref, live_ref, n_ref)
        return li_ref[0], s, b, 0, 0

    def group(i, hi, *refs):
        s, b = slot(i, hi, *refs)
        return s, (b * hb) // hg, 0, 0

    def channels(i, hi, *refs):
        s, b = slot(i, hi, *refs)
        return s, 0, b

    state_spec = pl.BlockSpec((None, None, hb, p, n), tiles)
    group_spec = pl.BlockSpec((None, None, w, n), group)
    chan_spec = pl.BlockSpec((None, w, hb * p), channels)
    return named_call(
        "ssm_slab_step",
        functools.partial(_slab_kernel, hb=hb, h=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r, nh),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                group_spec, group_spec, chan_spec, state_spec,
            ],
            out_specs=[state_spec, chan_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, f32),
            jax.ShapeDtypeStruct((r, w, h * p), f32),
        ],
        # Operand 7 counting the three prefetched scalars: the state is
        # its own output, updated where it lies.
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(
        jnp.asarray(li, jnp.int32).reshape(1), live.astype(jnp.int32),
        jnp.asarray(n_live, jnp.int32).reshape(1),
        keep.astype(f32).reshape(r * h),
        cm.astype(f32), bm.astype(f32), xw.astype(f32), states,
    )


def ssm_slab_step(
    states: jax.Array,  # [steps, R, H, P, N] fp32 — one layer's buffer
    li,  # scalar int32 — the scan step that steps
    live: jax.Array,  # [R] int32: the slots with a lane, first (`live_slots`)
    n_live,  # scalar int32: how many
    cm: jax.Array,  # [R, G, W, N] fp32
    bm: jax.Array,  # [R, G, W, N] fp32
    xw: jax.Array,  # [R, W, H * P] fp32: dt x decayed to the last lane
    keep: jax.Array,  # [R, H] fp32: the carried state's decay; 0 = restart
    block_h: int = 0,
):
    """-> (states with step `li` of the live slots stepped in place, y_raw
    [R, W, H * P] fp32 = C . S0 before its decay; the rows of a slot with
    no lane are NOT written).  `block_h`: heads a grid step (0:
    `block_h_for`)."""
    return _slab_step(
        states, li, live, n_live, cm, bm, xw, keep,
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

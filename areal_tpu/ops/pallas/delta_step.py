"""Pallas kernel of the Gated DeltaNet decode step: one token a row against
the layer's recurrent state, the state read ONCE and rewritten in place.

Per value head, with the [d_k, d_v] fp32 tile S, the token's k, q [d_k],
v [d_v] and the scalars e^g, beta:

    sk = S^T k;  sq = S^T q
    d  = beta (v - e^g sk)
    o  = e^g sq + (k . q) d          (= S_new^T q)
    S <- e^g S + k d^T

`d` is a reduction over the whole d_k axis of the tile that is then
rewritten: XLA emits the reduction and the rewrite as two fusions and each
streams the state from HBM (0.18 + 0.41 ms a layer at 64 rows where the
bytes allow 0.33: ledger, PR 41).  Here a head's tile stays in VMEM
between the two.  The stacked state `[n_linear, B, h_v, d_k, d_v]` goes in
as it lies and comes out ALIASED to itself: the layer is a prefetched
scalar in the index maps of the input and of the output block, so layer
`li`'s tiles are read where they lie and written where they lie and no
other layer's bytes are touched — no slice, no `dynamic-update-slice`.

Grid (B, blocks of value heads).  k and q arrive as rows ([heads, 128],
d_k on lanes) because a `[d_k, 1]` column pads to 128 lanes in HBM and
would cost the state's bytes again; the kernel turns them into columns
itself (an XLU tile transpose a head and operand).  The call is bound by
its DMA, not by these: 0.41 ms a layer at 64 rows x 32 heads in the decode
loop (653 GB/s; 8, 16 or 32 heads a grid step, the columns made once a
head block or once a head, all within 1%: chip runs, PR 42).  Every
product and sum is an fp32 VPU operation — the mathematics and the
precision of `models/linear_attention.delta_step_jnp`, which stays the
path off a TPU backend and this kernel's oracle; only the order of the
fp32 additions differs.  Rows are independent, so a mesh that spreads
them over devices runs the kernel per device on its own
rows (`gdn_delta_step_sharded`).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES = 128
MAX_BLOCK_H = 16  # heads a grid step: 1 MB of tiles in, 1 MB out, twice


def fits(dk: int, dv: int) -> bool:
    """Whether a head's tile is whole 128-lane tiles both ways (what the
    kernel's blocks and its transposes need)."""
    return dk % LANES == 0 and dv % LANES == 0


def block_h_for(hv: int) -> int:
    """Heads a grid step: whole 8-sublane tiles of the [heads, 128]
    operands, at most MAX_BLOCK_H; all of them where they are fewer."""
    return min(hv, MAX_BLOCK_H)


def _delta_step_kernel(
    layer_ref,  # prefetched scalar, read by the index maps alone
    q_ref, k_ref, v_ref, eg_ref, beta_ref, s_ref,  # inputs
    s_out_ref, o_ref,  # outputs
    *, hb: int,
):
    del layer_ref
    dk, dv = s_ref.shape[-2:]
    k = k_ref[0]  # [hb, dk]
    q = q_ref[0]
    v = v_ref[0]  # [hb, dv]
    eg = eg_ref[0]  # [hb, dv], a head's e^g on every lane
    beta = beta_ref[0]  # [hb, 1]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)  # [hb, 1]
    for h in range(hb):
        s = s_ref[h]  # [dk, dv]
        # k and q as columns over the tile's lanes: the row on every
        # sublane, turned (an XLU tile transpose, hidden behind the DMA).
        kc = jnp.broadcast_to(k[h: h + 1], (dv, dk)).T  # [dk, dv]
        qc = jnp.broadcast_to(q[h: h + 1], (dv, dk)).T
        sk = jnp.sum(s * kc, axis=0, keepdims=True)  # [1, dv]
        sq = jnp.sum(s * qc, axis=0, keepdims=True)
        e = eg[h: h + 1]  # [1, dv]
        d = beta[h: h + 1] * (v[h: h + 1] - e * sk)
        o_ref[0, h: h + 1, :] = e * sq + kq[h: h + 1] * d
        s_out_ref[h] = s * e + kc * d


@functools.partial(jax.jit, static_argnames=("block_h",))
def gdn_delta_step(
    states: jax.Array,  # [n_linear, B, hv, dk, dv] fp32 — the STACKED state
    layer: jax.Array,  # scalar int32 — the layer that steps
    q: jax.Array,  # [B, hv, dk] fp32, normalised and scaled
    k: jax.Array,  # [B, hv, dk] fp32, normalised
    v: jax.Array,  # [B, hv, dv] fp32
    g: jax.Array,  # [B, hv] fp32 log-decay (<= 0)
    beta: jax.Array,  # [B, hv] fp32
    block_h: int = 0,
):
    """-> (states with layer `layer` stepped in place, o [B, hv, dv] fp32).
    `block_h`: heads a grid step (0: `block_h_for`); one that does not
    divide hv leaves a last block whose tail is read and never written."""
    _, b, hv, dk, dv = states.shape
    hb = block_h or block_h_for(hv)
    assert hb % 8 == 0 or hb == hv, (hb, hv)
    f32 = jnp.float32

    def rows(x):
        return pl.BlockSpec(
            (1, hb, x.shape[-1]), lambda bi, hi, _: (bi, hi, 0))

    def tiles(bi, hi, layer_ref):
        return layer_ref[0], bi, hi, 0, 0

    # e^g reaches the kernel spread over the lanes (1 MB at 64 rows): it
    # scales the whole tile, and Mosaic does not broadcast a [1, 1] both
    # ways.
    eg = jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], (b, hv, dv))
    beta = beta.astype(f32)[..., None]
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    state_spec = pl.BlockSpec((None, None, hb, dk, dv), tiles)
    return named_call(
        "gdn_delta_step",
        functools.partial(_delta_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, pl.cdiv(hv, hb)),
            in_specs=[
                rows(q), rows(k), rows(v), rows(eg), rows(beta), state_spec,
            ],
            out_specs=[state_spec, rows(v)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, f32),
            jax.ShapeDtypeStruct((b, hv, dv), f32),
        ],
        # Operand 6 counting the prefetched layer: the state is its own
        # output, updated where it lies.
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), q, k, v, eg, beta, states)


def gdn_delta_step_sharded(states, layer, q, k, v, g, beta, mesh):
    """`gdn_delta_step` on a mesh whose batch axes (data, fsdp) spread the
    rows: Pallas kernels are not GSPMD-partitionable, so `shard_map` pins
    the layout — the row axis of every operand over the batch axes, nothing
    else split — and each device steps its own rows' tiles.  No
    collective: a row's state is its own."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import BATCH_AXES

    row3, row2 = P(BATCH_AXES, None, None), P(BATCH_AXES, None)
    state = P(None, BATCH_AXES, None, None, None)

    step = shard_map(
        gdn_delta_step,
        mesh=mesh,
        in_specs=(state, P(), row3, row3, row3, row2, row2),
        out_specs=(state, row3),
        check_vma=False,  # pallas_call outputs carry no vma metadata
    )
    return step(states, jnp.asarray(layer, jnp.int32), q, k, v, g, beta)

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

Grid (B, blocks of value heads).  A block is `block_h_for` heads' own
[d_k, d_v] tiles — the array's last two dimensions, so any d_k of whole
8-sublane tiles and any d_v of whole or half 128-lane tiles is a legal
block (`fits`): 128 x 128 (qwen3_next) and 96 x 192 (olmo_hybrid, PR 60)
run this one body.  A d_v of 192 lies in HBM as 256 lanes (`T(8,128)`: a
program whose one argument is `f32[3,64,30,96,192]` counts 566 MB where
the shapes count 425), so such a head moves 4/3 of its bytes — once each
way, where the `jnp` form moved them three times (PERF.md section 6,
PR 60); storing two heads side by side on the lanes would save the third,
but the benchmark's reference reads `cache.state` as [.., h_v, d_k, d_v].

k and q arrive as rows ([heads, lanes], d_k on whole lane tiles, zeros
behind a d_k of 96: in HBM the row is that wide anyway) because a
`[d_k, 1]` column pads to 128 lanes in HBM and would cost the state's
bytes again; the kernel turns a row into a column itself — the row on 128
sublanes, ONE XLU tile transpose a head and operand, cut to d_k rows —
and, every lane of a column being the same number, that one turned lane
tile serves every lane tile of the head: the body walks d_v a lane tile at
a time (128 + 64 lanes of 192).  The call is bound by its DMA, not by
these: 0.41 ms a layer at 64 rows x 32 heads of 128 x 128 in the decode
loop (653 GB/s; 8, 16 or 32 heads a grid step, the columns made once a
head block or once a head, all within 1%: chip runs, PR 42) and 0.575 ms
at 30 heads of 96 x 192 (657 GB/s over the padded bytes; 8 or 16 heads a
grid step within 0.3%, 24 heads 7% slower: chip runs, PR 60; `block_h_for`
takes 8).  Every
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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.pallas.flash_attention import _interpret, named_call

LANES = 128
SUBLANES = 8
BLOCK_BYTES = 1 << 20  # a grid step's tiles: as much in, as much out, twice


def fits(dk: int, dv: int) -> bool:
    """Whether the kernel takes a head's [d_k, d_v] tile: rows that are
    whole 8-sublane tiles (k's column is cut to them) and columns that are
    whole or half 128-lane tiles (the widths compiled for the chip and
    measured: 128, 192, 256)."""
    return dk % SUBLANES == 0 and dv % (LANES // 2) == 0


def block_h_for(hv: int, dk: int, dv: int) -> int:
    """Heads a grid step: what makes about BLOCK_BYTES of tiles as they
    lie (d_v on whole lane tiles) — 16 heads of 128 x 128 at 64 KB, 8 of
    96 x 192 at 96 KB — in whole 8-sublane tiles of the [heads, lanes]
    operands; all of them where they are fewer.  The call is bound by its
    DMA at 8, 16 or 32 heads alike (module docstring); the body unrolls the
    heads, so fewer of them trace and lower sooner."""
    head = dk * (dv + -dv % LANES) * 4
    return min(hv, max(SUBLANES, BLOCK_BYTES // head // SUBLANES * SUBLANES))


# The body is written in `lax` primitives, not `jnp` operators: a `jnp`
# operator on a traced value is a `jit` call of its own, the body unrolls
# some twenty of them a head and lane tile, and a program that holds the
# kernel paid 2.7 s of tracing for them in the benchmark's process (PERF.md
# section 6, PR 60).  The primitives bound are the same ones.


def _row(x, h: int, lo: int = 0, hi: int = 0):
    """Head `h` of a [heads, lanes] value, lanes lo..hi (0: to the end):
    a [1, w] row."""
    return lax.slice(x, (h, lo), (h + 1, hi or x.shape[1]))


def _colsum(x):
    """A tile summed over its rows, as a [1, w] row."""
    return lax.broadcast_in_dim(
        lax.reduce_sum(x, (0,)), (1, x.shape[1]), (1,))


def _column(row, dk: int):
    """A head's k or q, one [1, lanes] row, as a column on every lane of
    ONE lane tile [dk, 128]: the row on every sublane, turned (an XLU tile
    transpose, hidden behind the DMA), and cut to the tile's rows where the
    row came padded to whole lanes."""
    col = lax.transpose(
        lax.broadcast_in_dim(row, (LANES, row.shape[1]), (0, 1)), (1, 0))
    return col if col.shape[0] == dk else lax.slice(col, (0, 0), (dk, LANES))


def _delta_step_kernel(
    layer_ref,  # prefetched scalar, read by the index maps alone
    q_ref, k_ref, v_ref, eg_ref, beta_ref, s_ref,  # inputs
    s_out_ref, o_ref,  # outputs
    *, hb: int,
):
    del layer_ref
    mul, add, sub = lax.mul, lax.add, lax.sub
    dk, dv = s_ref.shape[-2:]
    k = k_ref[0]  # [hb, d_k in whole lane tiles]
    q = q_ref[0]
    v = v_ref[0]  # [hb, dv]
    eg = eg_ref[0]  # [hb, dv], a head's e^g on every lane
    beta = beta_ref[0]  # [hb, 1]
    kq = lax.broadcast_in_dim(  # [hb, 1]
        lax.reduce_sum(mul(k, q), (1,)), (hb, 1), (0,))
    for h in range(hb):
        kt = _column(_row(k, h), dk)  # [dk, 128]
        qt = _column(_row(q, h), dk)
        # The tile a lane tile at a time: every lane of a column is the
        # same, so one turned tile serves them all, the last cut to what
        # is left of d_v (64 lanes of 192).
        for lo in range(0, dv, LANES):
            hi = min(lo + LANES, dv)
            whole = hi - lo == LANES
            kc = kt if whole else lax.slice(kt, (0, 0), (dk, hi - lo))
            qc = qt if whole else lax.slice(qt, (0, 0), (dk, hi - lo))
            s = s_ref[h, :, lo:hi]  # [dk, w]
            sk = _colsum(mul(s, kc))  # [1, w]
            sq = _colsum(mul(s, qc))
            e = _row(eg, h, lo, hi)
            d = mul(_row(beta, h), sub(_row(v, h, lo, hi), mul(e, sk)))
            o_ref[0, h: h + 1, lo:hi] = add(mul(e, sq), mul(_row(kq, h), d))
            s_out_ref[h, :, lo:hi] = add(mul(s, e), mul(kc, d))


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
    hb = block_h or block_h_for(hv, dk, dv)
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
    if dk % LANES:
        # k and q as rows of whole lane tiles (zeros behind d_k = 96: in
        # HBM the row is that wide already), so that the kernel can turn
        # them.
        to_lanes = ((0, 0), (0, 0), (0, -dk % LANES))
        q, k = jnp.pad(q, to_lanes), jnp.pad(k, to_lanes)
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

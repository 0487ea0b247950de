"""The Pallas kernel of the Gated DeltaNet decode step (`ops/pallas/
delta_step.py`), interpreted on the CPU, against the `jnp` form it takes
the place of on a TPU backend (`linear_attention.delta_step_jnp`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.base.topology import BATCH_AXES, ParallelConfig, make_mesh
from areal_tpu.models import linear_attention as la
from areal_tpu.ops.pallas import delta_step
from areal_tpu.ops.pallas.flash_attention import row_kernel_form

D = 128  # a head's tile is [D, D]: whole lanes both ways
TOL = dict(rtol=2e-6, atol=2e-6)


def _operands(rows, hk, hv, n=3, seed=0, dk=D, dv=D, neg_eigval=False):
    """A stack of `n` layers' states and one token's q, k (normalised, each
    key head repeated for its value heads), v, g, beta — in (0, 1), or in
    (0, 2) with `neg_eigval` (olmo_hybrid: I - beta k k^T then flips k's
    direction in the heads whose beta passes 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    states = jax.random.normal(ks[0], (n, rows, hv, dk, dv), jnp.float32)
    q = la._l2norm(jax.random.normal(ks[1], (rows, hk, dk))) * dk**-0.5
    k = la._l2norm(jax.random.normal(ks[2], (rows, hk, dk)))
    q, k = (jnp.repeat(x, hv // hk, axis=-2) for x in (q, k))
    v = jax.random.normal(ks[3], (rows, hv, dv))
    g = -jax.random.uniform(ks[4], (rows, hv), minval=0.01, maxval=2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, hv)))
    if neg_eigval:
        beta = (2 * beta).at[:, 0].set(1.5).at[:, -1].set(0.5)
    return states, q, k, v, g, beta


def _check(got_states, got_o, states, li, args):
    want_state, want_o = la.delta_step_jnp(states[li], *args)
    assert got_states.dtype == jnp.float32 and got_o.dtype == jnp.float32
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_states[li], want_state, **TOL)
    # In place means the layer alone: every other layer's bits are the
    # input's.
    for j in range(states.shape[0]):
        if j != li:
            np.testing.assert_array_equal(got_states[j], states[j])
    # The state stays fp32: what a bf16 copy of it would lose is there.
    kept = got_states[li]
    assert float(jnp.max(jnp.abs(
        kept - kept.astype(jnp.bfloat16).astype(jnp.float32)))) > 1e-4


@pytest.mark.parametrize("rows,hk,hv,li,block_h,dk,dv,neg_eigval", [
    (1, 2, 4, 0, 0, D, D, False),  # one row, a key head serves two value heads
    (3, 4, 12, 2, 8, D, D, False),  # 8 + 4: the last block half empty
    (8, 8, 16, 1, 8, D, D, False),  # two whole blocks
    (3, 2, 2, 2, 0, D, D, True),  # no repeat, the last layer, beta in (0, 2)
    (8, 5, 20, 0, 16, D, D, False),  # blocks of 16 over 20 heads
    (1, 20, 20, 2, 8, D, D, False),  # 8 + 8 + 4
    (3, 1, 2, 1, 0, D, 2 * D, False),  # a tile that is not square
    # Heads that are no whole 128-lane tiles.  olmo_hybrid's published 30
    # heads of 96 x 192 (a lane tile and a half, k and q padded to a tile)
    # and its beta in (0, 2): a negative eigenvalue of I - beta k k^T.
    (2, 30, 30, 1, 0, 96, 192, True),  # the cell's 8 + 8 + 8 + 6, an odd layer
    (1, 30, 30, 2, 16, 96, 192, True),  # 16 + 14
    (2, 15, 30, 0, 24, 96, 192, False),  # 24 + 6, each key head serves two
    (3, 3, 3, 1, 0, 96, 192, True),  # fewer heads than a block
    (2, 2, 4, 1, 0, 16, 64, True),  # half a lane tile, 16 rows
    (1, 2, 2, 2, 0, 2 * D, 64, False),  # rows of two lane tiles
    (1, 1, 2, 0, 0, 40, 3 * D + 64, True),  # three lane tiles and a half
], ids=lambda x: str(x))
def test_the_kernel_steps_one_layer_in_place_as_the_jnp_form_does(
        rows, hk, hv, li, block_h, dk, dv, neg_eigval):
    assert delta_step.fits(dk, dv)
    states, *args = _operands(
        rows, hk, hv, seed=rows + hv, dk=dk, dv=dv, neg_eigval=neg_eigval)
    got_states, got_o = delta_step.gdn_delta_step(
        states, jnp.int32(li), *args, block_h=block_h)
    _check(got_states, got_o, states, li, args)


def test_block_sizes_and_the_widths_the_kernel_takes():
    assert delta_step.block_h_for(32, D, D) == 16  # q3next's: two blocks a row
    assert delta_step.block_h_for(12, D, D) == 12  # all of them where fewer
    # About a megabyte of tiles as they lie: olmo_hybrid's 96 x 192 takes
    # 96 KB a head on 256 lanes, so 8 + 8 + 8 + 6 of its 30.
    assert delta_step.block_h_for(30, 96, 192) == 8
    assert delta_step.block_h_for(32, 128, 256) == 8
    assert delta_step.block_h_for(4, 16, 64) == 4
    assert delta_step.fits(128, 128) and delta_step.fits(128, 256)
    # Rows in whole sublane tiles, columns in whole or half lane tiles:
    # olmo_hybrid's 96 x 192 and a 128 x 64 head, not the toys'.
    assert delta_step.fits(96, 192) and delta_step.fits(128, 64)
    assert not delta_step.fits(16, 16) and not delta_step.fits(12, 24)
    assert not delta_step.fits(100, 128)


@pytest.mark.parametrize("dk,dv", [(D, D), (96, 192)], ids=str)
def test_rows_spread_over_a_mesh_step_as_on_one_device(dk, dv):
    """Eight host devices, the rows over (data, fsdp): each device runs
    the kernel on its own rows' tiles, and the stack comes back spread the
    same way with the numbers of the one-device call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    pc = ParallelConfig.from_str("d4f2")
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    states, *args = _operands(16, 2, 4, seed=5, dk=dk, dv=dv, neg_eigval=True)
    li = 1
    want_states, want_o = delta_step.gdn_delta_step(
        states, jnp.int32(li), *args)

    def put(x, *spec):
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    placed = [put(states, None, BATCH_AXES)] + [
        put(x, BATCH_AXES) for x in args]
    got_states, got_o = jax.jit(
        lambda s, *a: delta_step.gdn_delta_step_sharded(
            s, jnp.int32(li), *a, mesh))(*placed)
    assert len(got_states.sharding.device_set) == pc.world_size
    assert got_states.sharding.spec[1] == BATCH_AXES
    np.testing.assert_array_equal(got_states, want_states)
    np.testing.assert_array_equal(got_o, want_o)
    _check(got_states, got_o, states, li, args)


@pytest.mark.parametrize("kernel,dk,dv,neg_eigval", [
    (True, D, D, False), ("mesh", D, D, False),
    (True, 96, 192, True),  # olmo_hybrid's head and its beta in (0, 2)
], ids=str)
def test_the_decode_step_takes_the_kernel_where_it_is_told_to(
        kernel, dk, dv, neg_eigval):
    """`linear_attn_step(kernel=True)` (what a TPU backend picks at these
    widths) against the `jnp` form the CPU picks, through the projections,
    the conv and the gated norm: same y, same stepped state, same tails.
    A mesh off a TPU backend picks the `jnp` form as one device does."""
    from tests.test_qwen3_next import _cfg

    cfg = _cfg(linear_k_head_dim=dk, linear_v_head_dim=dv,
               linear_neg_eigval=neg_eigval)
    hv = cfg.linear_n_v_heads
    assert cfg.linear_n_k_heads < hv  # the repeat
    fits = delta_step.fits(cfg.linear_k_head_dim, cfg.linear_v_head_dim)
    assert row_kernel_form(None, fits) == (False, None)  # this is a CPU
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    blk = jax.tree.map(lambda x: x[0], la.init_linear_attn(
        cfg, keys[0], 1,
        lambda k, shape, fan: jax.random.normal(k, shape) * fan**-0.5))
    b, n = 3, 3
    h = jax.random.normal(keys[1], (b, 1, cfg.hidden_dim))
    states = jax.random.normal(keys[2], (n, b, hv, dk, dv), jnp.float32)
    tails = jax.random.normal(
        keys[3], (n, b, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim))
    want = la.linear_attn_step(h, blk, cfg, states, tails, 2)
    if kernel == "mesh":
        kernel = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        assert row_kernel_form(kernel, fits) == (False, kernel)
        got = la.linear_attn_step(h, blk, cfg, states, tails, 2, kernel)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        return
    assert row_kernel_form(True, fits) == (True, None)
    got = la.linear_attn_step(h, blk, cfg, states, tails, 2, True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], **TOL)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[1][:2], states[:2])

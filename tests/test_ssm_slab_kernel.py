"""The Pallas kernel of the serving plane's Mamba-2 recurrence
(`ops/pallas/ssm_slab.py`: one short SSD chunk a live slot, its terms made
inside and its state stepped in place), interpreted on the CPU, against the
`jnp` form it takes the place of on a TPU backend (`mamba.ssd_slab`) — and the serving chunk's inner loop compiled for a
described v5e at the cell's size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import mamba
from areal_tpu.ops.pallas import ssm_slab

N = 128  # a head's tile is [P, N]: whole lanes
TOL = dict(rtol=2e-5, atol=2e-5)


def _slab(counts, fresh, w, h, g, p, steps=3, seed=0, n=N):
    """A stack of `steps` scan steps' states, one slab's operands as
    `ssm_ragged` makes them (dt = 0 behind a slot's lanes) and its lanes."""
    r = len(counts)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    count = jnp.asarray(counts, jnp.int32)
    valid = jnp.arange(w)[None] < count[:, None]
    x = jax.random.normal(ks[0], (r, w, h, p))
    dt = jnp.where(
        valid[..., None],
        jax.nn.softplus(jax.random.normal(ks[1], (r, w, h))), 0.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (r, w, g, n))
    cm = jax.random.normal(ks[4], (r, w, g, n))
    states = jax.random.normal(ks[5], (steps, r, h, p, n), jnp.float32)
    # The stream is every lane of the slab, in slab order: y comes back
    # as [R * W, H, P].
    lanes = mamba.SlotLanes(
        None, valid, count, jnp.asarray(fresh),
        jnp.repeat(jnp.arange(r), w), jnp.tile(jnp.arange(w), r),
        *ssm_slab.live_slots(count))
    return (x, dt, a, bm, cm), states, lanes


def _rows(x, bm, cm):
    """x | B | C as the conv leaves them: [R, W, H P + 2 G N]."""
    r, w = x.shape[:2]
    return jnp.concatenate(
        [v.reshape(r, w, -1) for v in (x, bm, cm)], axis=-1)


def _skip(h, seed=0):
    """D [H], far from one and from zero."""
    return 0.5 + jax.random.uniform(jax.random.PRNGKey(100 + seed), (h,))


def _in_place(ops, states, li, lanes, block_h=0, d=None):
    """-> (y [T, H, P], states); `d` None: no skip (D = 0)."""
    x, dt, a, bm, cm = ops
    h, p = x.shape[2:]
    y, states = mamba.ssd_slab_in_place(
        _rows(x, bm, cm), dt, a, jnp.zeros((h,)) if d is None else d,
        states, li, lanes, block_h=block_h)
    return y.reshape(-1, h, p), states


def _check(ops, states, lanes, li, block_h=0):
    got_y, got_states = _in_place(ops, states, li, lanes, block_h)
    got_y = got_y.reshape(ops[0].shape)
    want_y, want_state = mamba.ssd_slab(
        *ops, states[li], 1.0 - lanes.fresh.astype(jnp.float32))
    assert got_states.dtype == jnp.float32 and got_y.dtype == jnp.float32
    held = np.asarray(lanes.count) > 0
    live = np.asarray(lanes.valid)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(
        np.asarray(got_y)[live] / scale, np.asarray(want_y)[live] / scale,
        **TOL)
    np.testing.assert_allclose(
        np.asarray(got_states[li])[held], np.asarray(want_state)[held], **TOL)
    # A slot with no lane is never touched: its bits are the input's.
    np.testing.assert_array_equal(
        np.asarray(got_states[li])[~held], np.asarray(states[li])[~held])
    # In place means the step alone: every other step's bits are the input's.
    for j in range(states.shape[0]):
        if j != li:
            np.testing.assert_array_equal(got_states[j], states[j])
    # The state stays fp32: what a bf16 copy of it would lose is there.
    if held.any():
        kept = got_states[li][held]
        assert float(jnp.max(jnp.abs(
            kept - kept.astype(jnp.bfloat16).astype(jnp.float32)))) > 1e-4
    return got_y, got_states


@pytest.mark.parametrize("counts,fresh,w,h,g,p,li,block_h", [
    # every slot one lane (all decoding), the heads in two blocks
    ((1, 1, 1), (False,) * 3, 4, 4, 1, 64, 0, 2),
    # every slot its W lanes (all prefilling)
    ((4, 4), (True, False), 4, 4, 1, 64, 1, 0),
    # a partial count, one lane, W lanes and two slots with no lane
    ((2, 0, 1, 4, 0), (False,) * 5, 4, 4, 1, 64, 2, 0),
    # a fresh slot beside a carried one: the old state is not seen
    ((3, 3), (True, False), 4, 2, 1, 64, 0, 0),
    # the first and the last slot hold no lane
    ((0, 1, 2, 0), (False, True, False, False), 2, 2, 1, 64, 1, 0),
    # blocks of 4 over 6 heads: the last is half empty
    ((1, 0, 3), (False, False, True), 3, 6, 1, 64, 2, 4),
    # two groups, a block a group; and two blocks a group
    ((1, 4, 0, 2), (False,) * 4, 4, 4, 2, 64, 0, 0),
    ((1, 4, 0, 2), (True, False, False, False), 4, 8, 2, 64, 1, 2),
    # heads of 128 channels, W = 8 (the cell's), one block
    ((8, 1, 0), (False,) * 3, 8, 2, 1, 128, 0, 0),
    # heads of 16 channels: 8 heads fill the lanes
    ((1, 2), (False, True), 2, 8, 1, 16, 0, 0),
], ids=lambda x: str(x).replace(" ", ""))
def test_the_kernel_steps_the_live_slots_in_place_as_the_jnp_form_does(
        counts, fresh, w, h, g, p, li, block_h):
    ops, states, lanes = _slab(counts, fresh, w, h, g, p, seed=len(counts) + h)
    _check(ops, states, lanes, li, block_h)


@pytest.mark.parametrize("counts,fresh,w,h,g,p,block_h", [
    # one lane, three and W in one slab, all carried; the cell's W
    ((1, 3, 8), (False,) * 3, 8, 2, 1, 64, 0),
    # the same lane counts, a fresh slot among carried ones
    ((3, 1, 8, 1), (False, True, False, False), 8, 2, 1, 64, 0),
    # every live slot fresh, slots with no lane around and between them
    ((0, 8, 0, 1, 3, 0), (False, True, False, True, True, False),
     8, 2, 1, 64, 0),
    # two groups: a block reads its own group's B and C
    ((1, 3, 4, 0), (False, False, True, False), 4, 4, 2, 64, 0),
    ((4, 0, 1, 3), (True, False, False, False), 4, 8, 2, 64, 2),
    # block_h at its smallest (one head of 128 channels, two of 64) and
    # at its largest (every head)
    ((1, 0, 3, 8), (False,) * 4, 8, 4, 1, 128, 1),
    ((1, 0, 3, 4), (False, False, True, False), 4, 8, 1, 64, 2),
    ((1, 0, 3, 4), (False, False, True, False), 4, 8, 1, 64, 8),
], ids=lambda x: str(x).replace(" ", ""))
def test_the_kernel_makes_the_chunks_terms_for_the_live_slots_alone(
        counts, fresh, w, h, g, p, block_h):
    """`ssm_slab_step` itself, from the operands as they are gathered to
    the slab (x, dt, A, B, C, D and the fresh flags: no `slab_terms`),
    against `ssd_slab` + D x: the finished y of every lane a slot holds and
    the state it leaves.  A slot with no lane is poisoned with NaN in every operand and
    in its state: it is never read, its state keeps its bits, and no live
    slot's numbers see it."""
    (x, dt, a, bm, cm), states, lanes = _slab(
        counts, fresh, w, h, g, p, seed=sum(counts) + h)
    r, li = len(counts), 1
    held = np.asarray(counts) > 0
    carried = 1.0 - lanes.fresh.astype(jnp.float32)
    want_y, want_state = mamba.ssd_slab(x, dt, a, bm, cm, states[li], carried)
    d = _skip(h, seed=r)
    want_y = want_y + d[:, None] * x  # the skip is the kernel's too
    nan = jnp.asarray(~held)

    def poisoned(v):
        return jnp.where(nan.reshape((r,) + (1,) * (v.ndim - 1)), jnp.nan, v)

    states = states.at[li].set(poisoned(states[li]))
    got_states, got_y = ssm_slab.ssm_slab_step(
        states, li, lanes.live, lanes.n_live, poisoned(_rows(x, bm, cm)),
        poisoned(dt), a, d, carried, block_h=block_h)
    live = np.asarray(lanes.valid)
    got_y = np.asarray(got_y).reshape(r, w, h, p)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(
        got_y[live] / scale, np.asarray(want_y)[live] / scale, **TOL)
    np.testing.assert_allclose(
        np.asarray(got_states[li])[held], np.asarray(want_state)[held], **TOL)
    assert np.isnan(np.asarray(got_states[li])[~held]).all()
    for j in (0, 2):
        np.testing.assert_array_equal(got_states[j], states[j])


def test_the_stream_never_reads_the_rows_a_dead_lane_maps_to(monkeypatch):
    """A dead lane of the stream is clipped onto some slot's row of the
    slab's y, written or not: with the rows the kernel did not write (a
    slot with no lane) poisoned with NaN, the stream's y is the live
    lanes' numbers and zero at every dead lane."""
    ops, states, lanes = _slab((3, 0, 1, 0), (False,) * 4, 4, 2, 1, 64, seed=5)
    step = ssm_slab.ssm_slab_step

    def poisoned_step(*args, **kw):
        new, y = step(*args, **kw)
        return new, jnp.where(
            (lanes.count > 0)[:, None, None], y, jnp.nan)

    want, _ = _in_place(ops, states, 0, lanes)
    monkeypatch.setattr(ssm_slab, "ssm_slab_step", poisoned_step)
    got, _ = _in_place(ops, states, 0, lanes)
    np.testing.assert_array_equal(got, want)
    dead = ~np.asarray(lanes.valid).reshape(-1)
    assert dead.sum() == 12 and not np.asarray(got)[dead].any()


def test_a_fresh_slot_ignores_the_state_it_holds():
    """Two runs that differ in a fresh slot's old state alone leave the
    same new state and y there (and the other slot's as they were)."""
    ops, states, lanes = _slab((2, 3), (True, False), 4, 2, 1, 64, seed=3)
    _, got = _check(ops, states, lanes, 1)
    other = states.at[1, 0].set(7.0 * states[1, 0] + 1.0)
    _, got2 = _in_place(ops, other, 1, lanes)
    np.testing.assert_array_equal(got2[1], got[1])


def test_a_step_with_no_live_slot_changes_nothing():
    ops, states, lanes = _slab((0, 0, 0), (False,) * 3, 4, 4, 1, 64, seed=4)
    assert int(lanes.n_live) == 0
    y, got = _in_place(ops, states, 1, lanes)
    np.testing.assert_array_equal(got, states)
    assert not np.asarray(y).any()


def test_the_work_list_names_the_live_slots_first_and_repeats_the_last():
    live, n = ssm_slab.live_slots(jnp.asarray([0, 2, 0, 1, 1, 0], jnp.int32))
    assert int(n) == 3 and live.tolist() == [1, 3, 4, 4, 4, 4]
    live, n = ssm_slab.live_slots(jnp.zeros((4,), jnp.int32))
    assert int(n) == 0 and live.tolist() == [0, 0, 0, 0]
    live, n = ssm_slab.live_slots(jnp.ones((3,), jnp.int32))
    assert int(n) == 3 and live.tolist() == [0, 1, 2]


def test_block_sizes_and_the_widths_the_kernel_takes():
    assert ssm_slab.block_h_for(64, 1, 64) == 32  # the cell's: two a slot
    assert ssm_slab.block_h_for(6, 1, 64) == 6
    assert ssm_slab.block_h_for(8, 2, 64) == 4  # a block within a group
    assert ssm_slab.block_h_for(8, 1, 16) == 8  # 8 heads fill the lanes
    # A block of x is a run of whole lane tiles of the conv's rows:
    assert ssm_slab.block_h_for(4, 1, 16) == 0
    assert ssm_slab.block_h_for(4, 2, 16) == 0
    assert ssm_slab.fits(64, 1, 64, 128) and ssm_slab.fits(128, 8, 64, 128)
    assert ssm_slab.fits(8, 1, 16, 128) and not ssm_slab.fits(4, 1, 16, 128)
    # B and C lie behind x at whole N-column blocks of the rows
    assert not ssm_slab.fits(2, 1, 64, 256) and ssm_slab.fits(4, 1, 64, 256)
    assert not ssm_slab.fits(4, 1, 16, 16)  # the toy's N
    assert not ssm_slab.fits(4, 1, 12, 128)  # P is not whole sublanes
    assert not ssm_slab.fits(4, 2, 16, 128)


@pytest.mark.parametrize("kernel,n", [
    (True, N), (False, N), (None, N), (True, 16)],
    ids=["forced", "refused", "the_cpu_picks", "does_not_fit"])
def test_ssm_ragged_takes_the_kernel_where_it_is_told_to_and_it_fits(
        kernel, n, monkeypatch):
    """`ssm_ragged(kernel=True)` (what a TPU backend picks at whole-tile
    widths) against the `jnp` form the CPU picks, through the projections,
    the conv and the gated norm: same y, same stepped state, same tails,
    a slot with no lane bit-identical.  Off a TPU `None` is the `jnp`
    form, and so is a forced kernel at widths that do not fit."""
    from areal_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        n_layers=1, hidden_dim=32, n_q_heads=2, n_kv_heads=2, head_dim=16,
        intermediate_dim=64, vocab_size=64, window_pattern="M",
        ssm_n_heads=8, ssm_head_dim=16, ssm_state_dim=n, pos_emb="none",
        param_dtype="float32")
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    blk = jax.tree.map(lambda v: v[0], mamba.init_ssm(
        cfg, keys[0], 1,
        lambda k, shape, fan: jax.random.normal(k, shape) * fan**-0.5))
    slots, t, w = 4, 10, 4
    # Slot 0 decodes, slot 2 prefills 4 lanes from position 0, slot 3 two
    # lanes; slot 1 holds none; three dead lanes.
    row_of = jnp.asarray([0, 2, 2, 2, 2, 3, 3, 4, 4, 4], jnp.int32)
    pos = jnp.asarray([5, 0, 1, 2, 3, 7, 8, 0, 0, 0], jnp.int32)
    lanes = mamba.slot_lanes_of(row_of, pos, slots, w)
    assert lanes.live.tolist() == [0, 2, 3, 3] and int(lanes.n_live) == 3
    h = jax.random.normal(keys[1], (t, cfg.hidden_dim))
    states = jax.random.normal(
        keys[2], (2, slots, 8, 16, n), jnp.float32)
    tails = jax.random.normal(
        keys[3], (2, slots, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim))
    calls = []
    step = ssm_slab.ssm_slab_step
    monkeypatch.setattr(
        ssm_slab, "ssm_slab_step",
        lambda *a, **kw: calls.append(1) or step(*a, **kw))
    want = mamba.ssm_ragged(h, blk, cfg, states, tails, 1, lanes, False)
    assert not calls
    got = mamba.ssm_ragged(h, blk, cfg, states, tails, 1, lanes, kernel)
    assert len(calls) == (1 if kernel and n == N else 0)
    if not calls:
        for g, w_ in zip(got, want):
            np.testing.assert_array_equal(g, w_)
        return
    np.testing.assert_allclose(got[0][:7], want[0][:7], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], **TOL)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[1][0], states[0])
    np.testing.assert_array_equal(got[1][1, 1], states[1, 1])


# ------------------------------------------- compiled for a described v5e


def test_the_serving_loop_compiles_for_v5e_with_the_state_stepped_in_place(
        v5e_chips, monkeypatch):
    """Mosaic and XLA:TPU for real, the serving chunk's inner loop at the
    size of `granite4hm-serving-waves` (64 slots, 96 lanes, W = 8, the
    published widths): each of the nine Mamba layers steps its buffer
    `f32[1, 64, 64, 64, 128]` on `ssm_slab_step` under `layer/ssm/
    ssm_ragged/ssd_scan`, no other operation of the loop makes or moves an
    array of a layer's state (no slice, copy, select or
    `dynamic-update-slice`), and the nine calls share ONE lowered kernel
    body (`setup_s`: a body is traced and lowered once a program)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import transformer as tfm
    from benchmark import files
    from benchmark import run as bench_run

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(
        files.load_json("configs", "granite-4.0-h-micro-l10.json"))
    chip = SingleDeviceSharding(v5e_chips[0])
    slots, t, w, page, max_pages = 64, 96, 8, 128, 4

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree.map(placed, jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0))))
    pool = jax.tree.map(placed, jax.eval_shape(
        lambda: tfm.init_paged_kv_cache(
            big, slots * max_pages, page, dtype=jnp.bfloat16, n_slots=slots)))

    def i32(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    def loop(params, pool, tok, pos, table, row_of):
        def body(i, st):
            pool, tok = st
            logits, pool = tfm.decode_step_ragged_paged(
                params, big, tok, pos + i, pool, table, row_of, slot_lanes=w)
            return pool, jnp.argmax(logits, -1).astype(jnp.int32)

        return jax.lax.fori_loop(0, 32, body, (pool, tok))

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(loop, donate_argnums=(1,)).lower(
            params, pool, i32(t), i32(t), i32(slots, max_pages), i32(t))
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    n = big.n_ssm_layers
    assert n == 9
    buffer, layer = "f32[1,64,64,64,128]", "f32[64,64,64,128]"
    calls = [line for line in text.splitlines()
             if "%ssm_slab_step" in line.split(" = ")[0]]
    assert len(calls) == n, len(calls)
    for line in calls:
        assert "tpu_custom_call" in line and buffer in line.split(" = ")[1]
        scope = line.split('op_name="')[1].split('"')[0]
        assert "gen/decode_step/layer/ssm/ssm_ragged/ssd_scan/" in scope
    passes = [
        line.strip()[:160] for line in text.splitlines()
        if " = " in line
        and any(s in line.split(" = ")[1].split("(")[0] for s in (buffer, layer))
        and any(op in line for op in (
            " fusion(", " copy(", " select(", " dynamic-update-slice(",
            " dynamic-slice(", " convolution("))
    ]
    assert not passes, passes[:3]
    # One kernel body for the nine layers: the calls go through one `jit`.
    stablehlo = lowered.as_text()
    bodies = [line for line in stablehlo.splitlines()
              if "func.func" in line and "@_slab_step" in line]
    assert len(bodies) == 1, bodies
    assert stablehlo.count("call @_slab_step(") == n
    assert stablehlo.count("tpu_custom_call") == 2  # and the paged attention

"""The Pallas chunk sweep of Mamba-2's SSD recurrence in training
(`ops/pallas/ssd_chunk.py`), interpreted on the CPU at toy lengths and the
cells' own widths (heads of 64 channels, a state of 128 columns, chunks of
128), against the `jnp` form it takes the place of on a TPU backend
(`mamba.ssd_chunked`) with that form's `jax.grad`, and against a float64
recurrence run token by token: y and all five gradients."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import mamba
from areal_tpu.models import transformer as tfm
from areal_tpu.ops.pallas import ssd_chunk

P, N, CHUNK = 64, 128, 128
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC")
# fp32 operands both sides: what is left is the order of the sums.
TOL = dict(rtol=2e-4, atol=2e-6)


def _segments(*rows):
    """Rows of (segment id, length) runs -> [B, S] int32."""
    return jnp.asarray(np.stack([
        np.concatenate([np.full(n, i) for i, n in row]) for row in rows
    ]).astype(np.int32))


def _operands(seg, h, g, seed=0):
    """As the mixer makes them: dt after its softplus and zero on pads, A
    negative, and the weights of the sum that is differentiated."""
    b, s = seg.shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    dt = jnp.where((seg > 0)[..., None], dt, 0.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    bm = 0.3 * jax.random.normal(ks[3], (b, s, g, N))
    cm = 0.3 * jax.random.normal(ks[4], (b, s, g, N))
    weights = jax.random.normal(ks[5], (b, s, h, P))
    return (x, dt, a, bm, cm), weights


def _y_and_grads(rule, ops, weights):
    def loss(*ops):
        y = rule(*ops)
        return jnp.sum(y * weights), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*ops)
    return (y, *grads)


def _oracle(seg):
    fill = mamba._fill_pads(seg)
    return lambda *ops: mamba.ssd_chunked(*ops, fill, CHUNK)[0]


def _kernel(seg, d=None, **kw):
    """The sweep on the oracle's operands: x | B | C side by side as the
    conv leaves them, the skip (`d`: [H]) zero unless given."""
    kw.setdefault("operands", jnp.float32)
    fill = mamba._fill_pads(seg)

    def rule(x, dt, a, bm, cm):
        b, s = seg.shape
        conv = jnp.concatenate(
            [v.reshape(b, s, -1) for v in (x, bm, cm)], axis=-1)
        skip = jnp.zeros(a.shape, jnp.float32) if d is None else d
        return ssd_chunk.ssd_chunk(
            conv, dt, a, skip, fill, CHUNK, x.shape[-1], bm.shape[2],
            **kw).reshape(x.shape)

    return rule


def _recurrence(seg):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t, one
    token at a time, S dropped where a segment starts (a pad counts to the
    token before it)."""
    fill = mamba._fill_pads(seg)
    start = jnp.concatenate(
        [jnp.ones_like(fill[:, :1], bool), fill[:, 1:] != fill[:, :-1]], axis=1)

    def rule(x, dt, a, bm, cm):
        b, s, h, p = x.shape
        g, n = bm.shape[2:]
        bm, cm = (jnp.repeat(v, h // g, axis=2) for v in (bm, cm))

        def step(state, t):
            xt, dtt, bt, ct, st = t
            state = jnp.where(st[:, None, None, None], 0.0, state)
            state = state * jnp.exp(dtt * a)[..., None, None] + (
                (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
            return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

        _, y = jax.lax.scan(
            step, jnp.zeros((b, h, p, n), x.dtype),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm, start)))
        return jnp.moveaxis(y, 0, 1)

    return rule


def _float64(seg, ops, weights):
    with jax.enable_x64(True):
        got = _y_and_grads(
            _recurrence(seg), tuple(jnp.asarray(np.asarray(v), jnp.float64)
                                    for v in ops),
            jnp.asarray(np.asarray(weights), jnp.float64))
        return [np.asarray(v) for v in got]


def _assert_close(got, want, tol=TOL):
    for name, x, y in zip(NAMES, got, want):
        assert x.dtype == jnp.float32 and x.shape == y.shape, name
        scale = float(np.max(np.abs(y)))
        np.testing.assert_allclose(
            x, y, rtol=tol["rtol"], atol=tol["atol"] + tol["rtol"] * scale,
            err_msg=name)


def _far(got, want):
    """The largest distance of each result, in units of its largest entry."""
    return np.asarray([
        np.max(np.abs(np.asarray(x, np.float64) - y)) / np.max(np.abs(y))
        for x, y in zip(got, want)])


CASES = {
    # 140 | 116 | 128: the second segment starts twelve tokens into chunk two
    "a_segment_starts_inside_a_chunk":
        (_segments([(1, 140), (2, 116), (3, 128)]), 8, 1),
    # 128 | 256: the second starts on chunk two's first token
    "a_segment_starts_on_a_chunks_first_token":
        (_segments([(1, 128), (2, 256)]), 8, 1),
    # five chunks and a half of ONE segment: the carried state matters
    "a_row_of_one_segment_longer_than_four_chunks":
        (_segments([(1, 700)]), 8, 2),
    # pads in front, and behind over a chunk's edge: the state passes
    # through them and every pad reads it
    "leading_and_trailing_pads":
        (_segments([(0, 9), (1, 170), (2, 50), (0, 155)]), 8, 1),
    # 300 tokens: the last chunk is 44 real tokens and 84 neutral ones
    "a_length_that_is_not_whole_chunks":
        (_segments([(1, 200), (2, 100)]), 8, 2),
    "one_group": (_segments([(1, 140), (2, 116)]), 16, 1),
    "two_groups_of_a_trip_each": (_segments([(1, 140), (2, 116)]), 16, 2),
    "two_groups_in_one_trip": (_segments([(1, 140), (2, 116)]), 8, 2),
    "two_rows": (_segments([(1, 140), (2, 100), (0, 16)],
                           [(1, 128), (2, 128)]), 8, 2),
}


@pytest.mark.parametrize("case", list(CASES), ids=str)
def test_y_and_the_five_gradients_are_the_jnp_forms(case):
    seg, h, g = CASES[case]
    ops, weights = _operands(seg, h, g, seed=len(case))
    want = _y_and_grads(_oracle(seg), ops, weights)
    got = _y_and_grads(_kernel(seg), ops, weights)
    _assert_close(got, want)


@pytest.mark.parametrize("case", [
    "a_segment_starts_inside_a_chunk",
    "a_row_of_one_segment_longer_than_four_chunks",
    "leading_and_trailing_pads", "two_groups_in_one_trip"], ids=str)
def test_against_a_float64_recurrence_token_by_token(case):
    """On fp32 operands the sweep is no further from float64 than the
    `jnp` form is (half as far again at most, or the order of the sums)."""
    seg, h, g = CASES[case]
    ops, weights = _operands(seg, h, g, seed=len(case))
    want = _float64(seg, ops, weights)
    ours = _far(_y_and_grads(_kernel(seg), ops, weights), want)
    jnps = _far(_y_and_grads(_oracle(seg), ops, weights), want)
    assert np.all(ours <= np.maximum(1.5 * jnps, 1e-5)), (ours, jnps)
    assert np.all(ours < 1e-4), ours


@jax.custom_vjp
def _round_in(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_round_in.defvjp(lambda x: (_round_in(x), None), lambda _, ct: (ct,))


@jax.custom_vjp
def _round_back(y):
    return y


_round_back.defvjp(lambda y: (y, None), lambda _, ct: (_round_in(ct),))


def _einsum_as_a_tpu_lowers_it(spec, *xs, **kw):
    """A product's operands rounded to bf16, its sums fp32 — in the
    backward's products too (the cotangent on its way in)."""
    return _round_back(_EINSUM(spec, *(_round_in(v) for v in xs), **kw))


_EINSUM = jnp.einsum


@pytest.mark.parametrize("case", [
    "a_segment_starts_inside_a_chunk",
    "a_row_of_one_segment_longer_than_four_chunks",
    "two_groups_in_one_trip"], ids=str)
def test_bf16_operands_are_no_further_from_float64_than_the_jnp_forms(
        case, monkeypatch):
    """What a TPU runs: every product on bf16 operands with fp32 sums, as
    XLA lowers the `jnp` form's there.  The `jnp` form with its products'
    operands rounded the same way, forward and backward, is the yardstick:
    y and each gradient — dA, the sum of running sums in which roundings
    pile up, among them — are no further from the float64 recurrence than
    that form's."""
    seg, h, g = CASES[case]
    ops, weights = _operands(seg, h, g, seed=11)
    want = _float64(seg, ops, weights)
    got = _y_and_grads(_kernel(seg, operands=jnp.bfloat16), ops, weights)
    monkeypatch.setattr(mamba.jnp, "einsum", _einsum_as_a_tpu_lowers_it)
    yard = _y_and_grads(_oracle(seg), ops, weights)
    monkeypatch.undo()
    ours, jnps = _far(got, want), _far(yard, want)
    assert np.all(ours <= 1.2 * jnps), (ours, jnps)
    assert np.all(ours < 3e-2) and ours.max() > 1e-4  # bf16 was there


def test_the_skip_inside_the_sweep_is_the_skip_around_the_jnp_form():
    """y + D x and the gradients of x and D with the skip added by the
    kernels against the `jnp` form with the skip around it."""
    seg, h, g = CASES["two_groups_of_a_trip_each"]
    ops, weights = _operands(seg, h, g, seed=3)
    d = jax.random.normal(jax.random.PRNGKey(9), (h,))
    fill = mamba._fill_pads(seg)

    def want(d, *ops):
        return mamba.ssd_chunked(*ops, fill, CHUNK)[0] + (
            d[:, None] * ops[0])

    def got(d, *ops):
        return _kernel(seg, d)(*ops)

    def both(rule):
        def loss(d, *ops):
            y = rule(d, *ops)
            return jnp.sum(y * weights), y

        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(d, *ops)
        return (y, *grads)

    for name, x, y in zip(("y", "dD", "dx", "ddt"), both(got), both(want)):
        scale = float(jnp.max(jnp.abs(y)))
        np.testing.assert_allclose(
            x, y, rtol=TOL["rtol"], atol=TOL["atol"] + TOL["rtol"] * scale,
            err_msg=name)


def test_the_widths_the_kernel_takes():
    assert ssd_chunk.fits(64, 8, 64, 128, 128)  # nemo3n
    assert ssd_chunk.fits(64, 1, 64, 128, 128)  # granite
    assert ssd_chunk.fits(8, 2, 64, 128, 256) and ssd_chunk.fits(
        8, 1, 128, 256, 128)
    assert not ssd_chunk.fits(4, 1, 16, 16, 8)  # the toys
    assert not ssd_chunk.fits(64, 8, 64, 128, 64)  # a chunk of half a tile
    assert not ssd_chunk.fits(64, 8, 64, 16, 128)
    assert not ssd_chunk.fits(12, 1, 64, 128, 128)  # no whole trips
    assert not ssd_chunk.fits(8, 8, 64, 128, 128)  # a group of half a tile
    assert not ssd_chunk.fits(8, 1, 64, 384, 128)  # C no block behind x
    assert not ssd_chunk.fits(256, 8, 64, 128, 128)  # x over the VMEM's room
    with pytest.raises(AssertionError):
        seg = _segments([(1, 128)])
        _kernel(seg)(*_operands(seg, 4, 1)[0])


# ------------------------------------------------ which form the mixer takes


def _mixer(**changes):
    from tests.test_nemotron_h import _cfg, _params

    cfg = _cfg(**{**dict(
        ssm_n_heads=8, ssm_head_dim=P, ssm_state_dim=N, ssm_n_groups=2,
        ssm_chunk=CHUNK), **changes})
    params = _params(cfg)
    blk = {k: v[0] for k, v in params["blocks"].items()
           if k in mamba.SSM_LEAVES}
    seg = _segments([(1, 140), (2, 100), (0, 16)])
    h = jax.random.normal(
        jax.random.PRNGKey(2), (1, seg.shape[1], cfg.hidden_dim))
    return cfg, blk, h, seg


def _fits(cfg):
    return ssd_chunk.fits(cfg.ssm_n_heads, cfg.ssm_n_groups, cfg.ssm_head_dim,
                          cfg.ssm_state_dim, cfg.ssm_chunk)


def _d2_mesh():
    from areal_tpu.base.topology import ParallelConfig, make_mesh

    pc = ParallelConfig.from_str("d2")
    return make_mesh(pc, jax.devices()[: pc.world_size])


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the Pallas sweep was called")

    monkeypatch.setattr(ssd_chunk, "ssd_chunk", refuse)


def test_the_form_is_decided_by_what_the_code_can_see(monkeypatch):
    cfg, *_ = _mixer()
    toy, *_ = _mixer(ssm_head_dim=16, ssm_state_dim=16, ssm_chunk=16)
    assert _fits(cfg) and not _fits(toy)
    assert not mamba.ssd_kernel_form(cfg)  # a CPU backend
    assert mamba.ssd_kernel_form(cfg, True)  # forced: interpreted
    assert not mamba.ssd_kernel_form(cfg, True, with_state=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mamba.ssd_kernel_form(cfg)
    assert not mamba.ssd_kernel_form(toy)  # no whole tiles
    assert not mamba.ssd_kernel_form(cfg, _d2_mesh())  # one device's program
    assert not mamba.ssd_kernel_form(cfg, with_state=True)  # prefill
    assert not mamba.ssd_kernel_form(cfg, False)


def test_the_counter_says_which_form_the_chunks_took(monkeypatch):
    cfg, _, _, seg = _mixer()
    stats = mamba.BRANCH.train_stats(cfg, 4, seg, None)
    assert float(stats["ssm/chunks"]) == 4 * 2
    assert float(stats["ssm/chunks_on_kernel"]) == 0  # a CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on = mamba.BRANCH.train_stats(cfg, 4, seg, None)
    assert float(on["ssm/chunks_on_kernel"]) == float(on["ssm/chunks"]) == 8
    mesh = mamba.BRANCH.train_stats(cfg, 4, seg, _d2_mesh())
    assert float(mesh["ssm/chunks_on_kernel"]) == 0


def test_the_mixer_takes_the_jnp_form_on_a_cpu_backend(no_kernel):
    cfg, blk, h, seg = _mixer()
    y = mamba.ssm_forward(h, blk, cfg, seg)
    assert y.shape == h.shape and bool(jnp.all(jnp.isfinite(y)))


def test_the_mixer_keeps_the_jnp_form_under_with_state_and_on_a_mesh(
        no_kernel):
    """Prefill reads the final state: it keeps `ssd_chunked` even where the
    kernel is forced; a mesh keeps it whatever the backend."""
    cfg, blk, h, seg = _mixer()
    y, state, tail = mamba.ssm_forward(
        h, blk, cfg, seg, with_state=True, kernel=True)
    assert state.shape == (1, 8, P, N) and state.dtype == jnp.float32
    mamba.ssm_forward(h, blk, cfg, seg, kernel=_d2_mesh())


@pytest.mark.parametrize("groups", [1, 2])
def test_the_mixer_on_the_forced_kernel_is_the_mixer_on_the_jnp_form(
        groups, monkeypatch):
    """`ssm_forward(kernel=True)` (interpreted) against the `jnp` form: the
    output and the gradient of every leaf — A_log, dt_bias and D among them
    — inside fp32's bounds on fp32 operands, and within what bf16 operands
    move on the operands a TPU runs."""
    cfg, blk, h, seg = _mixer(ssm_n_groups=groups)

    def loss(blk, h, kernel):
        y = mamba.ssm_forward(h, blk, cfg, seg, kernel=kernel)
        return jnp.sum(jnp.sin(y)), y

    run = jax.value_and_grad(loss, (0, 1), has_aux=True)
    (_, y0), g0 = run(blk, h, False)
    (_, y1), g1 = run(blk, h, True)
    np.testing.assert_allclose(y1, y0, rtol=3e-2, atol=3e-2 * float(
        jnp.max(jnp.abs(y0))))
    for x, y in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            x, y, rtol=5e-2, atol=5e-2 * float(jnp.max(jnp.abs(y))))
    sweep = ssd_chunk.ssd_chunk
    monkeypatch.setattr(ssd_chunk, "ssd_chunk", functools.partial(
        sweep, operands=jnp.float32))
    (_, y2), g2 = run(blk, h, True)
    np.testing.assert_allclose(y2, y0, rtol=2e-4, atol=2e-4 * float(
        jnp.max(jnp.abs(y0))))
    for (path, x), y in zip(jax.tree.leaves_with_path(g2), jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            x, y, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(y))),
            err_msg=str(path))


def test_a_gradient_program_binds_one_traced_sweep_for_every_layer(
        monkeypatch):
    """Four Mamba layers, one `jit` entry point: the kernel bodies are
    traced once a FORM for the four call sites (a bare `pallas_call` is
    traced, and its body lowered, at every site).  That the kernels sit
    under the mixer's `ssd_scan` scope is held on the compiled program
    (`tests/test_granite_hybrid.py`)."""
    cfg, _, _, _ = _mixer()
    from tests.test_nemotron_h import _params

    params = _params(cfg)
    seg = _segments([(1, 100), (2, 284)])  # a length no other test traces
    tokens = jnp.zeros(seg.shape, jnp.int32)
    traced = {"fwd": 0, "bwd": 0}

    def counting(name):
        body = getattr(ssd_chunk, f"_{name}_kernel")

        def kernel(*a, **kw):
            traced[name] += 1
            return body(*a, **kw)

        monkeypatch.setattr(ssd_chunk, f"_{name}_kernel", kernel)

    counting("fwd")
    counting("bwd")

    def loss(p):
        x, _ = tfm.hidden_states(p, cfg, tokens, seg, row_kernel=True)
        return jnp.sum(x)

    jax.jit(jax.grad(loss)).lower(params)
    assert cfg.n_ssm_layers == 4
    # the forward once without residuals (the rule as called) and once with
    # (the rule's forward pass), whatever the number of layers
    assert traced == {"fwd": 2, "bwd": 1}


def train_step_on_the_sweep_is_the_jnp_forms(cfg, params, monkeypatch,
                                              jit=False):
    """A toy model's sum of next-token log-probs and the gradient of every
    leaf with the chunked scan on `ssd_chunk` (forced: interpreted, its
    products on fp32 operands as the CPU's `jnp` form has them) against
    `ssd_chunked`, inside the fp32 bounds of the families' test files
    (`tests/test_nemotron_h.py`, `tests/test_granite_hybrid.py`: their
    case of this); the sweep is called once a Mamba layer."""
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 300)), jnp.int32)
    seg = _segments([(1, 140), (2, 100), (3, 50), (0, 10)])
    calls = []
    sweep = ssd_chunk.ssd_chunk

    def fp32_sweep(*a, **kw):
        calls.append(1)
        return sweep(*a, **kw, operands=jnp.float32)

    monkeypatch.setattr(ssd_chunk, "ssd_chunk", fp32_sweep)

    def loss(p, kernel):
        x, _ = tfm.hidden_states(p, cfg, tokens, seg, row_kernel=kernel)
        lp = jax.nn.log_softmax(tfm._head(p, cfg, x)[0, :-1], axis=-1)
        lp = jnp.take_along_axis(lp, tokens[0, 1:, None], axis=-1)[:, 0]
        return jnp.sum(lp * (seg[0, 1:] > 0))

    run = jax.value_and_grad(loss)
    if jit:  # a file that keeps its compiles few
        run = jax.jit(run, static_argnums=1)
    want, g_want = run(params, False)
    assert not calls
    got, g_got = run(params, True)
    assert len(calls) == cfg.n_ssm_layers
    np.testing.assert_allclose(got, want, rtol=5e-4)
    for (path, x), y in zip(
            jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)):
        scale = float(jnp.abs(y).max())
        if not scale:  # the router's choice bias takes no gradient
            assert not np.asarray(x).any(), path
            continue
        np.testing.assert_allclose(
            np.asarray(x) / scale, np.asarray(y) / scale, atol=2e-3,
            err_msg=str(path))
    return seg

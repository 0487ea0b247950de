"""The Pallas chunk sweep of the Gated DeltaNet's delta rule in training
(`ops/pallas/delta_chunk.py`), interpreted on the CPU at toy lengths and
whole 128-lane heads, against the `jnp` form it takes the place of on a TPU
backend (`linear_attention.gated_delta_chunked` on q and k repeated to the
value heads) and that form's `jax.grad`: o and all five gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import linear_attention as la
from areal_tpu.models import transformer as tfm
from areal_tpu.ops.pallas import delta_chunk

D = 128
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# fp32 operands both sides: what is left is the order of the sums.
TOL = dict(rtol=2e-4, atol=2e-6)


def _segments(*rows):
    """Rows of (segment id, length) runs -> [B, S] int32."""
    return jnp.asarray(np.stack([
        np.concatenate([np.full(n, i) for i, n in row]) for row in rows
    ]).astype(np.int32))


def _operands(seg, hk, hv, seed=0, dk=D, dv=D):
    b, s = seg.shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la._l2norm(jax.random.normal(ks[0], (b, s, hk, dk))) * dk**-0.5
    k = la._l2norm(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hv, dv))
    g = -jax.random.uniform(ks[3], (b, s, hv), minval=0.01, maxval=2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    weights = jax.random.normal(ks[5], (b, s, hv, dv))
    return (q, k, v, g, beta), weights


def _o_and_grads(rule, ops, weights):
    def loss(*ops):
        o = rule(*ops)
        return jnp.sum(o * weights), o

    (_, o), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*ops)
    return (o, *grads)


def _oracle(seg, rep):
    def rule(q, k, v, g, beta):
        q, k = (jnp.repeat(x, rep, axis=2) for x in (q, k))
        return la.gated_delta_chunked(q, k, v, g, beta, seg)[0]

    return rule


def _kernel(seg, **kw):
    kw.setdefault("operands", jnp.float32)
    return lambda *ops: delta_chunk.gdn_chunk(*ops, seg, **kw)


def _assert_close(got, want, tol=TOL):
    for name, x, y in zip(NAMES, got, want):
        assert x.dtype == jnp.float32 and x.shape == y.shape, name
        scale = float(jnp.max(jnp.abs(y)))
        np.testing.assert_allclose(
            x, y, rtol=tol["rtol"], atol=tol["atol"] + tol["rtol"] * scale,
            err_msg=name)


CASES = {
    # 70 | 58 | 64: the second segment starts six tokens into chunk two
    "a_segment_starts_inside_a_chunk":
        (_segments([(1, 70), (2, 58), (3, 64)]), 1, 2),
    # 64 | 128: the second starts on chunk two's first token
    "a_segment_starts_on_a_chunks_first_token":
        (_segments([(1, 64), (2, 128)]), 1, 2),
    "a_row_of_one_segment": (_segments([(1, 192)]), 1, 2),
    "pads_at_the_end": (_segments([(1, 90), (2, 60), (0, 42)]), 1, 2),
    # 150 tokens: the last chunk is 22 real tokens and 42 neutral ones
    "a_length_that_is_not_whole_chunks":
        (_segments([(1, 100), (2, 50)]), 1, 2),
    "one_value_head_a_key_head": (_segments([(1, 70), (2, 58)]), 2, 2),
    "two_value_heads_a_key_head": (_segments([(1, 70), (2, 58)]), 2, 4),
    "two_rows": (_segments([(1, 70), (2, 58), (0, 22)],
                           [(1, 64), (2, 86)]), 1, 2),
    # (d_k, d_v) behind the heads: no whole 128-lane tiles, so the sweep
    # runs them as 128 x 256 on zero columns, and three heads as four
    "heads_of_96_by_192_run_as_whole_tiles":
        (_segments([(1, 70), (2, 58), (0, 22)]), 3, 3, 96, 192),
}


@pytest.mark.parametrize("case", list(CASES), ids=str)
def test_o_and_the_five_gradients_are_the_jnp_forms(case):
    seg, hk, hv, *widths = CASES[case]
    dk, dv = widths or (D, D)
    ops, weights = _operands(seg, hk, hv, seed=len(case), dk=dk, dv=dv)
    want = _o_and_grads(_oracle(seg, hv // hk), ops, weights)
    got = _o_and_grads(_kernel(seg), ops, weights)
    _assert_close(got, want)


def test_a_head_that_is_not_square_and_segments_that_come_back():
    """d_v twice d_k; and a segment id met again after another (a row is
    never packed so, but the masks compare ids, not positions): tokens of
    one id in ONE chunk see each other, as the `jnp` form has it."""
    seg = _segments([(1, 40), (2, 30), (1, 58)])
    ops, weights = _operands(seg, 1, 2, seed=7, dv=2 * D)
    want = _o_and_grads(_oracle(seg, 2), ops, weights)
    _assert_close(_o_and_grads(_kernel(seg), ops, weights), want)


def test_rebuilt_states_and_any_heads_a_step_give_the_same_bits():
    """The backward with the forward's residuals and with a forward sweep
    of its own; two heads a grid step and four; the four in one trip of the
    step's loop and in two: one arithmetic."""
    seg, hk, hv = CASES["two_value_heads_a_key_head"]
    ops, weights = _operands(seg, hk, hv, seed=3)
    first = _o_and_grads(_kernel(seg, block_h=2), ops, weights)
    for kw in (dict(block_h=2, save=False), dict(block_h=4),
               dict(block_h=4, group=2)):
        again = _o_and_grads(_kernel(seg, **kw), ops, weights)
        for name, x, y in zip(NAMES, again, first):
            np.testing.assert_array_equal(x, y, err_msg=f"{name} {kw}")


def test_bf16_operands_stay_within_bf16_of_the_fp32_form():
    """What a TPU runs: every product outside the solve on bf16 operands,
    as XLA lowers the `jnp` form's there.  Against fp32 products the
    distance is a bf16 rounding's, and the solve's own operands are not
    rounded: with beta = 0 nothing but the solve's identity is left."""
    seg, hk, hv = CASES["a_segment_starts_inside_a_chunk"]
    ops, weights = _operands(seg, hk, hv, seed=11)
    want = _o_and_grads(_oracle(seg, hv // hk), ops, weights)
    got = _o_and_grads(
        _kernel(seg, operands=jnp.bfloat16), ops, weights)
    _assert_close(got, want, dict(rtol=3e-2, atol=1e-5))
    far = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(got, want))
    assert far > 1e-5  # bf16 was really there


def test_block_sizes_and_the_widths_the_kernel_takes():
    assert delta_chunk.group_for(32, 2) == 4  # the cell's: eight trips
    assert delta_chunk.group_for(2, 1) == 2  # fewer heads: all of them
    assert delta_chunk.group_for(6, 1) == 6
    assert delta_chunk.group_for(48, 12) == 48  # 12 value heads a key head
    assert delta_chunk.fits(128, 128) and delta_chunk.fits(128, 256)
    # Zero columns make whole tiles of 96 x 192 at 1.78 times the products;
    # 30 heads with their own keys run as 32, eight trips of four.
    assert delta_chunk.fits(96, 192) and not delta_chunk.fits(64, 64)
    assert delta_chunk.run_heads(30, 30) == 32
    assert delta_chunk.run_heads(16, 32) == 32 == delta_chunk.run_heads(32, 32)
    assert delta_chunk.run_heads(3, 6) == 6
    assert not delta_chunk.fits(16, 16) and not delta_chunk.fits(128, 64)


def _merges_at_fp32(a):
    """The same block merges on fp32 operands throughout (numpy)."""
    c = a.shape[0]
    row, col = np.indices((c, c))
    t = (np.eye(c) - np.where(row // 2 == col // 2, a, 0)).astype(np.float32)
    m = 2
    while m < c:
        below = (row // (2 * m) == col // (2 * m)) & (row // m != col // m)
        t = t - (t @ np.where(below, a, 0).astype(np.float32)) @ t
        m *= 2
    return t


def test_the_inverse_is_fp32s_against_float64():
    """Three bf16 passes a merge and one Newton step at fp32: against the
    float64 inverse T is as far as the same merges on fp32 operands
    throughout are — within 2e-7 of its largest entry on blocks of the
    rule's size (beta k.k' decay: entries of a tenth), and no further than
    one and a half times the fp32 merges' (the furthest of four blocks;
    or 3e-7) on harsher ones whose inverse grows to hundreds."""
    rng = np.random.default_rng(0)
    for scale in (0.1, 0.3, 0.5):
        a = np.tril(
            rng.normal(size=(4, 64, 64)) * scale, -1).astype(np.float32)
        want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
        top = np.max(np.abs(want), axis=(1, 2))
        got = np.stack(delta_chunk._inverses([jnp.asarray(x) for x in a]))
        ours = np.max(np.abs(got - want), axis=(1, 2)) / top
        ref = np.stack([_merges_at_fp32(x) for x in a])
        fp32 = np.max(np.abs(ref - want), axis=(1, 2)) / top
        assert ours.max() <= max(1.5 * fp32.max(), 3e-7), (scale, ours, fp32)
        if scale == 0.1:
            assert np.all(ours <= 2e-7), ours


# ------------------------------------------------ which form the mixer takes


def _mixer(dims=D):
    from tests.test_qwen3_next import _cfg, _params

    cfg = _cfg(linear_k_head_dim=dims, linear_v_head_dim=dims,
               linear_n_k_heads=1, linear_n_v_heads=2)
    params = _params(cfg)
    blk = {k: v[0] for k, v in params["blocks"].items()
           if k in la.LINEAR_LEAVES}
    seg = _segments([(1, 70), (2, 50), (0, 8)])
    h = jax.random.normal(
        jax.random.PRNGKey(2), (1, seg.shape[1], cfg.hidden_dim))
    return cfg, blk, h, seg


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the Pallas sweep was called")

    monkeypatch.setattr(delta_chunk, "gdn_chunk", refuse)


def test_the_mixer_takes_the_jnp_form_on_a_cpu_backend(no_kernel):
    cfg, blk, h, seg = _mixer()
    assert delta_chunk.fits(cfg.linear_k_head_dim, cfg.linear_v_head_dim)
    y = la.linear_attn_forward(h, blk, cfg, seg)
    assert y.shape == h.shape and bool(jnp.all(jnp.isfinite(y)))


def test_the_mixer_keeps_the_jnp_form_under_with_state(no_kernel):
    """Prefill reads the final state: it keeps `gated_delta_chunked` even
    where the kernel is forced."""
    cfg, blk, h, seg = _mixer()
    y, state, tail = la.linear_attn_forward(
        h, blk, cfg, seg, with_state=True, kernel=True)
    assert state.shape == (1, 2, D, D) and state.dtype == jnp.float32


def test_the_mixer_keeps_the_jnp_form_on_a_mesh_and_at_toy_widths(no_kernel):
    from areal_tpu.base.topology import ParallelConfig, make_mesh

    cfg, blk, h, seg = _mixer()
    pc = ParallelConfig.from_str("d2")
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    la.linear_attn_forward(h, blk, cfg, seg, kernel=mesh)
    cfg, blk, h, seg = _mixer(dims=16)  # no whole lanes: never the kernel
    la.linear_attn_forward(h, blk, cfg, seg)


def test_the_mixer_on_the_forced_kernel_is_the_mixer_on_the_jnp_form():
    """`linear_attn_forward(kernel=True)` (interpreted; bf16 products, as
    on a TPU) against the `jnp` form: the output and the gradient of every
    leaf, within what bf16 operands move."""
    cfg, blk, h, seg = _mixer()

    def loss(blk, h, kernel):
        y = la.linear_attn_forward(h, blk, cfg, seg, kernel=kernel)
        return jnp.sum(jnp.sin(y)), y

    (_, y0), g0 = jax.value_and_grad(loss, (0, 1), has_aux=True)(blk, h, False)
    (_, y1), g1 = jax.value_and_grad(loss, (0, 1), has_aux=True)(blk, h, True)
    np.testing.assert_allclose(y1, y0, rtol=3e-2, atol=3e-2 * float(
        jnp.max(jnp.abs(y0))))
    for x, y in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            x, y, rtol=5e-2, atol=5e-2 * float(jnp.max(jnp.abs(y))))


def test_a_gradient_program_binds_one_traced_rule_for_every_layer(
        monkeypatch):
    """Three Gated DeltaNet layers, one `jit` entry point: the kernel
    bodies are traced once a FORM for the three call sites (a bare
    `pallas_call` is traced, and its body lowered, at every site).  That
    the kernels sit under the mixer's `delta_rule` scope is held on the
    compiled program (`tests/test_qwen3_next.py`)."""
    from tests.test_qwen3_next import _cfg, _params

    cfg = _cfg(linear_k_head_dim=D, linear_v_head_dim=D,
               linear_n_k_heads=1, linear_n_v_heads=2)
    params = _params(cfg)
    seg = _segments([(1, 100), (2, 156)])  # a length no other test traces
    tokens = jnp.zeros(seg.shape, jnp.int32)
    traced = {"fwd": 0, "bwd": 0}

    def counting(name):
        body = getattr(delta_chunk, f"_{name}_kernel")

        def kernel(*a, **kw):
            traced[name] += 1
            return body(*a, **kw)

        monkeypatch.setattr(delta_chunk, f"_{name}_kernel", kernel)

    counting("fwd")
    counting("bwd")

    def loss(p):
        x, _ = tfm.hidden_states(p, cfg, tokens, seg, row_kernel=True)
        return jnp.sum(x)

    jax.jit(jax.grad(loss)).lower(params)
    assert cfg.n_linear_layers == 3
    # the forward once without residuals (the rule as called) and once
    # with (the rule's forward pass), whatever the number of layers
    assert traced == {"fwd": 2, "bwd": 1}


def test_the_gradient_programs_compact_schedule_goes_with_the_kernel(
        monkeypatch):
    """`TrainEngine._grad_compiler_options`: the scheduler's memory limit
    is set where the gradient program runs the rule on its sweep — a TPU
    backend, one device, Gated DeltaNet layers of whole-lane heads — and
    nowhere else (a CPU backend would refuse the option's name)."""
    import types

    from areal_tpu.engines.train import TrainEngine
    from tests.test_qwen3_next import _cfg

    def options(cfg, row_kernel=None):
        engine = types.SimpleNamespace(cfg=cfg, _row_kernel=row_kernel)
        return TrainEngine._grad_compiler_options(engine)

    wide = _cfg(linear_k_head_dim=D, linear_v_head_dim=D)
    assert options(wide) == {}  # a CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert options(wide) == {
        "xla_tpu_scheduler_percent_shared_memory_limit": 50}
    from areal_tpu.base.topology import ParallelConfig, make_mesh

    pc = ParallelConfig.from_str("d2")
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    assert options(wide, row_kernel=mesh) == {}  # a mesh: the jnp form
    assert options(_cfg()) == {}  # toy heads: the jnp form
    from areal_tpu.models.config import tiny_config

    assert options(tiny_config()) == {}  # no such layers

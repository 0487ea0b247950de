"""The Pallas operator of the mixers' depthwise causal conv + activation in
training (`ops/pallas/causal_conv.py`), interpreted on the CPU at toy
lengths, against the `jnp` form it takes the place of on a TPU backend
(`linear_attention.causal_conv` + bias + SiLU) with that form's `jax.grad`:
the result and the gradients of x, the taps and the bias; and which form
each of the three kinds that run the conv takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import linear_attention as la
from areal_tpu.models import mamba, short_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.ops.pallas import causal_conv as kernels
from tests.test_ssd_chunk_kernel import _d2_mesh, _segments

# A layout's rows have one length.  `blocks`: 3 x 128 tokens, a segment
# that starts on a block's first token (the second: at 128), one of a single
# token, one that spans all three blocks, trailing pads; `ragged`: a length
# that is no multiple of any token block (padded inside the operator), a
# segment that ends on the row's last token; `short`: one tile, leading pads.
LAYOUTS = {
    "blocks": _segments(
        [(1, 128), (2, 1), (3, 200), (0, 55)],
        [(4, 100), (5, 260), (6, 3), (0, 21)]),
    "ragged": _segments([(1, 150), (2, 180), (3, 256), (4, 14)]),
    "short": _segments([(0, 3), (1, 30), (2, 7)]),
}


def _operands(seg, channels, taps, bias, dtype, seed=0):
    b, s = seg.shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, s, channels)).astype(dtype)
    t = (0.5 * jax.random.normal(ks[1], (taps, channels))).astype(dtype)
    bb = (0.3 * jax.random.normal(ks[2], (channels,))).astype(
        dtype) if bias else None
    weights = jax.random.normal(ks[3], (b, s, channels))
    return (x, t, bb), weights


def _oracle(seg, act):
    def op(x, t, b):
        pre = la.causal_conv(x, t, seg)
        if b is not None:
            pre = pre + b.astype(jnp.float32)
        return jax.nn.silu(pre) if act == "silu" else pre

    return op


def _kernel(seg, act):
    return lambda x, t, b: kernels.causal_conv_act(
        x, t, b, seg, act, interpret=True)


def _out_and_grads(op, ops, weights):
    def loss(*ops):
        y = op(*ops)
        return jnp.sum(y * weights), y

    argnums = (0, 1) if ops[2] is None else (0, 1, 2)
    (_, y), grads = jax.value_and_grad(
        loss, argnums=argnums, has_aux=True)(*ops)
    return (y, *grads)


NAMES = ("out", "dx", "dtaps", "dbias")


def _hold(got, want, dtype):
    """fp32 operands: what is left is the order of the sums.  bf16: the
    gradients of x and of the taps leave in bf16, one rounding each side."""
    for name, x, y in zip(NAMES, got, want):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        scale = float(np.max(np.abs(y)))
        tol = 2e-5 if dtype == jnp.float32 or name == "out" else 1e-2
        np.testing.assert_allclose(
            x, y, rtol=tol, atol=tol * scale, err_msg=name)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("taps,bias,act", [
    (4, False, "silu"), (4, True, "silu"), (3, False, "identity"),
    (3, True, "identity")], ids=["k4_silu", "k4_bias_silu", "k3", "k3_bias"])
def test_the_operator_is_the_jnp_forms_on_fp32_operands(
        layout, taps, bias, act):
    seg = LAYOUTS[layout]
    ops, weights = _operands(seg, 256, taps, bias, jnp.float32)
    _hold(_out_and_grads(_kernel(seg, act), ops, weights),
          _out_and_grads(_oracle(seg, act), ops, weights), jnp.float32)


@pytest.mark.parametrize("channels", [256, 384, 640])
@pytest.mark.parametrize("layout", ["blocks", "ragged"])
def test_the_operator_is_the_jnp_forms_on_bf16_operands(channels, layout):
    """The cells' operands (bf16 in, fp32 out, the input's gradient in
    bf16) at channel counts that are and are not powers of two: a channel
    block of 256, of 384 and of 128 lanes.  The forward sums in the `jnp`
    form's order: to the bit."""
    seg = LAYOUTS[layout]
    ops, weights = _operands(seg, channels, 4, True, jnp.bfloat16, seed=1)
    got = _out_and_grads(_kernel(seg, "silu"), ops, weights)
    want = _out_and_grads(_oracle(seg, "silu"), ops, weights)
    np.testing.assert_array_equal(got[0], want[0])
    _hold(got, want, jnp.bfloat16)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_tile_without_a_boundary_skips_the_selects_and_keeps_the_result(
        layout):
    """The flags say which tiles no segment starts or ends in reach of;
    forcing every tile down the masked path gives the same result."""
    seg = LAYOUTS[layout]
    ops, weights = _operands(seg, 256, 4, True, jnp.bfloat16, seed=2)
    _, sp, _, rows = kernels._blocks(seg.shape[1], 256)
    padded = jnp.pad(
        seg, ((0, 0), (0, sp - seg.shape[1])), constant_values=-1)
    bits, flags = kernels._mask_bits(padded, 4, rows)
    flags = np.asarray(flags).reshape(seg.shape[0], -1)
    ids = np.asarray(padded).reshape(seg.shape[0], -1, rows)
    for b, t in np.ndindex(*flags.shape):
        lo, hi = t * rows, (t + 1) * rows
        row = np.asarray(padded[b])
        behind = row[max(lo - 3, 0): hi]
        ahead = row[lo: hi + 3]
        assert bool(flags[b, t] & 1) == (
            lo >= 3 and len(set(behind)) == 1), (b, t)
        assert bool(flags[b, t] & 2) == (
            hi + 3 <= sp and len(set(ahead)) == 1), (b, t)
    assert ids.shape[1] == flags.shape[1]

    def rule(all_masked):
        def op(x, t, b):
            x = jnp.pad(x, ((0, 0), (0, sp - seg.shape[1]), (0, 0)))
            f = jnp.zeros_like(flags) if all_masked else jnp.asarray(flags)
            return kernels._rule(
                "silu", True, x, t, b, bits, f.reshape(-1))[:, :seg.shape[1]]

        return _out_and_grads(op, ops, weights)

    got, want = rule(False), rule(True)
    np.testing.assert_array_equal(got[0], want[0])
    # (the CPU's compiler contracts the two branches' sums its own way:
    # a gradient in bf16 may land an ulp apart)
    _hold(got, want, jnp.bfloat16)


def test_the_widths_the_operator_takes():
    for channels in (11520, 8192, 6144, 4352, 2048):  # the five cells'
        assert kernels.fits(channels, 4) and kernels.fits(channels, 3)
    assert not kernels.fits(64, 3) and not kernels.fits(192, 4)  # the toys
    assert not kernels.fits(256, 1) and not kernels.fits(256, 9)
    # the cells' blocks: (tokens, padded row, channels, a tile's rows)
    assert kernels._blocks(8192, 11520) == (1024, 8192, 384, 32)
    assert kernels._blocks(8192, 4352) == (1024, 8192, 256, 64)
    assert kernels._blocks(4224, 2048) == (128, 4224, 512, 32)
    assert kernels._blocks(1000, 640) == (1024, 1024, 128, 128)
    assert kernels._blocks(1100, 640) == (128, 1152, 128, 128)
    assert kernels._blocks(40, 256) == (64, 64, 256, 64)
    with pytest.raises(AssertionError):
        seg = LAYOUTS["short"]
        kernels.causal_conv_act(
            jnp.zeros((1, 40, 192)), jnp.zeros((4, 192)), None, seg)


# ------------------------------------------------ which form a mixer takes


def test_the_form_is_decided_by_what_the_code_can_see(monkeypatch):
    assert not la.conv_kernel_form(256, 4)  # a CPU backend
    assert la.conv_kernel_form(256, 4, True)  # forced: interpreted
    assert not la.conv_kernel_form(192, 4, True)  # forced, no whole tiles
    assert not la.conv_kernel_form(256, 4, True, with_state=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.conv_kernel_form(256, 4) and la.conv_kernel_form(2048, 3)
    assert not la.conv_kernel_form(192, 4)  # no whole tiles
    assert not la.conv_kernel_form(256, 4, _d2_mesh())  # one device's
    assert not la.conv_kernel_form(256, 4, with_state=True)  # prefill
    assert not la.conv_kernel_form(256, 4, False)


def _gdn_mixer():
    from tests.test_qwen3_next import _cfg, _params

    cfg = _cfg()
    return (la, cfg, _params(cfg), la.LINEAR_LEAVES, la.linear_attn_forward,
            (cfg.linear_conv_dim, cfg.linear_conv_kernel),
            "linear_attn/conv_on_kernel")


def _ssm_mixer():
    from tests.test_nemotron_h import _cfg, _params

    cfg = _cfg()
    return (mamba, cfg, _params(cfg), mamba.SSM_LEAVES, mamba.ssm_forward,
            (cfg.ssm_conv_dim, cfg.ssm_conv_kernel), "ssm/conv_on_kernel")


def _sconv_mixer():
    from tests.test_lfm2_moe import _cfg, _params

    cfg = _cfg(hidden_dim=128)
    return (short_conv, cfg, _params(cfg), short_conv.SCONV_LEAVES,
            short_conv.sconv_forward, (cfg.hidden_dim, cfg.sconv_kernel),
            "sconv/conv_on_kernel")


MIXERS = {"gdn": _gdn_mixer, "ssm": _ssm_mixer, "sconv": _sconv_mixer}


def _layer(kind):
    module, cfg, params, leaves, forward, widths, stat = MIXERS[kind]()
    blk = {k: v[0] for k, v in params["blocks"].items() if k in leaves}
    seg = _segments([(1, 70), (2, 50), (0, 8)])
    h = jax.random.normal(
        jax.random.PRNGKey(2), (1, seg.shape[1], cfg.hidden_dim))
    return module, cfg, blk, h, seg, forward, widths, stat


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the Pallas conv was called")

    monkeypatch.setattr(kernels, "causal_conv_act", refuse)


@pytest.mark.parametrize("kind", sorted(MIXERS))
def test_the_mixer_takes_the_jnp_form_on_a_cpu_backend_under_with_state_and_on_a_mesh(
        kind, no_kernel):
    """Prefill keeps `causal_conv` even where the kernel is forced; a mesh
    keeps it whatever the backend; a CPU backend has no kernel to take."""
    _, cfg, blk, h, seg, forward, widths, _ = _layer(kind)
    assert kernels.fits(*widths)
    y = forward(h, blk, cfg, seg)
    assert y.shape == h.shape and bool(jnp.all(jnp.isfinite(y)))
    out = forward(h, blk, cfg, seg, with_state=True, kernel=True)
    assert out[0].shape == h.shape and len(out) >= 2
    forward(h, blk, cfg, seg, kernel=_d2_mesh())


@pytest.mark.parametrize("kind", sorted(MIXERS))
def test_the_mixer_on_the_forced_kernel_is_the_mixer_on_the_jnp_form(
        kind, monkeypatch):
    """`forward(kernel=True)` with the conv on the interpreted operator —
    the kind's recurrence kept on its `jnp` form, so that the conv is what
    differs — against `kernel=False`: the output and every leaf's
    gradient, fp32 operands, inside the order of the sums."""
    module, cfg, blk, h, seg, forward, _, _ = _layer(kind)
    for chooser in ("chunk_kernel_form", "ssd_kernel_form"):
        if hasattr(module, chooser):
            monkeypatch.setattr(module, chooser, lambda *a, **kw: False)
    calls = []
    op = kernels.causal_conv_act

    def counted(*a, **kw):
        calls.append(a[4] if len(a) > 4 else kw.get("act", "silu"))
        return op(*a, **kw)

    monkeypatch.setattr(kernels, "causal_conv_act", counted)

    def loss(blk, h, kernel):
        y = forward(h, blk, cfg, seg, kernel=kernel)
        return jnp.sum(jnp.sin(y)), y

    run = jax.value_and_grad(loss, (0, 1), has_aux=True)
    (_, y0), g0 = run(blk, h, False)
    assert not calls
    (_, y1), g1 = run(blk, h, True)
    assert calls == ["identity" if kind == "sconv" else "silu"]
    np.testing.assert_allclose(y1, y0, rtol=2e-5, atol=2e-5 * float(
        jnp.max(jnp.abs(y0))))
    for (path, x), y in zip(
            jax.tree.leaves_with_path(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            x, y, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(y))),
            err_msg=str(path))


@pytest.mark.parametrize("kind", sorted(MIXERS))
def test_the_counter_says_which_form_the_conv_took(kind, monkeypatch):
    module, cfg, _, _, seg, _, _, stat = _layer(kind)
    stats = module.BRANCH.train_stats(cfg, 3, seg, None)
    assert float(stats[stat]) == 0  # a CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert float(module.BRANCH.train_stats(cfg, 3, seg, None)[stat]) == 1
    assert float(module.BRANCH.train_stats(cfg, 3, seg, _d2_mesh())[stat]) == 0
    assert float(module.BRANCH.train_stats(cfg, 3, seg, False)[stat]) == 0


@pytest.mark.parametrize("kind", ["gdn", "ssm"])
def test_a_gradient_program_binds_one_traced_conv_for_every_layer(
        kind, monkeypatch):
    """Several conv layers, one `jit` entry point: the kernel bodies are
    traced once a FORM for all the call sites (a bare `pallas_call` is
    traced, and its body lowered, at every site)."""
    module, cfg, params, *_ = MIXERS[kind]()
    for chooser in ("chunk_kernel_form", "ssd_kernel_form"):
        if hasattr(module, chooser):
            monkeypatch.setattr(module, chooser, lambda *a, **kw: False)
    seg = _segments([(1, 90), (2, 166)])  # a length no other test traces
    tokens = jnp.zeros(seg.shape, jnp.int32)
    traced = {"fwd": 0, "bwd": 0}
    calls = []

    def counting(name):
        body = getattr(kernels, f"_{name}_kernel")

        def kernel(*a, **kw):
            traced[name] += 1
            return body(*a, **kw)

        monkeypatch.setattr(kernels, f"_{name}_kernel", kernel)

    counting("fwd")
    counting("bwd")
    op = kernels.causal_conv_act
    monkeypatch.setattr(
        kernels, "causal_conv_act",
        lambda *a, **kw: calls.append(1) or op(*a, **kw))

    def loss(p):
        x, _ = tfm.hidden_states(p, cfg, tokens, seg, row_kernel=True)
        return jnp.sum(x)

    jax.jit(jax.grad(loss)).lower(params)
    assert len(calls) >= 3
    # the forward once as the rule is called and once as the rule's forward
    # pass, whatever the number of layers
    assert traced == {"fwd": 2, "bwd": 1}

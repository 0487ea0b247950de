"""One expert-parallel rank's share under the grouped dispatch: the slab of
`expert_slab_rows` pairs and its dropless overflow
(`transformer._experts_grouped`).

The families' toy configurations hold half of what their routers score,
where the slab is every pair and the code path is the one before the slab.
Here a rank holds 2 of 16: 1,024 rows x 2 choices make 2,048 pairs and a
slab of 512, with and without a gradient.  Gated and ungated experts,
softmax and sigmoid routers, and three routings: balanced (the first slab
alone), every choice held here (all four slabs run, and each expert's rows
are split over two), none held.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.engines.train import _moe_stats
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig

TOL = dict(rtol=5e-4, atol=5e-4)  # as the families' files hold the dispatches
T, D, F, WIDTH, HELD, K = 1024, 16, 8, 16, 2, 2
OFFSET = 6  # experts [6, 8) of the router's 16
REGIMES = {"balanced": 0.0, "all_held": 30.0, "none_held": -30.0}


def _cfg(gated=True, score="softmax", **changes) -> ModelConfig:
    cfg = ModelConfig(
        n_layers=1, hidden_dim=D, n_q_heads=2, n_kv_heads=2, head_dim=8,
        intermediate_dim=F, vocab_size=64, param_dtype="float32",
        n_experts=HELD, n_router_experts=WIDTH, expert_offset=OFFSET,
        n_experts_per_tok=K, moe_intermediate_dim=F, mlp_gated=gated,
        hidden_act="silu" if gated else "relu2", moe_score_func=score,
        moe_routed_scale=1.5 if score == "sigmoid" else 1.0,
    )
    return dataclasses.replace(cfg, **changes)


def _layer(cfg: ModelConfig, tilt: float, seed=0):
    """(h [1, T, D], the layer's leaves).  Column 0 of h is one, and row 0
    of the softmax router (the sigmoid router's choice bias) tilts the held
    experts' scores by `tilt`."""
    rng = np.random.default_rng(seed)
    h = rng.normal(size=(1, T, D)).astype(np.float32)
    h[..., 0] = 1.0
    n = cfg.n_experts
    blk = {
        "router": 0.5 * rng.normal(size=(D, cfg.router_width)),
        "wu": 0.3 * rng.normal(size=(n, D, F)),
        "wd": 0.3 * rng.normal(size=(n, F, D)),
    }
    if cfg.mlp_gated:
        blk["wg"] = 0.3 * rng.normal(size=(n, D, F))
    mine = slice(cfg.expert_offset, cfg.expert_offset + n)
    if cfg.moe_score_func == "sigmoid":
        blk["router_bias"] = np.zeros((cfg.router_width,))
        blk["router_bias"][mine] = tilt
    else:
        blk["router"][0, mine] += tilt
    return jnp.asarray(h), {
        k: jnp.asarray(v, jnp.float32) for k, v in blk.items()
    }


def _full_gather(x, top_w, top_idx, one_hot, blk, cfg):
    """The dispatch before the slab: every pair through `_grouped_rows`."""
    order = jnp.argsort(top_idx.reshape(-1), stable=True)
    sizes = jnp.sum(one_hot, axis=(0, 1)).astype(jnp.int32)
    return tfm._grouped_rows(x, top_w, order, sizes, blk, cfg)


def _primitives(jaxpr) -> set:
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def _shapes(jaxpr, into=None):
    """{primitive: result shapes} outside and inside every sub-jaxpr but a
    loop's body."""
    into = {} if into is None else into
    for e in jaxpr.eqns:
        into.setdefault(e.primitive.name, []).extend(
            tuple(v.aval.shape) for v in e.outvars)
        if e.primitive.name != "while":
            for sub in jax.core.jaxprs_in_params(e.params):
                _shapes(sub, into)
    return into


def _wide(by_op, rows):
    """The [rows, >= F] results among `_shapes`: a dispatch's buffers."""
    return [s for ss in by_op.values() for s in ss
            if len(s) == 2 and s[0] == rows and s[1] >= F]


def test_the_slab_is_twice_a_balanced_routers_rows_in_whole_tiles():
    cfg = _cfg()
    assert tfm.expert_slab_rows(cfg, T * K) == 512
    # The benchmark's share cells, a micro-batch row a layer.
    share = lambda n, w: dataclasses.replace(  # noqa: E731
        cfg, n_experts=n, n_router_experts=w, expert_offset=0)
    assert tfm.expert_slab_rows(share(64, 512), 81_920) == 20_480
    assert tfm.expert_slab_rows(share(16, 128), 49_152) == 12_288
    assert tfm.expert_slab_rows(share(8, 64), 20_480) == 5_120
    # A decode step's 640 pairs round up to a tile; a share of half and a
    # model that holds every expert have no slab.
    assert tfm.expert_slab_rows(share(64, 512), 640) == 512
    assert tfm.expert_slab_rows(share(8, 16), 2_048) == 2_048
    assert tfm.expert_slab_rows(share(16, 0), 2_048) == 2_048


@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_slab_and_overflow_are_the_dense_oracle(gated, score, regime):
    cfg = _cfg(gated, score)
    oracle = dataclasses.replace(cfg, moe_dispatch="dense")
    h, blk = _layer(cfg, REGIMES[regime])
    cot = jnp.asarray(
        np.random.default_rng(1).normal(size=h.shape), jnp.float32)

    def run(c):
        def loss(h, blk):
            out, aux, counts = tfm._mlp_moe(h, blk, c)
            return jnp.sum(out * cot), (out, aux, counts)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, (got, aux, counts)), (dh, dblk) = run(cfg)(h, blk)
        (_, (want, _, _)), (dh_want, dblk_want) = run(oracle)(h, blk)
    held = counts.sum()
    pairs, slab = T * K, tfm.expert_slab_rows(cfg, T * K)
    expect = {"balanced": None, "all_held": pairs, "none_held": 0}[regime]
    if expect is None:  # an eighth of the pairs, give or take
        assert pairs // 16 < int(held) < slab
    else:
        assert int(held) == expect
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(dh, dh_want, **TOL)
    assert set(dblk) == set(blk)
    for name in blk:
        np.testing.assert_allclose(
            dblk[name], dblk_want[name], err_msg=name, **TOL)
    if regime != "none_held":
        assert float(jnp.abs(got).max()) > 1e-3
        assert all(float(jnp.abs(dblk[n]).max()) > 1e-3
                   for n in tfm._expert_leaves(cfg))

    # One algorithm with and without a gradient, and bit for bit the full
    # gather's: with two choices a row a row's sum has two terms, which any
    # order adds to the same bits (with more, the later slabs' sum joins
    # the first slab's in another order: scripts/check_moe_slab.py).
    @jax.jit
    def three(h, blk):
        x = h.reshape(-1, D)
        routed = tfm._moe_route(x, blk, cfg)[:3]
        experts = lambda x: tfm._experts_grouped(x, *routed, blk, cfg)  # noqa: E731
        return (jax.vjp(experts, x)[0], experts(x),
                _full_gather(x, *routed, blk, cfg))

    slabs, plain, full = three(h, blk)
    np.testing.assert_array_equal(np.asarray(slabs), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(full))

    stats = _moe_stats(aux, counts[None], cfg, pairs)
    gathered = {"balanced": 25.0, "all_held": 100.0, "none_held": 25.0}
    assert int(tfm.expert_slabs_run(slab, pairs, held)) == {
        "balanced": 1, "all_held": 4, "none_held": 1}[regime]
    assert float(stats["moe/rows_gathered_share"]) == gathered[regime]
    np.testing.assert_allclose(
        float(stats["moe/slab_fill_max"]), int(held) / slab, rtol=1e-6)


@pytest.mark.parametrize("regime", ["balanced", "all_held", "none_held"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_grouped_kernel_under_the_slabs_gives_ragged_dots_gradients(
        gated, regime):
    """`kernel=True`: the expert matmuls of the FIRST slab are the Pallas
    kernel `grouped_matmul` (interpreted here) under `_grouped_slabs`'s own
    gradient rule, by `jax.vjp`; the later slabs (three of them where every
    choice is held here: each expert's rows split over two slabs) keep
    `ragged_dot`, recomputed inside the loop on the way back.  Output, the rows'
    gradient and every leaf's against the same layer on `ragged_dot` and
    against the dense oracle."""
    cfg = _cfg(gated)
    h, blk = _layer(cfg, REGIMES[regime])
    cot = jnp.asarray(
        np.random.default_rng(1).normal(size=h.shape), jnp.float32)

    def run(kernel, c=cfg):
        def loss(h, blk):
            out, _, counts = tfm._mlp_moe(h, blk, c, kernel=kernel)
            return jnp.sum(out * cot), (out, counts)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    before = tfm.expert_matmuls_traced()
    with jax.default_matmul_precision("highest"):
        (_, (got, counts)), (dh, dblk) = run(True)(h, blk)
        calls, on_kernel = (b - a for a, b in zip(
            before, tfm.expert_matmuls_traced()))
        (_, (want, _)), (dh_want, dblk_want) = run(False)(h, blk)
        (_, (dense, _)), (dh_dense, dblk_dense) = run(
            False, dataclasses.replace(cfg, moe_dispatch="dense"))(h, blk)
    # the first slab's: the later slabs' loop keeps `ragged_dot`
    assert 0 < on_kernel < calls
    assert tfm.expert_matmuls_traced()[1] == before[1] + on_kernel
    slabs = int(tfm.expert_slabs_run(512, T * K, counts.sum()))
    assert slabs == {"balanced": 1, "all_held": 4, "none_held": 1}[regime]
    # ... against the same layer on `ragged_dot`, and against the dense
    # oracle (every expert over every row)
    for other, dh_other, dblk_other in (
            (want, dh_want, dblk_want), (dense, dh_dense, dblk_dense)):
        np.testing.assert_allclose(got, other, **TOL)
        np.testing.assert_allclose(dh, dh_other, **TOL)
        for name in blk:
            np.testing.assert_allclose(
                dblk[name], dblk_other[name], err_msg=name, **TOL)
    if regime != "none_held":
        assert all(float(jnp.abs(dblk[n]).max()) > 1e-3
                   for n in tfm._expert_leaves(cfg))


def test_the_first_slab_is_outside_the_loop_and_the_loop_saves_nothing():
    """In every program of the unstacked path the first slab's gather,
    kernels and scatter-add stay outside any control flow (the benchmark's
    readers find a program's ragged kernels by the scoped activation
    product's rows); the later slabs are one loop with a traced bound —
    and one more on the way back where a gradient is taken — and what is
    kept for the way back is one slab's activations and the operands: no
    [pairs, ...] buffer anywhere, with or without a gradient."""
    cfg = _cfg()
    h, blk = _layer(cfg, 0.0)
    slab = 512

    def loss(h, blk):
        return jnp.sum(tfm._mlp_moe(h, blk, cfg)[0])

    plain = _shapes(jax.make_jaxpr(loss)(h, blk).jaxpr)
    assert "cond" not in plain and len(plain["while"]) > 0
    assert _wide(plain, slab) and not _wide(plain, T * K)
    grad = _shapes(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, blk).jaxpr)
    assert "cond" not in grad and len(grad["while"]) > len(plain["while"])
    assert _wide(grad, slab) and not _wide(grad, T * K)
    # What the gradient's forward pass keeps: one slab's activations.
    _, vjp = jax.vjp(loss, h, blk)
    kept = [tuple(x.shape) for x in jax.tree.leaves(vjp)]
    assert (slab, F) in kept and not [s for s in kept if s[:1] == (T * K,)
                                      and len(s) == 2 and s[1] >= F], kept


@pytest.mark.parametrize("case", ["every_expert_held", "a_share_of_half"])
def test_a_model_without_a_slab_lowers_as_before(case):
    """No `cond` and no slice of the order where `expert_slab_rows` is every
    pair: the program of a model that holds every expert its router scores
    (and of the toy shares of one half) is the one before the slab."""
    cfg = _cfg(
        n_experts=WIDTH if case == "every_expert_held" else WIDTH // 2,
        expert_offset=0,
        n_router_experts=0 if case == "every_expert_held" else WIDTH,
    )
    h, blk = _layer(cfg, 0.0)
    jaxpr = jax.make_jaxpr(lambda h: tfm._mlp_moe(h, blk, cfg)[0])(h).jaxpr
    names = _primitives(jaxpr)
    assert "ragged_dot_general" in names or "ragged_dot" in names
    assert not names & {
        "cond", "while", "slice", "dynamic_slice", "cumsum", "pad"}, names
    assert not any(n.startswith("custom_vjp") for n in names), names


# A decode step (stacked leaves): (held, router width, choices, tokens,
# tilt, kernel) -> the pairs and what `decode_slab_rows` makes of them.
DECODE_STEPS = {
    "640_pairs_of_64_in_512": (64, 512, 10, 64, 0.0, False),  # a twin's
    "256_pairs_of_8_in_64": (8, 64, 4, 64, 0.0, False),  # under one tile
    "2048_pairs_of_16_in_128": (16, 128, 8, 256, 0.0, False),  # a block's
    "2048_pairs_tilted": (16, 128, 8, 256, 30.0, False),
    "2048_pairs_on_the_kernel": (16, 128, 8, 256, 0.0, True),
    "2048_pairs_tilted_on_the_kernel": (16, 128, 8, 256, 30.0, True),
}


@pytest.mark.parametrize("case", list(DECODE_STEPS))
def test_a_decode_step_takes_the_slab_that_leaves_out_half(case, monkeypatch):
    """Stacked leaves (`layer` given): the step takes `expert_slab_rows`
    where that is half its pairs or fewer, by the shapes alone.  A token
    loop's pairs — 640 would round to a slab of 512, 256 are under one tile
    — keep every pair on the one path: no loop, the program the rule was
    not there for.  A block loop's 2,048 of a rank that holds 16 of 128
    gather 512: a router as initialised gives the full gather's result bit
    for bit, one tilted toward the held experts runs the overflow to the
    same result, never a dropped pair, and the loop's counters say what was
    gathered.  `kernel`: the first slab's matmuls are
    `grouped_decode_matmul` (interpreted), the later slabs' `ragged_dot`."""
    from areal_tpu.models.branches import LoopStep

    held, width, k, tokens, tilt, kernel = DECODE_STEPS[case]
    cfg = _cfg(n_experts=held, n_router_experts=width, expert_offset=held,
               n_experts_per_tok=k)
    h, blk = _layer(cfg, tilt)
    x, pairs = h[:, :tokens], tokens * k
    stacked = {n: jnp.stack([0.5 * blk[n], blk[n]])
               for n in tfm._expert_leaves(cfg)}

    def step(x):
        return tfm._mlp_moe(
            x, blk, cfg, stacked=stacked, layer=1, kernel=kernel)

    slab = tfm.decode_slab_rows(cfg, pairs)
    jaxpr = jax.make_jaxpr(step)(x)
    with monkeypatch.context() as m:  # every pair on the one path
        m.setattr(tfm, "decode_slab_rows", lambda cfg, pairs: pairs)
        one_path = jax.make_jaxpr(step)(x)
        with jax.default_matmul_precision("highest"):
            want, _, _ = jax.jit(step)(x)
    counter = tfm.BRANCHES["moe"].counter
    if pairs < 2048:
        assert tfm.expert_slab_rows(cfg, pairs) in (pairs, 512)
        assert slab == pairs
        assert str(jaxpr) == str(one_path)
        assert not {"cond", "while"} & _primitives(jaxpr.jaxpr)
        assert counter.width(cfg, tokens) == 5
        np.testing.assert_allclose(want, tfm._mlp_moe(x, blk, cfg)[0], **TOL)
        return
    assert slab == 512 == tfm.expert_slab_rows(cfg, pairs)
    names = _primitives(jaxpr.jaxpr)
    # (a `cond` is the interpreted kernel's own `pl.when`)
    assert "while" in names and (kernel or "cond" not in names)
    assert ("pallas_call" in names) == kernel
    outside = _shapes(jaxpr.jaxpr)  # the first slab's buffers: 512 rows
    assert _wide(outside, 512) and not _wide(outside, pairs)
    with jax.default_matmul_precision("highest"):
        got, _, counts = jax.jit(step)(x)
    held = int(counts.sum())
    if tilt:  # every choice held here: four slabs
        assert held == pairs
        np.testing.assert_allclose(got, want, **TOL)
    else:  # an eighth of the pairs, give or take: no trip
        assert pairs // 16 < held < 512
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(got).max()) > 1e-3
    at = LoopStep(None, None, None, tokens)
    assert counter.width(cfg, tokens) == 8
    layers = jnp.stack([counts, counts])  # as the layer scan stacks them
    report = counter.report(
        2 * np.asarray(counter.step(layers, cfg, at)), cfg,
        {"blocks": stacked})
    assert report["moe_rows_routed"] == 2 * 2 * pairs
    assert report["moe_rows_local"] == 2 * 2 * held
    assert report["moe_slab_fill_max"] == held / 512
    assert report["moe_rows_gathered"] == 2 * 2 * (pairs if tilt else 512)


def test_a_call_of_programs_with_and_without_a_slab_adds_up():
    """A generate call's chunks may differ in rows: one program's steps
    take a slab and carry the three sums of it, another's do not.  The
    host's `CallSums` adds each vector to the slots it has, and the report
    counts a step without a slab as gathering every pair of its own."""
    from areal_tpu.models.branches import CallSums, LoopStep

    cfg = _cfg(n_experts=16, n_router_experts=128, expert_offset=16,
               n_experts_per_tok=8)
    counter = tfm.BRANCHES["moe"].counter
    params = {"blocks": dict.fromkeys(tfm._expert_leaves(cfg), np.zeros(()))}
    counts = jnp.full((2, 16), 10, jnp.int32)  # 160 pairs held a layer
    sums = CallSums()
    for tokens in (64, 256, 64):  # 512 pairs, 2,048, 512
        step = np.asarray(counter.step(
            counts, cfg, LoopStep(None, None, None, tokens)), float)
        assert step.size == counter.width(cfg, tokens)
        sums += step
    assert isinstance(sums, CallSums) and sums.size == 8
    report = counter.report(sums, cfg, params)
    assert report["moe_decode_steps"] == 3
    assert report["moe_rows_routed"] == 2 * (512 + 2048 + 512)
    assert report["moe_rows_gathered"] == 2 * (512 + 512 + 512)
    assert report["moe_slab_fill_max"] == 160 / 512  # of the one step's
    # ... and a call without a slab anywhere reports neither
    plain = CallSums()
    plain += np.asarray(counter.step(
        counts, cfg, LoopStep(None, None, None, 64)), float)
    assert not {"moe_rows_gathered", "moe_slab_fill_max"} & set(
        counter.report(plain, cfg, params))


def test_pads_are_left_out_of_a_slab():
    """A packed row's or a prompt batch's pads are one vector many times
    over.  Here 600 of them score the held pair top: 1,200 pairs more on a
    slab of 512.  Told which rows are real (`valid`), the layer leaves the
    others out of the dispatch — one slab runs, the real rows come out as
    from the dense oracle and bit for bit as with the pads dispatched, a
    pad's expert output is zero, and the trainer's rows per expert are
    exactly the dispatch's groups, so its slab counters count.  Not told,
    it dispatches them: three slabs, never a dropped pair."""
    cfg = _cfg()
    h, blk = _layer(cfg, 0.0)
    pad = np.zeros((D,), np.float32)
    pad[0] = 1.0
    router = np.asarray(blk["router"]).copy()
    router[0, OFFSET: OFFSET + HELD] += 30.0
    h = h.at[:, :, 0].set(0.0).at[:, T - 600:].set(jnp.asarray(pad))
    blk = dict(blk, router=jnp.asarray(router))
    valid = jnp.arange(T)[None] < T - 600
    layer = jax.jit(tfm._mlp_moe, static_argnums=2)
    dense = dataclasses.replace(cfg, moe_dispatch="dense")
    with jax.default_matmul_precision("highest"):
        out, aux, counts = layer(h, blk, cfg, valid)
        every, _, routed = layer(h, blk, cfg, None)
        want, _, counts_want = layer(h, blk, dense, valid)
    real = slice(0, T - 600)
    assert int(routed.sum()) - int(counts.sum()) == 600 * K
    assert int(counts.sum()) < 512 < int(routed.sum())
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_want))
    assert int(tfm.expert_slabs_run(512, T * K, counts.sum())) == 1
    assert int(tfm.expert_slabs_run(512, T * K, routed.sum())) == 3
    np.testing.assert_allclose(out[:, real], want[:, real], **TOL)
    np.testing.assert_array_equal(
        np.asarray(out[:, real]), np.asarray(every[:, real]))
    np.testing.assert_allclose(every, want, **TOL)
    assert np.abs(np.asarray(every[:, T - 600:])).max() > 1e-3
    assert np.abs(np.asarray(out[:, T - 600:])).max() == 0.0
    stats = _moe_stats(aux, counts[None], cfg, T * K)
    assert float(stats["moe/rows_gathered_share"]) == 25.0
    assert float(stats["moe/slab_fill_max"]) == int(counts.sum()) / 512
    # No slab, no mask: a share of half dispatches its pads as it did.
    half = _cfg(n_experts=WIDTH // 2, expert_offset=0)
    h2, blk2 = _layer(half, 0.0)
    masked = jax.make_jaxpr(lambda h: tfm._mlp_moe(h, blk2, half, valid)[0])(h2)
    plain = jax.make_jaxpr(lambda h: tfm._mlp_moe(h, blk2, half)[0])(h2)
    assert "select_n" not in _primitives(masked.jaxpr) - _primitives(plain.jaxpr)


@pytest.mark.parametrize("case", ["every_expert_held", "a_share_of_half",
                                  "another_dispatch", "under_pp"])
def test_only_a_slab_has_slab_counters(case):
    """`moe/rows_gathered_share` and `moe/slab_fill_max` exist where the
    grouped dispatch works on a slab, and nowhere else: a model that holds
    every expert keeps the trainer's stats it had."""
    cfg = {
        "every_expert_held": _cfg(
            n_experts=WIDTH, n_router_experts=0, expert_offset=0),
        "a_share_of_half": _cfg(n_experts=WIDTH // 2, expert_offset=0),
        "another_dispatch": _cfg(moe_dispatch="dense"),
        "under_pp": _cfg(),
    }[case]
    counts = None if case == "under_pp" else jnp.ones(
        (1, cfg.n_experts), jnp.int32)
    stats = _moe_stats(jnp.float32(0), counts, cfg, T * K)
    assert set(stats) == {"moe/aux_loss"} | (
        set() if counts is None else {"moe/load_max_over_mean"})
    assert set(_moe_stats(jnp.float32(0), jnp.ones((1, HELD)), _cfg(), T * K)) == {
        "moe/aux_loss", "moe/load_max_over_mean",
        "moe/rows_gathered_share", "moe/slab_fill_max"}


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_the_chip_probe_runs_at_a_toy_size(score):
    """scripts/check_moe_slab.py is what reads the overflow and the
    gradient program's log-probs at a cell's shapes on the chip; here its
    two checks on the toy share, the dense dispatch standing in for the
    plain reference."""
    import sys
    import types

    sys.path.insert(0, "scripts")
    try:
        import check_moe_slab as probe
    finally:
        sys.path.remove("scripts")
    cfg = _cfg(score=score)
    flat, tilted = (probe.dispatch_check(cfg, T, t) for t in (0.0, 4.0))
    assert flat["ok"] and flat["slabs_run"] == 1
    assert flat["forward_elements_differ"] == 0
    assert tilted["ok"] and tilted["slabs_run"] > 1, tilted
    assert not probe.dispatch_check(cfg, T, 0.0, seed=1)["slabs_run"] > 1
    # ... and a decode step's: stacked leaves, the loop's counters beside
    step, tilted = (probe.decode_check(cfg, T, t) for t in (0.0, 4.0))
    assert step["ok"] and step["slabs_run"] == 1 and not step["elements_differ"]
    assert step["moe_rows_gathered"] == 512 and step["moe_slab_fill_max"] < 1
    assert tilted["ok"] and tilted["moe_slab_fill_max"] > 1, tilted
    assert tilted["moe_rows_gathered"] == 512 * tilted["slabs_run"] > 512

    oracle = dataclasses.replace(cfg, moe_dispatch="dense")

    def next_token_logprobs(params, _, seq):
        tokens = jnp.asarray(seq)[None]
        ones = jnp.ones_like(tokens)
        x, _ = tfm.hidden_states(params, oracle, tokens, ones)
        return np.asarray(
            tfm.per_token_output(params, oracle, x, tokens, ones))[0, :-1]

    ref = types.SimpleNamespace(
        next_token_logprobs=next_token_logprobs,
        TOLERANCE={"mean_abs": 1e-4, "max_abs": 1e-3})
    report = probe.logprob_check(cfg, ref, [400, 300, 200], T, remat=True)
    assert report["ok"], report
    assert report["slab"] == 512 and 0 < report["slab_fill_max"] < 1
    assert report["grad_vs_forward_max_abs"] <= 1e-5
    ref.TOLERANCE = {"mean_abs": 0.0, "max_abs": 0.0}
    assert not probe.logprob_check(cfg, ref, [400, 300], T, remat=True)["ok"]


def test_a_models_gradient_program_takes_the_slab_under_scan_and_remat():
    """The whole path the trainer runs: the toy hybrid model holding 1 of
    its router's 8 experts, every layer under the remat policy inside the
    layer scan, the loop over later slabs inside both, the rows' pads left
    out of the slab — the real rows' hidden states, the trainer's counters
    and every leaf's gradient against the dense dispatch's."""
    from tests.test_qwen3_next import _cfg as hybrid_cfg, _params

    cfg = hybrid_cfg(n_experts=1, n_router_experts=8, expert_offset=3)
    oracle = dataclasses.replace(cfg, moe_dispatch="dense")
    params = _params(cfg)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 512)), jnp.int32)
    seg = jnp.asarray(
        np.where(np.arange(512)[None] < [[500], [384]], 1, 0), jnp.int32)
    pairs = tokens.size * cfg.n_experts_per_tok
    slab = tfm.expert_slab_rows(cfg, pairs)
    assert slab == 1024 < pairs

    def run(c):
        def loss(p):
            x, aux, counts = tfm.hidden_states(
                p, c, tokens, seg, remat=True, with_moe_counts=True)
            # A pad is not read.  (The router's loss is over every row, and a
            # pad's hidden state now lacks its experts' part: not compared.)
            x = jnp.where(seg[..., None] > 0, x, 0.0)
            return jnp.mean(jnp.square(x)), (x, counts)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    with jax.default_matmul_precision("highest"):
        (got, (x, counts)), grads = run(cfg)
        (want, (x_want, _)), grads_want = run(oracle)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(x, x_want, **TOL)
    for name in grads["blocks"]:
        np.testing.assert_allclose(
            grads["blocks"][name], grads_want["blocks"][name],
            err_msg=name, **TOL)
    assert counts.shape == (cfg.n_layers, 1)
    held = np.asarray(counts)[:, 0]
    assert (held <= (500 + 384) * cfg.n_experts_per_tok).all()  # real rows'
    stats = _moe_stats(jnp.float32(0), counts, cfg, pairs)
    slabs = np.clip(-(-held // slab), 1, pairs // slab)
    assert float(stats["moe/rows_gathered_share"]) == pytest.approx(
        100.0 * np.mean(slabs * slab / pairs))
    assert float(stats["moe/slab_fill_max"]) == pytest.approx(
        held.max() / slab)


@pytest.mark.parametrize("regime", ["balanced", "all_held"])
def test_rows_spread_over_eight_devices_take_the_same_slab(regime):
    """On a data mesh the sort is over every device's rows, `order[:slab]`
    a gather GSPMD partitions and the predicate one replicated scalar: the
    layer and its gradients are the one-device program's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs the eight virtual CPU devices of tests/conftest.py")
    cfg = _cfg()
    h, blk = _layer(cfg, REGIMES[regime])
    h = h.reshape(8, T // 8, D)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))

    def loss(h, blk):
        out, _, counts = tfm._mlp_moe(h, blk, cfg)
        return jnp.sum(jnp.square(out)), counts.sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    with jax.default_matmul_precision("highest"):
        (want, held_want), (dh_want, dblk_want) = jax.jit(grad)(h, blk)
        spread = jax.device_put(h, NamedSharding(mesh, P("data")))
        whole = jax.device_put(blk, NamedSharding(mesh, P()))
        (got, held), (dh, dblk) = jax.jit(grad)(spread, whole)
    assert len(dh.sharding.device_set) == 8
    assert int(held) == int(held_want)
    assert (int(held) > 512) == (regime == "all_held")
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(dh, dh_want, **TOL)
    for name in blk:
        np.testing.assert_allclose(
            dblk[name], dblk_want[name], err_msg=name, **TOL)

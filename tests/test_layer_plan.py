"""The layer plan (`ModelConfig.plan`: prefix + unit x repeats, a layer a
tuple of residual branches) and the one view of the stacked leaves every
program walks it through, on the toys of the six benchmark configurations
and on two units no family has: the counting properties against the
formulae they had before there was a plan, the view's shapes and each
layer's leaves against the stored stacking alone, the cache's populations,
the stored tree's names and shapes as recorded at the parent of PR 43, and
— for the two made-up units — the programs against the layers applied one
by one in plain Python.
"""

import dataclasses
import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import DENSE_PREFIX, ModelConfig
from areal_tpu.models.linear_attention import LINEAR_LEAVES, linear_attn_forward
from areal_tpu.models.mamba import SSM_LEAVES, ssm_forward
from areal_tpu.ops.attention import packed_attention
from benchmark import files
from benchmark import run as bench_run

TOL = dict(rtol=5e-4, atol=5e-4)
BENCHMARK_CONFIGS = {
    "q1p5b": "qwen2.5-math-1.5b.json",
    "q7b": "r1-distill-qwen-7b-l8.json",
    "olmoe": "olmoe-1b-7b-0125-l3.json",
    "q3next": "qwen3-next-80b-a3b-l4-e64.json",
    "glm47f": "glm-4.7-flash-l7-e8.json",
    "nemo3n": "nemotron-3-nano-30b-a3b-l9-e16.json",
}
MADE_UP = ("pattern_E*M_x2", "hybrid_interval_2")
NAMES = (*BENCHMARK_CONFIGS, *MADE_UP)


def _cfg(name) -> ModelConfig:
    if name == "pattern_E*M_x2":  # an expert layer first, one '*' a unit
        return dataclasses.replace(
            _cfg("nemo3n"), n_layers=6, layer_pattern="E*M" * 2)
    if name == "hybrid_interval_2":  # ONE Gated DeltaNet layer a period
        return dataclasses.replace(
            _cfg("q3next"), n_layers=4, full_attn_interval=2)
    config, _ = bench_run.toy(
        files.load_json("configs", BENCHMARK_CONFIGS[name]),
        files.load_json("traffic", "straggler-tail.json"))
    return bench_run.model_config(config)


@functools.lru_cache(maxsize=None)
def _params(cfg, seed=0):
    """Random weights, every leaf moved off its initial zeros and ones so
    that a layer reading another layer's leaf cannot pass."""

    @jax.jit
    def draw():
        params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
        return jax.tree.unflatten(treedef, [
            w + 0.05 * jax.random.normal(k, w.shape, w.dtype)
            for w, k in zip(leaves, keys)])

    return draw()


# ------------------------------------------ (a) the counts, by the old formulae


def _old_counts(cfg):
    """The counting properties as `config.py` computed them at the parent
    of PR 43 (commit 750d69c), from the three fields alone."""
    pattern = cfg.layer_pattern
    unit = pattern
    for n in range(1, len(pattern) + 1):
        if len(pattern) % n == 0 and pattern[:n] * (len(pattern) // n) == pattern:
            unit = pattern[:n]
            break
    n_scan = cfg.n_layers - cfg.first_k_dense
    if pattern:
        n_periods = cfg.n_layers // len(unit)
    else:
        n_periods = n_scan // cfg.full_attn_interval
    is_hybrid = cfg.full_attn_interval > 1
    return dict(
        is_pattern=bool(pattern),
        is_hybrid=is_hybrid,
        n_scan_layers=n_scan,
        n_periods=n_periods,
        n_linear_layers=n_periods * (cfg.full_attn_interval - 1),
        n_ssm_layers=pattern.count("M"),
        n_moe_layers=(
            pattern.count("E") if pattern
            else (n_scan if cfg.n_experts > 0 else 0)),
        n_attn_layers=(
            pattern.count("*") if pattern else n_periods + cfg.first_k_dense),
        has_recurrent_state=is_hybrid or pattern.count("M") > 0,
    )


@pytest.mark.parametrize("name", NAMES)
def test_every_counting_property_keeps_its_value(name):
    cfg = _cfg(name)
    assert {k: getattr(cfg, k) for k in _old_counts(cfg)} == _old_counts(cfg)
    plan = cfg.plan
    assert len(plan.prefix) + plan.repeats * len(plan.unit) == cfg.n_layers
    assert cfg.plan is plan  # derived once


def test_the_plans_of_the_families():
    two = lambda mixer, mlp, n=1: ((mixer, mlp),) * n  # noqa: E731
    assert _cfg("q1p5b").plan.unit == two("attention", "mlp")
    assert _cfg("olmoe").plan.unit == two("attention", "moe")
    q3 = _cfg("q3next").plan
    assert q3.unit == two("gdn", "moe", 3) + two("attention", "moe")
    assert (q3.prefix, q3.repeats) == ((), 1)
    glm = _cfg("glm47f").plan
    assert (glm.prefix, glm.unit, glm.repeats) == (
        two("latent", "mlp"), two("latent", "moe"), 2)
    nemo = _cfg("nemo3n").plan
    kinds = {"M": ("ssm",), "E": ("moe",), "*": ("attention",)}
    assert nemo.unit == tuple(kinds[c] for c in "MEMEM*EME")
    assert _cfg("pattern_E*M_x2").plan == type(nemo)(
        (), (("moe",), ("attention",), ("ssm",)), 2)
    assert _cfg("hybrid_interval_2").plan == type(nemo)(
        (), two("gdn", "moe") + two("attention", "moe"), 2)


# ------------------------- (b) the view and a layer's leaves, by the stacking


@pytest.mark.parametrize("name", NAMES)
def test_the_view_splits_each_leaf_by_scan_step_and_layers_get_their_own(name):
    """From the stored stacking alone: a leaf stacked over N layers is
    viewed [repeats, N / repeats, ...] (as it is where that is one layer a
    step), and the leaves the layers are handed, put back in layer order,
    are the stored leaf."""
    cfg = _cfg(name)
    plan = cfg.plan
    blocks = _params(cfg)["blocks"]
    view = tfm._unit_view(cfg, blocks)
    assert set(view) == {n for n in blocks if not n.startswith(DENSE_PREFIX)}
    for leaf, w in view.items():
        per_step = blocks[leaf].shape[0] // plan.repeats
        assert blocks[leaf].shape[0] == per_step * plan.repeats
        want = blocks[leaf].shape if per_step == 1 else (
            plan.repeats, per_step, *blocks[leaf].shape[1:])
        assert w.shape == want, leaf
        if len(plan.unit) == 1:
            assert w is blocks[leaf]  # a unit of one: the identity

    handed = {leaf: [] for leaf in view}
    for step in range(plan.repeats):
        sliced = {leaf: w[step] for leaf, w in view.items()}
        for j, want_kind in enumerate(plan.unit):
            kind, index, blk = tfm._unit_layer(cfg, sliced, j)
            assert kind == want_kind
            assert index == {
                b: sum(b in k for k in plan.unit[:j]) for b in kind}
            assert "ln1" in blk and ("ln2" in blk) == (len(kind) == 2)
            for leaf, w in blk.items():
                handed[leaf].append(w)
    for leaf, ws in handed.items():
        np.testing.assert_array_equal(np.stack(ws), blocks[leaf], err_msg=leaf)

    lead = tfm._prefix_layers(cfg, blocks)
    assert [kind for kind, _, _ in lead] == list(plan.prefix)
    for i, (kind, index, blk) in enumerate(lead):
        assert index == dict.fromkeys(kind, i)  # today's prefixes: one kind
        assert blk and all(
            np.array_equal(w, blocks[DENSE_PREFIX + leaf][i])
            for leaf, w in blk.items())


@pytest.mark.parametrize("name", NAMES)
def test_a_layer_is_handed_the_leaves_of_its_branches_and_no_others(name):
    cfg = _cfg(name)
    view = tfm._unit_view(cfg, _params(cfg)["blocks"])
    step = {leaf: w[0] for leaf, w in view.items()}
    owned = {
        "attention": set(tfm._FULL_ATTN_LEAVES),
        "latent": set(tfm._LATENT_LEAVES) | {"wo"},
        "gdn": set(LINEAR_LEAVES),
        "ssm": set(SSM_LEAVES),
        "mlp": {"wg", "wu", "wd", "bproj", "bfc"},
        "moe": set(tfm._MOE_LEAVES),
    }
    for j, kind in enumerate(cfg.plan.unit):
        _, _, blk = tfm._unit_layer(cfg, step, j)
        allowed = set().union(*(owned[b] for b in kind))
        assert set(blk) - {"ln1", "ln2", "ln1_b", "ln2_b"} <= allowed
        for b in kind:  # and every stored leaf of the branch is there
            assert owned[b] & set(view) <= set(blk)


# ------------------------------------------------ (c) the cache's populations


@pytest.mark.parametrize("name", NAMES)
def test_the_cache_has_one_population_a_kind_of_branch(name):
    cfg = _cfg(name)
    old = _old_counts(cfg)
    b, s = 3, 40
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, b, s))
    shapes = {
        f.name: getattr(cache, f.name).shape
        for f in dataclasses.fields(cache) if getattr(cache, f.name) is not None}
    if cfg.kv_lora_rank:
        want = {"latent": (cfg.n_layers, b, s, cfg.latent_dim)}
    else:
        kv = (old["n_attn_layers"], b, s, cfg.n_kv_heads, cfg.head_dim)
        want = {"k": kv, "v": kv}
    if old["is_hybrid"]:
        nl = old["n_linear_layers"]
        want["state"] = (nl, b, cfg.linear_n_v_heads, cfg.linear_k_head_dim,
                         cfg.linear_v_head_dim)
        want["conv"] = (nl, b, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim)
    if old["n_ssm_layers"]:
        nm = old["n_ssm_layers"]
        want["state"] = (nm, b, cfg.ssm_n_heads, cfg.ssm_head_dim,
                         cfg.ssm_state_dim)
        want["conv"] = (nm, b, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim)
    assert shapes == want
    assert cache.s_max == s
    if "state" in want:
        assert cache.state.dtype == jnp.float32
    assert list(tfm._CACHE_FIELDS) == [
        f.name for f in dataclasses.fields(cache)]


@pytest.mark.parametrize("name", NAMES)
def test_one_refusal_reads_the_plan(name):
    cfg = _cfg(name)
    dense = name in ("q1p5b", "q7b", "olmoe")
    for serving in (False, True):
        refusal = tfm.plan_refusal(cfg, serving)
        assert (refusal is None) == dense
        if refusal:
            assert type(refusal) is (
                tfm.LatentLayoutError if cfg.kv_lora_rank
                else tfm.HybridLayoutError)
            assert ("serving plane" if serving else "data and fsdp") in str(
                refusal)


# ----------------------------- the stored tree, as recorded at the parent


def _tree_digest(cfg):
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    lines = sorted(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0])
    return len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


# (leaves, sha256[:16] of the sorted "path shape dtype" lines) of
# `init_params` at the parent of PR 43 (commit 750d69c), printed there by
# `_tree_digest`: names, stacking and the `dense_*` leaves do not move.
_PARENT_TREES = {
    "q1p5b": (14, 'a49c57d5df0b77e3'),
    "q7b": (15, '21b1fba76c289e0e'),
    "olmoe": (15, '848c9c0565798a0d'),
    "q3next": (28, '7f6fc445a36990ad'),
    "glm47f": (34, 'e1f132d5c391c3c0'),
    "nemo3n": (22, '738f419fcdde1d3d'),
    "pattern_E*M_x2": (22, '90c1f962c7953ecb'),
    "hybrid_interval_2": (28, '5db5be7f5b7bd5e5'),
}


@pytest.mark.parametrize("name", NAMES)
def test_the_stored_tree_is_the_parents(name):
    assert _tree_digest(_cfg(name)) == _PARENT_TREES[name]


# ------------- (d) the made-up units against their layers, one by one


def _plain_forward(params, cfg, kinds, tokens, segment_ids):
    """The model as a plain Python loop over `kinds` (a layer's branches,
    spelled out by the test), each leaf indexed by how many EARLIER layers
    have a branch that owns it — no plan, no view, no scan."""
    blocks = params["blocks"]
    owners = {
        "attention": tfm._FULL_ATTN_LEAVES,
        "gdn": LINEAR_LEAVES,
        "ssm": SSM_LEAVES,
        "moe": tfm._MOE_LEAVES,
    }
    positions = tfm.positions_from_segments(segment_ids)
    x = tfm._embed(params, cfg, tokens, positions)
    cos, sin = tfm.rope_cos_sin(positions, tfm._rope_dim(cfg), cfg.rope_theta)
    for i, kind in enumerate(kinds):
        for branch, ln in zip(kind, ("ln1", "ln2")):
            nth = sum(branch in k for k in kinds[:i])
            blk = {n: blocks[n][nth] for n in owners[branch] if n in blocks}
            h = tfm._norm(x, blocks[ln][i], None, cfg)
            if branch == "attention":
                q, k, v = tfm._block_kv(h, blk, cfg, cos, sin)
                a = packed_attention(q, k, v, segment_ids, causal=True)
                out = tfm._attn_out(
                    a.reshape(*h.shape[:2], cfg.q_dim), blk, cfg,
                    tfm._attn_gate(h, blk, cfg))
            elif branch == "gdn":
                out = linear_attn_forward(h, blk, cfg, segment_ids)
            elif branch == "ssm":
                out = ssm_forward(h, blk, cfg, segment_ids)
            else:
                out = tfm._mlp_moe(h, blk, cfg, valid=segment_ids > 0)[0]
            x = x + out
    return tfm._head(params, cfg, tfm._final_norm(params, cfg, x))


_SPELLED_OUT = {
    "pattern_E*M_x2": [("moe",), ("attention",), ("ssm",)] * 2,
    "hybrid_interval_2": [("gdn", "moe"), ("attention", "moe")] * 2,
}


@pytest.mark.parametrize("name", MADE_UP)
def test_forward_is_the_layers_applied_one_by_one(name):
    cfg = _cfg(name)
    params = _params(cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 48)), jnp.int32)
    seg = np.ones((2, 48), np.int32)
    seg[0, 30:] = 2  # two sequences packed in a row
    seg[1, 40:] = 0  # and a padded tail
    seg = jnp.asarray(seg)
    want = jax.jit(lambda p: _plain_forward(
        p, cfg, _SPELLED_OUT[name], tokens, seg))(params)

    @jax.jit
    def program(p):  # every layer under the remat policy, as a train step
        x, _, counts = tfm.hidden_states(
            p, cfg, tokens, seg, remat="full", with_moe_counts=True)
        return tfm._head(p, cfg, x), counts

    got, counts = program(params)
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(got)[real], np.asarray(want)[real], **TOL)
    assert counts.shape == (cfg.n_moe_layers, cfg.n_experts)
    # Every real (row, choice) pair is counted in every expert layer.
    held = np.asarray(counts).sum(axis=-1)
    assert (0 < held).all() and (held <= real.sum() * cfg.n_experts_per_tok).all()


@pytest.mark.parametrize("name", MADE_UP)
def test_prefill_and_four_decode_steps_are_forwards_logits(name):
    cfg = _cfg(name)
    params = _params(cfg)
    rng = np.random.default_rng(2)
    b, p, steps, window = 2, 12, 4, 32
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, p + steps)), jnp.int32)
    want = np.asarray(jax.jit(
        lambda w: tfm.forward(w, cfg, tokens, jnp.ones_like(tokens)))(params))
    logits, cache = jax.jit(lambda w: tfm.prefill(
        w, cfg, tokens[:, :p], jnp.ones((b, p), jnp.int32),
        tfm.init_kv_cache(cfg, b, window)))(params)
    np.testing.assert_allclose(np.asarray(logits), want[:, p - 1], **TOL)
    step = jax.jit(lambda tok, pos, cache, slot: tfm.decode_step(
        params, cfg, tok, pos, cache, slot, jnp.zeros((b,), jnp.int32),
        with_moe_counts=True))
    for i in range(steps):
        at = jnp.full((b,), p + i, jnp.int32)
        logits, cache, counts = step(tokens[:, p + i], at, cache, p + i)
        np.testing.assert_allclose(np.asarray(logits), want[:, p + i], **TOL)
        assert counts.shape == (cfg.n_moe_layers, cfg.n_experts)
    assert cache.k.shape[0] == cfg.n_attn_layers
    assert cache.state.shape[0] == (cfg.n_linear_layers or cfg.n_ssm_layers)

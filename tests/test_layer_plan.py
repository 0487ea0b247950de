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
# The plans the PROGRAMS are walked over against a hand-written loop: the
# made-up units and one of them with every norm on its branch's output.
POST_NORM = "hybrid_interval_2_post_norm"
WALKED = (*MADE_UP, POST_NORM)


def _cfg(name) -> ModelConfig:
    if name == "pattern_E*M_x2":  # an expert layer first, one '*' a unit
        return dataclasses.replace(
            _cfg("nemo3n"), n_layers=6, layer_pattern="E*M" * 2)
    if name == POST_NORM:  # x + norm(f(x)) where every other plan norms x
        return dataclasses.replace(
            _cfg("hybrid_interval_2"), branch_norm="output")
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
    have a branch that owns it — no plan, no view, no scan.  With
    `cfg.branch_norm` "output" the loop is x + norm(f(x)), f fed x."""
    post = cfg.branch_norm == "output"
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
            h = x if post else tfm._norm(x, blocks[ln][i], None, cfg)
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
            x = x + (tfm._norm(out, blocks[ln][i], None, cfg) if post else out)
    return tfm._head(params, cfg, tfm._final_norm(params, cfg, x))


_SPELLED_OUT = {
    "pattern_E*M_x2": [("moe",), ("attention",), ("ssm",)] * 2,
    "hybrid_interval_2": [("gdn", "moe"), ("attention", "moe")] * 2,
    POST_NORM: [("gdn", "moe"), ("attention", "moe")] * 2,
}


@pytest.mark.parametrize("name", WALKED)
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


@pytest.mark.parametrize("name", WALKED)
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
        with_counts=True))
    for i in range(steps):
        at = jnp.full((b,), p + i, jnp.int32)
        logits, cache, counts = step(tokens[:, p + i], at, cache, p + i)
        np.testing.assert_allclose(np.asarray(logits), want[:, p + i], **TOL)
        assert counts["moe"].shape == (cfg.n_moe_layers, cfg.n_experts)
    assert cache.k.shape[0] == cfg.n_attn_layers
    assert cache.state.shape[0] == (cfg.n_linear_layers or cfg.n_ssm_layers)


# ---------------- (e) the seam: a kind is one record, every reader reads it


def _benchmark_toys():
    """{configuration: its traffic} of every configuration the benchmark
    holds, from `BENCHMARK.json` (the first cell that runs it)."""
    bench = files.benchmark_json()
    traffic = {}
    for cell in bench["workloads"]:
        traffic.setdefault(cell["config"], cell["traffic"])
    return {c["name"]: traffic[c["name"]] for c in bench["configs"]}


def _toy(name) -> ModelConfig:
    config, _ = bench_run.toy(
        files.load_json("configs", name + ".json"),
        files.load_json("traffic", _benchmark_toys()[name] + ".json"))
    return bench_run.model_config(config)


# (matmul_params, flops_train(cfg, 4096, 3 * 1024**2), flops_generate(cfg,
# [512, 300], [128, 700])) of `base/monitor.py` at the parent of PR 57
# (commit c41906c), each file's toy: printed there, before the counts were
# sums over the records.
_PARENT_FLOPS = {
    "qwen2.5-math-1.5b": (106496, 7449083904.0, 800313344.0),
    "r1-distill-qwen-7b-l8": (106496, 7449083904.0, 800313344.0),
    "olmoe-1b-7b-0125-l3": (91136, 7071596544.0, 749932544.0),
    "qwen3-next-80b-a3b-l4-e64": (172800, 6662651904.0, 792287232.0),
    "glm-4.7-flash-l7-e8": (135936, 14212399104.0, 1460634624.0),
    "nemotron-3-nano-30b-a3b-l9-e16": (179200, 6819938304.0, 813279232.0),
    "mellum2-12b-a2.5b-l4-e16": (108544, 12331253760.0, 1258037248.0),
    "lfm2-8b-a1b-e8": (203712, 7422345216.0, 893678592.0),
    "granite-4.0-h-micro-l10": (234496, 8178892800.0, 994650112.0),
    "minicpm-sala-l4-v8": (215040, 6190792704.0, 814128384.0),
    # PR 59's configuration has no count at that parent: the records' sums
    # as PR 59 first printed them (222,720 matmul parameters as
    # `benchmark/peaks_gdnd.py` counts the toy + 3 layers x 4 heads x 3 x 12
    # x 24 of the recurrence).
    "olmo-hybrid-7b-l4-v8": (233088, 8144289792.0, 990031872.0),
    # PR 64's neither: the records' sums as PR 64 first printed them (the
    # toy's two full layers with their indexers, three sliding ones, the
    # selected keys of a query capped at index_topk 256 and the band of 9).
    "dots3-note-prev-l5-e8-h8": (163648, 8366456832.0, 1001366272.0),
    # PR 68's neither: as PR 68 first printed them.  Generation by diffusion
    # over blocks: the train stack runs over two streams of slots and the
    # head over one, a block takes T + 1 = 3 forwards (`base/monitor.py`).
    "sdar-30b-a3b-chat-l8-e16": (70656, 12331253760.0, 1350709248.0),
}


def test_the_flops_are_pinned_for_every_configuration_of_the_benchmark():
    assert set(_PARENT_FLOPS) == set(_benchmark_toys())


@pytest.mark.parametrize("name", sorted(_PARENT_FLOPS))
def test_every_reader_finds_a_kind_in_its_record(name):
    from areal_tpu.base import monitor

    cfg = _toy(name)
    plan, table = cfg.plan, tfm.BRANCHES
    kinds = tfm.branches_of(cfg)
    assert {b for kind in plan.kinds for b in kind} == set(kinds)

    # A block leaf is stacked over the layers of exactly the kinds whose
    # records list it (a norm is every layer's).
    blocks = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))["blocks"]
    for leaf, w in blocks.items():
        name_ = leaf.removeprefix(DENSE_PREFIX)
        if name_.startswith("ln"):
            continue
        owners = [n for n, b in table.items() if name_ in b.leaves]
        assert owners, leaf
        layers = (plan.in_prefix(*owners) if leaf.startswith(DENSE_PREFIX)
                  else plan.repeats * plan.in_unit(*owners))
        assert w.shape[0] == layers, leaf
        for kind in plan.kinds:
            assert tfm._owns(kind, name_) == any(b in owners for b in kind)

    # Every population of the cache is what the records say its layers keep.
    b, s = 3, 40
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, b, s))
    for f in dataclasses.fields(cache):
        keepers = [n for n, rec in kinds.items() if f.name in rec.cache]
        got = getattr(cache, f.name)
        assert (got is None) == (not keepers), f.name
        for n in keepers:
            shape, dtype = table[n].cache[f.name](cfg, b, s, cfg.dtype)
            assert got.shape == (plan.count(*keepers), *shape), f.name
            assert got.dtype == dtype, f.name

    # The FLOP counts are the parent's.
    params, train, gen = _PARENT_FLOPS[name]
    assert monitor.matmul_params(cfg) == params
    assert monitor.flops_train(cfg, 4096, 3 * 1024.0**2) == pytest.approx(
        train, rel=1e-12)
    assert monitor.flops_generate(
        cfg, [512, 300], [128, 700]) == pytest.approx(gen, rel=1e-12)


FAKE = "fake"


def _fake_kind():
    """A mixer no file under `areal_tpu/` knows: y = h + bias, and the
    normed input of a row's last token as its one row of `state`."""
    from areal_tpu.models.branches import Branch, HybridLayoutError, Refusal

    def init(cfg, key, n, dense):
        return {"fk_bias": dense(key, (n, cfg.hidden_dim), 1)}

    def last_token(h, segment_ids):
        idx = jnp.arange(segment_ids.shape[-1])
        last = jnp.max(jnp.where(segment_ids > 0, idx, 0), axis=-1)
        return jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]

    def packed(ctx, h, blk):
        left = {}
        if ctx.with_state:
            left["state"] = last_token(h, ctx.segment_ids).astype(jnp.float32)
        return h + blk["fk_bias"], left

    def step(ctx, h, blk, cache, li):
        state = jax.lax.dynamic_update_index_in_dim(
            cache.state, h[:, 0].astype(jnp.float32), li, axis=0)
        return h + blk["fk_bias"], dataclasses.replace(cache, state=state), {}

    return Branch(
        leaves=("fk_bias",), init=init, packed=packed, step=step,
        cache={"state": lambda cfg, batch, s_max, dtype: (
            (batch, cfg.hidden_dim), jnp.float32)},
        refusal=Refusal(
            HybridLayoutError, "a fake mixer runs on one device only",
            "a fake mixer has no slot on the serving plane"),
        matmul_params=lambda cfg: cfg.hidden_dim,
    )


@pytest.fixture
def fake_cfg(monkeypatch):
    """The kind registered from the test alone: its record in the table,
    its character among the window pattern's, its leaf's sharding rule."""
    from jax.sharding import PartitionSpec

    from areal_tpu.models import config
    from areal_tpu.parallel import sharding

    monkeypatch.setitem(tfm.BRANCHES, FAKE, _fake_kind())
    monkeypatch.setitem(config._WINDOW_KINDS, "X", FAKE)
    monkeypatch.setitem(sharding._BLOCK_RULES, "fk_bias", PartitionSpec())
    return dataclasses.replace(
        _cfg("q1p5b"), n_layers=4, window_pattern="XFXF")


def test_a_kind_registered_from_outside_runs_every_program(fake_cfg):
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base import monitor
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    cfg = fake_cfg
    assert cfg.plan.unit == ((FAKE, "mlp"), ("attention", "mlp"))
    params = _params(cfg)
    assert params["blocks"]["fk_bias"].shape == (2, cfg.hidden_dim)
    rng = np.random.default_rng(3)
    b, p, steps, window = 2, 12, 3, 32
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, p + steps)), jnp.int32)
    want = np.asarray(jax.jit(
        lambda w: tfm.forward(w, cfg, tokens, jnp.ones_like(tokens)))(params))
    # The bias is read: without it the logits move.
    flat = {**params, "blocks": {
        **params["blocks"],
        "fk_bias": jnp.zeros_like(params["blocks"]["fk_bias"])}}
    assert not np.allclose(want, np.asarray(
        tfm.forward(flat, cfg, tokens, jnp.ones_like(tokens))), atol=1e-3)
    logits, cache = jax.jit(lambda w: tfm.prefill(
        w, cfg, tokens[:, :p], jnp.ones((b, p), jnp.int32),
        tfm.init_kv_cache(cfg, b, window)))(params)
    np.testing.assert_allclose(np.asarray(logits), want[:, p - 1], **TOL)
    assert cache.state.shape == (2, b, cfg.hidden_dim)
    assert cache.k.shape[0] == 2 and cache.conv is None
    step = jax.jit(lambda tok, pos, cache, slot: tfm.decode_step(
        params, cfg, tok, pos, cache, slot, jnp.zeros((b,), jnp.int32)))
    for i in range(steps):
        before = np.asarray(cache.state)
        logits, cache = step(
            tokens[:, p + i], jnp.full((b,), p + i, jnp.int32), cache, p + i)
        np.testing.assert_allclose(np.asarray(logits), want[:, p + i], **TOL)
        assert not np.array_equal(before, np.asarray(cache.state))
    assert monitor.matmul_params(cfg) == (
        monitor.matmul_params(dataclasses.replace(cfg, window_pattern="FFFF"))
        - 2 * tfm.BRANCHES["attention"].matmul_params(cfg)
        + 2 * cfg.hidden_dim)

    # The generator's static program, and the serving plane's refusal.
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4,
        kv_page_size=8)
    g = GenerationHyperparameters(n=1, max_new_tokens=steps, greedy=True)
    toks, _, gen_len = engine.static_rollout(
        [np.asarray(tokens[r, :p]) for r in range(b)], g, jax.random.PRNGKey(0))
    assert gen_len.tolist() == [steps] * b
    assert toks[:, 0].tolist() == np.argmax(want[:, p - 1], axis=-1).tolist()
    for serving, words in ((True, "no slot on the serving plane"),
                           (False, "one device only")):
        refusal = tfm.plan_refusal(cfg, serving)
        assert type(refusal) is tfm.HybridLayoutError and words in str(refusal)
    with pytest.raises(tfm.HybridLayoutError, match="no slot on the serving"):
        tfm.init_paged_kv_cache(cfg, 8, 8)

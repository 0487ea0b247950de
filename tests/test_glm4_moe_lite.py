"""GLM-4.7-Flash (zai-org/GLM-4.7-Flash, `glm4_moe_lite`: the deepseek_v3
block) at toy size on the CPU, seeded random weights, fp32: latent
attention (MLA) with one cache row a token and an absorbed decode step, a
sigmoid router with an untrained choice bias, an ungated shared expert, a
leading dense layer outside the layer scan, one expert-parallel rank's
share — against the plain reference of
`benchmark/references/glm4_moe_lite.py` (materialised attention, no cache),
through the train forward over packed rows, the static prefill + decode
through the latent cache, and the gradients; the shares of all ranks
against the uncut layer; the HF reader both ways; the sharding rules; the
named refusals; and that every other family still lowers to the program
it had.  Logits and log-probabilities are compared, never sampled tokens.

Tolerances: TOL (5e-4) is fp32 matmul reassociation through three layers
at hidden size 64 — the absorbed form multiplies in another order, and
the packed row runs three segments in one score matrix; the gradient bound
(2e-3 of a leaf's largest entry) is what the recomputed forward under
`jax.checkpoint` moves; the reference's own fp32 bound (1e-4 mean, 1e-3
max on log-probs) must FAIL the router or the cache a precision lower.
"""

import dataclasses
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig, tiny_config
from areal_tpu.models.hf import registry
from areal_tpu.ops import attention as attn_ops
from areal_tpu.parallel import sharding
from benchmark import files, peaks_mla
from benchmark import run as bench_run
from benchmark.references import glm4_moe_lite as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "glm-4.7-flash-l7-e8.json"
FAMILY = registry.HF_FAMILIES["glm4_moe_lite"]


def _toy_hf(held=4):
    """The benchmark configuration's keys at its `toy` sizes; `held`
    experts of the router's 8 (8: the whole layer, no share)."""
    config = files.load_json("configs", CONFIG)
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout64-1k.json"))
    config["n_routed_experts"] = held
    if held == 8:
        del config["share"]
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales (the two latent norms
    among them), so that a norm left out cannot pass."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = [n for n in p["blocks"] if "ln" in n or "norm" in n]
    for k, name in zip(
            jax.random.split(jax.random.PRNGKey(seed + 1), len(names)), names):
        leaf = p["blocks"][name]
        p["blocks"][name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
    return p


@pytest.fixture(scope="module")
def params(cfg):
    return _params(cfg)


def _sequences(cfg, lens=(70, 50, 30), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _sparse_layer(blocks, i):
    return {k: v[i] for k, v in blocks.items()
            if not k.startswith(tfm.DENSE_PREFIX)}


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
        "norm_topk_prob": True, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "rope_theta": 1000000, "rms_norm_eps": 1e-05,
        "num_nextn_predict_layers": 1, "max_position_embeddings": 202752,
        "tie_word_embeddings": False, "model_type": "glm4_moe_lite",
    }
    assert {k: config[k] for k in published} == published
    group = config["benchmark"]
    assert sorted(group["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (7, 8)
    share = config["share"]
    assert (share["chips_per_layer"], share["rank"]) == (8, 0)
    assert share["router_num_experts"] == 64
    assert config["vocab_size"] * 8 == share["published_vocab_size"] == 154880
    assert group["weights_seed"] == 38 and group["reference"] == "glm4_moe_lite"
    assert any("num_nextn_predict_layers" in n for n in group["notes"])
    cfg = bench_run.model_config(config)
    assert cfg.is_latent and cfg.first_k_dense == 1 and cfg.latent_dim == 576
    assert (cfg.n_experts, cfg.router_width, cfg.expert_offset) == (8, 64, 0)
    assert cfg.moe_score_func == "sigmoid" and not cfg.shared_expert_gated
    assert cfg.moe_aux_loss_coef == 0.0 and cfg.moe_routed_scale == 1.8
    # 804.9 M parameters: the arithmetic of `reduced`, from the shapes.
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == pytest.approx(804.9e6, rel=1e-3)
    assert peaks_mla.attn_params(cfg) == pytest.approx(21.76e6, rel=1e-3)
    # One leading stack axis on every block leaf: [1, ...] or [6, ...].
    for name, x in shapes["blocks"].items():
        assert x.shape[0] == (1 if name.startswith("dense_") else 6), name


def test_config_both_ways_and_a_published_config_is_the_whole_model(cfg):
    assert registry.infer_model_type(cfg) == "glm4_moe_lite"
    back = FAMILY.config_from_hf(FAMILY.config_to_hf(cfg))
    # The draw of random weights is the benchmark configuration's
    # (`benchmark.assumed.router_bias_init_std`: read, and handed to
    # `init_params`); a checkpoint's config.json states none.
    assert cfg.router_bias_init_std == 0.02 and back.router_bias_init_std == 0
    assert dataclasses.replace(
        back, param_dtype="float32", router_bias_init_std=0.02) == cfg
    whole = _cfg(held=8)  # no share group: 1 of 1
    assert not whole.expert_share and whole.router_width == 8
    assert "share" not in FAMILY.config_to_hf(whole)


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("hidden_act", "gelu"),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        FAMILY.config_from_hf(dict(_toy_hf(), **{key: value}))


def test_unequal_qk_and_v_widths_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="one width"):
        FAMILY.config_from_hf(dict(_toy_hf(), v_head_dim=16))


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d, h = cfg.hidden_dim, cfg.n_q_heads
    # Layer 0 is dense, layers 1-2 sparse; the published shapes.
    pre = "model.layers.{}.self_attn."
    assert sd[pre.format(0) + "q_a_proj.weight"].shape == (cfg.q_lora_rank, d)
    assert sd[pre.format(1) + "q_b_proj.weight"].shape == (
        h * cfg.head_dim, cfg.q_lora_rank)
    assert sd[pre.format(2) + "kv_a_proj_with_mqa.weight"].shape == (
        cfg.latent_dim, d)
    assert sd[pre.format(1) + "kv_b_proj.weight"].shape == (
        h * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (
        cfg.intermediate_dim, d)
    assert "model.layers.0.mlp.gate.weight" not in sd
    assert sd["model.layers.1.mlp.gate.weight"].shape == (8, d)
    assert sd["model.layers.2.mlp.gate.e_score_correction_bias"].shape == (8,)
    assert sd["model.layers.1.mlp.shared_experts.up_proj.weight"].shape == (
        cfg.shared_expert_dim, d)
    assert "model.layers.1.mlp.experts.3.down_proj.weight" in sd
    assert "model.layers.1.mlp.experts.4.down_proj.weight" not in sd  # held 0-3
    assert not any(k.startswith("model.layers.3.") for k in sd)  # no MTP layer
    # The rope columns: HF pair (2j, 2j + 1) is our (j, j + r / 2).
    nope, r, c = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    ours = np.asarray(params["blocks"]["wkv_a"][0]).T  # [latent | rope, D]
    theirs = sd[pre.format(1) + "kv_a_proj_with_mqa.weight"]
    np.testing.assert_array_equal(theirs[:c], ours[:c])
    np.testing.assert_array_equal(theirs[c:][0::2], ours[c: c + r // 2])
    np.testing.assert_array_equal(theirs[c:][1::2], ours[c + r // 2:])
    q_b = sd[pre.format(1) + "q_b_proj.weight"].reshape(h, nope + r, -1)
    ours = np.asarray(params["blocks"]["wq_b"][0]).T.reshape(h, nope + r, -1)
    np.testing.assert_array_equal(q_b[:, :nope], ours[:, :nope])
    np.testing.assert_array_equal(q_b[:, nope::2], ours[:, nope: nope + r // 2])
    # kv_b_proj is [k_nope | v] per head.
    kv_b = sd[pre.format(1) + "kv_b_proj.weight"].reshape(h, -1, c)
    np.testing.assert_array_equal(
        kv_b[:, :nope].reshape(-1, c), np.asarray(params["blocks"]["wk_b"][0]).T)


def test_interleaved_rope_on_hf_columns_is_rotate_half_on_ours(cfg):
    """What the converter's permutation rests on: rotating interleaved
    pairs and then permuting equals permuting and then rotating halves, so
    q_pe . k_pe is the same sum in either layout."""
    r = cfg.qk_rope_head_dim
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, r)).astype(np.float32)
    ang = rng.normal(size=(5, r // 2)).astype(np.float32)
    pairs = x.reshape(5, r // 2, 2)
    rot = np.stack([
        pairs[..., 0] * np.cos(ang) - pairs[..., 1] * np.sin(ang),
        pairs[..., 1] * np.cos(ang) + pairs[..., 0] * np.sin(ang),
    ], axis=-1).reshape(5, r)
    order = registry._glm_rope_order(cfg)
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)
    ours = x[:, order]
    half = np.concatenate([-ours[:, r // 2:], ours[:, : r // 2]], -1)
    np.testing.assert_allclose(rot[:, order], ours * cos + half * sin, atol=1e-6)


# ------------------------------------------------ program against reference


@pytest.mark.parametrize("held", [4, 8])
def test_train_forward_over_packed_rows_matches_the_reference(held):
    """One packed row of three segments against the three run apart
    through the reference: positions and the causal mask restart at every
    segment start, the dense layer runs before the scanned sparse ones."""
    cfg = _cfg(held)
    params = _params(cfg)
    seqs = _sequences(cfg)
    tokens = jnp.asarray(np.concatenate(seqs + [np.zeros(10, np.int32)]))[None]
    seg = jnp.asarray(np.concatenate(
        [np.full(len(s), i + 1) for i, s in enumerate(seqs)]
        + [np.zeros(10)]).astype(np.int32))[None]
    got = np.asarray(tfm.forward(params, cfg, tokens, seg))[0]
    off = 0
    for s in seqs:
        want = np.asarray(reference.logits(params, cfg, s))
        np.testing.assert_allclose(got[off: off + len(s)], want, **TOL)
        off += len(s)


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        cfg, params):
    """Right-aligned prompts of unequal length through `prefill`
    (materialised attention), then six `decode_step`s (absorbed attention
    over the latent rows), against the reference's full forward pass of
    each row; the cache holds one row a token and no per-head k/v."""
    rng = np.random.default_rng(1)
    sp, new, plens = 40, 6, (40, 33, 17)
    rows = [rng.integers(0, cfg.vocab_size, p + new).astype(np.int32)
            for p in plens]
    want = [np.asarray(reference.logits(params, cfg, r)) for r in rows]
    prompt = np.zeros((3, sp), np.int32)
    for i, (r, p) in enumerate(zip(rows, plens)):
        prompt[i, sp - p:] = r[:p]
    plen = np.asarray(plens)
    seg = (np.arange(sp)[None] >= (sp - plen)[:, None]).astype(np.int32)
    cache = tfm.init_kv_cache(cfg, 3, 64)
    assert cache.k is None and cache.v is None and cache.state is None
    assert cache.latent.shape == (cfg.n_layers, 3, 64, cfg.latent_dim)
    assert cache.s_max == 64 and len(jax.tree.leaves(cache)) == 1
    logits, cache = tfm.prefill(
        params, cfg, jnp.asarray(prompt), jnp.asarray(seg), cache,
        use_flash=False)
    for i, p in enumerate(plens):
        np.testing.assert_allclose(logits[i], want[i][p - 1], **TOL)
    for t in range(new):
        tok = jnp.asarray([r[p + t] for r, p in zip(rows, plens)], jnp.int32)
        logits, cache, counts = tfm.decode_step(
            params, cfg, tok, jnp.asarray(plen + t, jnp.int32), cache, sp + t,
            jnp.asarray(sp - plen, jnp.int32), with_counts=True)
        assert counts["moe"].shape == (cfg.n_layers - 1, cfg.n_experts)  # sparse only
        for i, p in enumerate(plens):
            np.testing.assert_allclose(logits[i], want[i][p + t], **TOL)
    assert cache.k is None and cache.latent.shape[0] == cfg.n_layers


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "pallas"])
def test_absorbed_attention_equals_materialised_attention(
        cfg, params, use_kernel):
    """One layer, one new token a row over a window of earlier rows: the
    query carried into the latent space and scored against the rows
    themselves, the value up-projection after the sum, against keys and
    values built for every head — the same numbers in another order, as
    XLA ops and as the Pallas kernel (interpreted here)."""
    rng = np.random.default_rng(2)
    b, s = 2, 12
    blk = _sparse_layer(params["blocks"], 1)
    h = jnp.asarray(rng.normal(size=(b, s, cfg.hidden_dim)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    cos, sin = tfm.rope_cos_sin(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
    q, k, v, rows = tfm._latent_qkv(h, blk, cfg, cos, sin)
    want = attn_ops.packed_attention_reference(
        q, k, v, jnp.ones((b, s), jnp.int32), causal=True)[:, -1]
    want = want.reshape(b, 1, cfg.q_dim) @ blk["wo"]
    q_abs, row = tfm._latent_q_absorbed(
        h[:, -1:], blk, cfg, cos[:, -1:], sin[:, -1:])
    np.testing.assert_allclose(row[:, 0], rows[:, -1], **TOL)
    assert q_abs.shape == (b, 1, cfg.n_q_heads, cfg.latent_dim)
    cache = jnp.stack([jnp.zeros_like(rows), rows])  # layer 1 of 2

    def attend(lo):
        return attn_ops.latent_decode_attention(
            q_abs[:, 0], cache, 1, jnp.full((b,), lo, jnp.int32), s,
            cfg.kv_lora_rank, cfg.head_dim ** -0.5, use_kernel=use_kernel)

    summed = attend(0)
    assert summed.shape == (b, cfg.n_q_heads, cfg.kv_lora_rank)
    got = tfm._attn_out(summed.reshape(b, 1, -1), blk, cfg, absorbed=True)
    np.testing.assert_allclose(got, want, **TOL)
    # A window that starts later drops the earlier rows from the sum.
    assert float(jnp.abs(attend(5) - summed).max()) > 1e-3


@pytest.mark.parametrize("s_max,dtype", [
    (1280, "float32"), (1280, "bfloat16"), (384, "float32"), (40, "float32"),
])
def test_the_latent_decode_kernel_equals_the_xla_form(s_max, dtype):
    """The Pallas kernel (interpreted) against the XLA form over a stacked
    cache: two tiles of 640 slots, one of 384, a toy window in one piece;
    rows whose window starts late, ends inside the first tile, spans both,
    or is empty (exact zeros); heads padded to a sublane tile and dropped."""
    from areal_tpu.ops.pallas import latent_attention as kernel

    assert [kernel.block_s_for(n) for n in (1280, 384, 2048, 256, 40)] == [
        640, 384, 512, 256, 40]
    rng = np.random.default_rng(5)
    b, h, c, r = 4, 5, 128, 64
    cache = jnp.asarray(rng.normal(size=(3, b, s_max, c + r)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, c + r)), dtype)
    lo = jnp.asarray([0, 3, s_max // 3, 7], jnp.int32)
    hi = jnp.asarray([s_max, s_max // 2 - 1, s_max - 5, 7], jnp.int32)
    args = (q, cache, jnp.int32(2), lo, hi, c, (c + r) ** -0.5)
    want = attn_ops.latent_decode_attention(*args, use_kernel=False)
    got = attn_ops.latent_decode_attention(*args, use_kernel=True)
    assert got.shape == want.shape == (b, h, c) and got.dtype == q.dtype
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else TOL
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)
    assert float(jnp.abs(got[3]).max()) == 0.0  # the empty window
    # Another layer's rows give another answer: the index map reads `layer`.
    other = attn_ops.latent_decode_attention(
        q, cache, jnp.int32(0), lo, hi, c, (c + r) ** -0.5, use_kernel=True)
    assert float(jnp.abs(other[0] - got[0]).max()) > 1e-2


@pytest.mark.parametrize("mode", ["d2", "f2", "d2f2"])
def test_the_kernel_runs_per_device_on_a_mesh_that_spreads_the_rows(mode):
    """Rows are independent, so on a mesh whose batch axes spread them the
    kernel is `shard_map`ped over (data, fsdp) and each device runs it on
    its own rows of the stacked cache (interpreted here): the numbers of
    the XLA form, whichever way the rows are split."""
    from areal_tpu.ops.pallas import latent_attention as kernel

    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    rng = np.random.default_rng(9)
    b, h, c, r, s_max = 8, 5, 128, 64, 256
    cache = jnp.asarray(rng.normal(size=(2, b, s_max, c + r)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, h, c + r)), jnp.float32)
    lo = jnp.asarray(rng.integers(0, 40, size=b), jnp.int32)
    args = (q, cache, jnp.int32(1), lo, jnp.int32(200))
    want = attn_ops.latent_decode_attention(
        *args, c, (c + r) ** -0.5, use_kernel=False)
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import BATCH_AXES

    rows = sharding.named(mesh, P(BATCH_AXES))
    got = jax.jit(lambda *a: kernel.latent_decode_kernel_sharded(
        *a, mesh, n_value=c, scale=(c + r) ** -0.5))(
            jax.device_put(q, rows), cache, jnp.int32(1),
            jax.device_put(lo, rows), jnp.int32(200))
    assert len(got.sharding.device_set) == pc.world_size
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # Off a TPU backend the dispatch takes the XLA form for a mesh as for
    # one device; on one it takes the sharded kernel (the engine's choice).
    np.testing.assert_array_equal(
        attn_ops.latent_decode_attention(
            *args, c, (c + r) ** -0.5, use_kernel=mesh), want)


def test_gradients_match_the_reference(cfg, params):
    """d(sum of next-token log-probs)/d(params) through the scan under
    `jax.checkpoint` against autodiff of the plain reference; the router's
    choice bias gets none from either."""
    seq = _sequences(cfg, lens=(90,), seed=2)[0]
    toks = jnp.asarray(seq)

    def score(logits):
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return jnp.sum(jnp.take_along_axis(lp, toks[1:, None], axis=-1))

    def system(p):
        return score(tfm.forward(
            p, cfg, toks[None], jnp.ones((1, len(seq)), jnp.int32),
            remat="full")[0])

    got = jax.jit(jax.grad(system))(params)
    want = jax.jit(
        jax.grad(lambda p: score(reference.logits(p, cfg, seq))))(params)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        if "router_bias" in name:
            assert scale == 0 and float(jnp.abs(g).max()) == 0
            continue
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)


# ------------------------------------------------------------------ the router


def test_the_bias_chooses_and_the_score_weighs():
    """A bias that flips the choice: the experts are chosen by score +
    bias, weighted by their scores alone (renormalised, then scaled)."""
    cfg = _cfg(held=8, n_experts_per_tok=2)
    d = cfg.hidden_dim
    x = jnp.ones((1, d), jnp.float32)
    logits = jnp.asarray([2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0, -4.0])
    router = jnp.tile(logits[None] / d, (d, 1))
    blk = {"router": router, "router_bias": jnp.zeros((8,))}
    s = np.asarray(jax.nn.sigmoid(logits))
    top_w, top_idx, one_hot, aux = tfm._moe_route(x, blk, cfg)
    assert sorted(np.asarray(top_idx)[0]) == [0, 1] and float(aux) == 0.0
    np.testing.assert_allclose(
        sorted(np.asarray(top_w)[0]), sorted(1.8 * s[:2] / s[:2].sum()), rtol=1e-5)
    blk["router_bias"] = jnp.zeros((8,)).at[5].set(2.0)  # lifts expert 5
    top_w, top_idx, _, _ = tfm._moe_route(x, blk, cfg)
    assert sorted(np.asarray(top_idx)[0]) == [0, 5]
    pair = s[[0, 5]]  # weighted by SCORE: the bias is not in the weights
    got = dict(zip(np.asarray(top_idx)[0], np.asarray(top_w)[0]))
    np.testing.assert_allclose(
        [got[0], got[5]], 1.8 * pair / pair.sum(), rtol=1e-5)
    # The reference's router says the same.
    w = {"router": router, "router_bias": blk["router_bias"]}
    gates = np.asarray(reference._route(x, w, cfg))[0]
    np.testing.assert_allclose(gates[[0, 5]], 1.8 * pair / pair.sum(), rtol=1e-5)
    assert np.count_nonzero(gates) == 2


def test_the_bias_is_not_trained_and_keeps_no_moment(cfg):
    """A train step moves every matrix and leaves `router_bias` bit for bit
    (no gradient, no weight decay); Adam keeps no moment for it; every
    other family's optimizer is what it was."""
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.api.model_api import FinetuneSpec
    from areal_tpu.engines import train
    from areal_tpu.ops import functional as F
    from tests import fixtures

    assert train._trainable_mask(
        tfm.init_params(tiny_config(), jax.random.PRNGKey(0))) is None
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    engine = train.TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 8, 8))
    moments = [
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(engine.opt_state)[0]]
    assert any("wq_a" in m for m in moments)
    assert not any("router_bias" in m for m in moments)
    before = jax.tree.map(np.asarray, engine.get_params())
    sample = fixtures.random_sample(
        np.random.default_rng(0), ids=list("abcdefgh"),
        keys=("packed_input_ids", "prompt_mask"))
    sample.seqlens["prompt_mask"] = sample.seqlens["packed_input_ids"]
    sample.data["prompt_mask"] = np.zeros(
        len(sample.data["packed_input_ids"]), bool)
    stats = engine.train_batch(
        sample, MicroBatchSpec(), loss_fn=F.sft_loss,
        loss_weight_fn=F.sft_label_count, extra_keys=("prompt_mask",))
    assert np.isfinite(stats["loss"]) and stats["grad_norm"] > 0
    after = jax.tree.map(np.asarray, engine.get_params())
    np.testing.assert_array_equal(
        after["blocks"]["router_bias"], before["blocks"]["router_bias"])
    assert np.abs(before["blocks"]["router_bias"]).min() > 0  # drawn non-zero
    for name in ("router", "wq_a", "wk_b", "dense_wg", "ws_d", "wg"):
        assert (after["blocks"][name] != before["blocks"][name]).any(), name


# ----------------------------------------------- precision a step lower fails


def _system_logprobs(cfg, params, seq):
    logits = tfm.forward(
        params, cfg, jnp.asarray(seq)[None], jnp.ones((1, len(seq)), jnp.int32))
    lp = jax.nn.log_softmax(logits[0, :-1], axis=-1)
    return np.asarray(jnp.take_along_axis(lp, jnp.asarray(seq)[1:, None], 1))[:, 0]


@pytest.mark.parametrize("lower", ["lower", "lower:router", "lower:cache"])
def test_the_fp32_tolerance_fails_the_router_or_the_cache_a_precision_lower(
        cfg, params, lower):
    """Router scores rounded to bfloat16, or the latent rows to 8 bits,
    move log-probabilities by far more than the fp32 bound the CPU
    rehearsal holds the generator to; the system itself sits inside it."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    want = reference.next_token_logprobs(params, cfg, seq)
    low = reference.next_token_logprobs(params, cfg, seq, lower=lower)
    got = _system_logprobs(cfg, params, seq)
    tol = reference.TOLERANCE_FP32
    assert np.abs(got - want).mean() < tol["mean_abs"]
    assert np.abs(got - want).max() < tol["max_abs"]
    # ... and each control fails it, by its mean and by its maximum (the
    # router alone by the least: 2.7 times the mean bound on these weights).
    assert np.abs(low - want).mean() > 2 * tol["mean_abs"]
    assert np.abs(low - want).max() > 2 * tol["max_abs"]


def test_the_rows_the_generators_own_program_leaves_are_the_references(
        cfg, params):
    """`check_generator` builds a GeneratorEngine over the weights, runs
    ITS static decode program at 64 slots over prompts cut from the
    sequence, and finds (c_kv, roped k_pe) of every prompt and sampled
    token and layer in the cache that program left — in fp32 to
    rounding — and the program's own log-probs on the reference's."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    rollouts = reference.generator_rollouts(params, cfg, seq)
    assert len(rollouts) == 2  # the first and the last of the 64 slots
    n_new = 96 * 8 // 9
    for (toks, logps, rows), n_prompt in zip(rollouts, (8, 13)):
        assert len(toks) == n_prompt + n_new and len(logps) == n_new
        np.testing.assert_array_equal(toks[:n_prompt], seq[:n_prompt])
        assert rows.shape == (cfg.n_layers, len(toks), cfg.latent_dim)
    readings, problems = reference.check_generator(params, cfg, seq)
    assert problems == [] and readings["n_tokens"] == 2 * n_new
    assert readings["rows_rel_err_max"] < 1e-5
    assert readings["logprob_max_abs"] < reference.TOLERANCE_FP32["max_abs"]


@pytest.mark.parametrize("lower,refused", [
    ("lower", True), ("lower:cache", True), ("lower:router", False)])
def test_rows_kept_in_8_bits_are_refused_by_the_unrouted_layers(
        cfg, params, lower, refused):
    """The reference's own rows a precision lower against the reference
    proper, under the CHIP's limits: 8-bit rows read 0.03 in the layers no
    router has touched, three times the limit; the router's scores in
    bfloat16 change nothing there (and elsewhere only by flipped choices,
    which the system's own bfloat16 activations flip as often: no limit
    on the chip refuses it alone, the fp32 bound on the CPU does)."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    _, rows = reference._next_token_logprobs(params, cfg, seq)
    _, low = reference._next_token_logprobs(params, cfg, seq, lower)
    readings = reference.rows_readings(low, rows, cfg)
    problems = reference.rows_problems(readings, reference.ROWS_TOLERANCE)
    if refused:
        limit = reference.ROWS_TOLERANCE["rows_rel_err_unrouted"]
        assert readings["rows_rel_err_unrouted"] > 2.5 * limit
        assert "rows_rel_err_unrouted" in problems[0]
    else:
        assert readings["rows_rel_err_unrouted"] == 0.0


def test_a_cache_kept_in_8_bits_is_not_correct(cfg, params, monkeypatch):
    """What a later change might do for the bytes — the generator's latent
    rows stored as float8 — turns `next_token_logprobs` to NaN, which
    `checks.reference_check` reports as not `correct`: the cache is the
    one the ENGINE's program allocates."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    assert np.isfinite(reference.next_token_logprobs(params, cfg, seq)).all()
    inner = tfm.init_kv_cache

    def small(cfg, batch, s_max, dtype=None):
        return inner(cfg, batch, s_max, dtype=jnp.float8_e4m3fn)

    monkeypatch.setattr(tfm, "init_kv_cache", small)
    got = reference.next_token_logprobs(params, cfg, seq)
    assert np.isnan(got).all() and got.shape == (len(seq) - 1,)
    # A control computation checks nothing of the system's.
    assert np.isfinite(reference.next_token_logprobs(
        params, cfg, seq, lower="lower")).all()


# ------------------------------------------------- one rank's share of a layer


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(dispatch):
    """The guide's shares test: with the router's 8 experts over 4 ranks of
    2, the four partial MoE outputs — each with the ungated shared expert,
    which every rank computes alike, so counted once — sum to what the
    plain reference gives for the whole layer."""
    whole = _cfg(held=8, moe_dispatch=dispatch)
    params = _params(whole)
    blk = _sparse_layer(params["blocks"], 1)
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(2, 24, whole.hidden_dim)), jnp.float32)
    x = h.reshape(-1, whole.hidden_dim)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(
            x, reference._layer_weights(params["blocks"], 2, whole), whole)
        shared = (jax.nn.silu(x @ blk["ws_g"]) * (x @ blk["ws_u"])) @ blk["ws_d"]
    total, local_rows = 0.0, 0
    for rank in range(4):
        part = dataclasses.replace(
            whole, n_experts=2, n_router_experts=8, expert_offset=2 * rank)
        mine = dict(blk, **{n: blk[n][2 * rank: 2 * rank + 2]
                            for n in ("wg", "wu", "wd")})
        out, aux, counts = tfm._mlp_moe(h, mine, part)
        total = total + out.reshape(x.shape)
        local_rows += int(counts.sum())
        assert counts.shape == (2,) and float(aux) == 0.0  # no auxiliary loss
    assert local_rows == x.shape[0] * whole.n_experts_per_tok
    np.testing.assert_allclose(total - 3 * shared, want, **TOL)
    # ... and the whole layer in one piece is the same layer.
    np.testing.assert_allclose(
        tfm._mlp_moe(h, blk, whole)[0].reshape(x.shape), want, **TOL)


# -------------------------------------------------- sharding, refusals, counters


@pytest.mark.parametrize("mode", ["d2", "f2"])
def test_a_sharded_forward_equals_the_single_device_one(cfg, params, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    sharding.attn_dispatch(mesh, cfg)  # accepted
    assert sharding.check_divisibility(params, mesh) is None
    placed = sharding.shard_params(params, mesh)
    t = jnp.asarray(np.stack(_sequences(cfg, lens=(64,) * 4)))
    got = jax.jit(lambda p, t: tfm.forward(p, cfg, t, jnp.ones_like(t)))(
        placed, jax.device_put(t, sharding.named(mesh, sharding.batch_pspec())))
    want = tfm.forward(params, cfg, t, jnp.ones_like(t))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("mode", ["m2", "p2", "s2"])
def test_mesh_axes_not_tested_with_latent_attention_are_refused_by_name(
        cfg, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.LatentLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)
    sharding.attn_dispatch(mesh, tiny_config())  # every other model: fine


def test_the_serving_plane_refuses_latent_rows_by_name(cfg, params):
    with pytest.raises(tfm.LatentLayoutError, match="serving plane"):
        tfm.init_paged_kv_cache(cfg, 4, 16)
    with pytest.raises(tfm.LatentLayoutError, match="serving plane"):
        tfm.decode_step_ragged_paged(
            params, cfg, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            None, jnp.zeros((2, 2), jnp.int32), jnp.zeros((4,), jnp.int32))


def test_generate_refuses_the_serving_plane_and_reports_the_latent_cache(
        cfg, params):
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    sample = SequenceSample(
        keys={"packed_prompts"}, ids=["a", "b"],
        seqlens={"packed_prompts": [[6], [9]]},
        data={"packed_prompts": np.arange(8, 23, dtype=np.int32)},
    )
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4)
    g = GenerationHyperparameters(n=2, max_new_tokens=5, greedy=True)
    out = engine.generate(sample, MicroBatchSpec(), g)
    assert len(out.data["packed_input_ids"]) == 2 * (6 + 9) + 4 * 5
    pool = engine.last_pool_stats
    n_sparse = cfg.n_layers - cfg.first_k_dense
    assert pool["moe_decode_steps"] == 5
    assert pool["moe_rows_routed"] == 5 * 4 * cfg.n_experts_per_tok * n_sparse
    assert 0 < pool["moe_rows_local"] < pool["moe_rows_routed"]
    assert 1 <= pool["moe_experts_touched"] <= cfg.n_experts
    s_total = 256  # bucket_len(128 + 5)
    assert pool["latent_cache_bytes"] == (
        cfg.n_layers * 4 * s_total * cfg.latent_dim * 4)
    assert pool["kv_cache_bytes_as_heads"] == (
        cfg.n_layers * 4 * s_total * cfg.n_q_heads * 2 * cfg.head_dim * 4)
    assert "kv_cache_bytes" not in pool  # no per-head K/V is allocated
    for kwargs in (
        dict(inflight=True),  # forced
        dict(g=dataclasses.replace(g, n=3)),  # 6 requests > 4 slots
        dict(g=dataclasses.replace(g, stop=((5, 6),))),
        dict(g=dataclasses.replace(g, spec_decode_k=2)),
        dict(g=dataclasses.replace(g, max_new_tokens=4096)),
    ):
        gg = kwargs.pop("g", g)
        with pytest.raises(tfm.LatentLayoutError, match="serving plane"):
            engine.generate(sample, MicroBatchSpec(), gg, **kwargs)


@pytest.mark.parametrize("mode", ["d2", "f2"])
def test_static_generation_on_a_mesh_equals_one_device(cfg, params, mode):
    """Generation under data or fsdp sharding: the engine hands the decode
    step its MESH (never the slow form silently: on a TPU backend the
    kernel runs per device on its rows) and greedy tokens are one
    device's."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    prompts = [np.arange(5, 5 + n, dtype=np.int32) for n in (9, 6, 11, 7)]
    g = GenerationHyperparameters(n=1, max_new_tokens=6, greedy=True)
    out = {}
    for name in ("d1", mode):
        pc = ParallelConfig.from_str(name)
        mesh = make_mesh(pc, jax.devices()[: pc.world_size])
        engine = GeneratorEngine(
            cfg, sharding.shard_params(params, mesh), mesh,
            eos_token_id=cfg.vocab_size)
        assert engine._row_kernel is (None if name == "d1" else mesh)
        toks, logps, _ = engine.static_rollout(
            prompts, g, jax.random.PRNGKey(0))
        out[name] = (toks, logps)
    np.testing.assert_array_equal(out[mode][0], out["d1"][0])
    np.testing.assert_allclose(out[mode][1], out["d1"][1], **TOL)


def test_flops_and_bytes_follow_the_layer_kinds(cfg):
    from areal_tpu.base import monitor

    # The program's own count = the benchmark's: 3 latent-attention layers,
    # one dense MLP, two sparse ones at the held share, the head.
    assert sum(n for n, b in monitor._layers_of(cfg) if b.attn_flops) == cfg.n_layers == 3
    assert monitor.matmul_params(cfg) == pytest.approx(
        peaks_mla.matmul_params(cfg))
    # A GQA twin of the same head count and width counts more attention.
    gqa = dataclasses.replace(
        cfg, kv_lora_rank=0, q_lora_rank=0, qk_nope_head_dim=0,
        qk_rope_head_dim=0, v_head_dim=0, first_k_dense=0)
    assert tfm.BRANCHES["attention"].matmul_params(gqa) == 4 * cfg.hidden_dim * cfg.q_dim
    assert tfm.BRANCHES["latent"].matmul_params(cfg) == peaks_mla.attn_params(cfg)
    assert sum(n for n, b in monitor._layers_of(tiny_config()) if b.attn_flops) == tiny_config().n_layers
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    # Section "The cell" of ISSUE 38: what a decode step reads of the
    # attention weights and the whole 1,280-slot latent window, a layer.
    assert peaks_mla.attn_params(big) * 2 == pytest.approx(43.5e6, rel=0.01)
    assert 64 * 1280 * peaks_mla.latent_row_bytes(big) == pytest.approx(
        94.4e6, rel=0.01)
    assert peaks_mla.latent_row_bytes(big) == 1152
    assert peaks_mla.experts_per_token_held(big) == 0.5
    assert peaks_mla.experts_expected(big, 64) == pytest.approx(7.87, abs=0.01)
    # The latent row against per-head K/V: 17.8 times less.
    assert 20 * (256 + 256) * 2 / peaks_mla.latent_row_bytes(big) == (
        pytest.approx(17.8, abs=0.05))


# --------------------------- every other family lowers to the program it had


# sha256 of `lower(...).as_text()`, the results' names left out, as printed at
# the parent of PR 57 (commit c41906c; jax 0.9.0) — the texts PR 38 pinned at
# its parent (6613402), unchanged since but for the pattern's (below): the
# train gradient program on a one-device mesh (dense and
# olmoe are also pinned in tests/test_sharding.py) and prefill + one decode
# step through the cache.  To regenerate after a change that is MEANT to
# alter these programs: print `_program_sha(...)` below.
_PARENT_PROGRAMS = {
    ("dense", "grad"): "0ba9f358a62c9d167bdd7caf3492a5a8b2c10f10790a16df223feec83e91df5c",
    ("dense", "gen"): "171e3c9a89af7ad0c9b290ecde922fc1ebbf80fca2313386d163c3ea85c1a4f5",
    ("olmoe", "grad"): "392f49aacb622abbf54641066381932faae6e7a4430ee90155f0b5d1d97bdd9b",
    ("olmoe", "gen"): "a1caf27c449fb3bde99b484d36122a5c82d25da70d62c4f5babaac87c7d16dae",
    # PR 59 gives the hybrid's gradient program ONE more stat,
    # `linear_attn/rule_on_kernel` (at PR 59's parent, 6c2020a, the text
    # hashed f894b45f93ee4664...7805b05dd7d): regenerated after the loss,
    # every gradient leaf and the other stats — 34 outputs — were
    # `np.array_equal` between the two commits on a packed batch, the new
    # stat 0 on a CPU backend (CHANGES.md).  Its `gen` text, and what
    # `_gates` and `_layer_of` trace in every program here, are the parent's.
    # PR 65 gives it ONE more, `linear_attn/conv_on_kernel` (0 on a CPU
    # backend): with that stat taken out of the kind's `train_stats` the
    # text hashes 65a1883734fb02e0...d6fb77f581, PR 59's pin, to the letter
    # (CHANGES.md).
    ("hybrid", "grad"): "7fc21106c19e5d959343ccd290743503b9a49f4d20c9512d79e4f4b128bea3e4",
    ("hybrid", "gen"): "8c20c9e547199be117390301a3db79f058811586d057bddd75deb7769f3fa91e",
    # The Nemotron-H toy (tests/test_nemotron_h.py `_cfg()`), from PR 43 on.
    # At PR 43's parent (750d69c) its programs hashed dd2e0ac17f0794d4...
    # 55fb17b (grad) and 2b37b189ac4323f4...8966f1de (gen); PR 43's one
    # view leaves a leaf ONE layer of the unit owns unreshaped (the lone '*'
    # layer's: [P, ...], no longer [P, 1, ...] then [0]), which no one rule
    # could keep beside the hybrid's text.  Regenerated after loss, every
    # gradient leaf, prefill logits, every cache field and eight decode
    # steps were `np.array_equal` between the two commits (CHANGES.md).
    # PR 58 gives the gradient program ONE more stat, `ssm/chunks_on_kernel`
    # (at PR 58's parent, 6ead7dd, the text hashed e84b2254321de4ae...
    # 12cdcea2): regenerated after the loss, every gradient leaf and the 18
    # other stats were `np.array_equal` between the two commits on a packed
    # batch, the new stat 0 beside `ssm/chunks` 128 (CHANGES.md).
    # PR 65 gives it ONE more, `ssm/conv_on_kernel` (0 on a CPU backend):
    # with that stat taken out of the kind's `train_stats` the text hashes
    # a8176a3a89c6a275...3792bb321bb4f, PR 58's pin, to the letter
    # (CHANGES.md).
    ("pattern", "grad"): "ff987c4107ddcff23e9c2b7c7c27b9b9d01d5250e784c06fd3646fc6336993be",
    ("pattern", "gen"): "bd971236683b7a70284bd3c2e324b4a986b809ea89631ff0c8ed60c51aa4bbad",
}


def _other_toy(name):
    if name == "dense":
        return tiny_config()
    if name == "pattern":
        from tests.test_nemotron_h import _cfg as nemotron_toy

        return nemotron_toy()
    if name == "olmoe":
        from tests.test_olmoe import HF_TOY

        cfg = registry.HF_FAMILIES["olmoe"].config_from_hf(HF_TOY)
    else:
        config, _ = bench_run.toy(
            files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json"),
            files.load_json("traffic", "rollout64-512.json"))
        cfg = registry.HF_FAMILIES["qwen3_next"].config_from_hf(config)
    return dataclasses.replace(cfg, param_dtype="float32")


def _program_sha(cfg, program):
    ints = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    if program == "grad":
        from areal_tpu.api.model_api import FinetuneSpec
        from areal_tpu.engines.train import TrainEngine
        from areal_tpu.ops import functional as F

        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        engine = TrainEngine(
            cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), mesh,
            ftspec=FinetuneSpec(1, 8, 8))
        batch = {
            "tokens": ints, "segment_ids": ints, "positions": ints,
            "prompt_mask": jax.ShapeDtypeStruct((2, 128), jnp.bool_),
        }
        text = engine._get_grad_fn(F.sft_loss)[0].lower(
            engine.params, batch, jax.ShapeDtypeStruct((), jnp.float32)
        ).as_text()
    else:
        params = jax.eval_shape(
            lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))

        def f(params, prompt, seg, new):  # the module's name is in the text
            cache = tfm.init_kv_cache(cfg, 2, 64)
            logits, cache = tfm.prefill(params, cfg, prompt, seg, cache)
            return logits, tfm.decode_step(
                params, cfg, new, jnp.full((2,), 32, jnp.int32), cache, 32,
                jnp.zeros((2,), jnp.int32), with_counts=True)

        ints = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        text = jax.jit(f).lower(
            params, ints, ints, jax.ShapeDtypeStruct((2,), jnp.int32)).as_text()
    assert "stablehlo.dot_general" in text
    # The results' names are no part of the program (PR 57: the decode
    # step's counters went from a tuple's entry to a dict's, by name).
    text = re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name,program", sorted(_PARENT_PROGRAMS))
def test_every_other_family_lowers_to_the_parents_program(name, program):
    assert _program_sha(_other_toy(name), program) == _PARENT_PROGRAMS[
        (name, program)]


# ------------------------------------------- the decode loop compiled for v5e


@pytest.mark.parametrize("mode", ["d1", "d2", "f2"])
def test_the_decode_loop_compiles_for_v5e_without_a_copy_of_the_cache(
        v5e_chips, monkeypatch, mode):
    """Mosaic and XLA:TPU for real, at the cell's size (64 rows, a
    1,280-slot window, seven layers, the published widths), on one chip
    and with the rows spread over two (`shard_map`, data or fsdp): the
    Pallas kernel `latent_decode` compiles, and the loop reads and writes
    the stacked latent cache AS IT LIES — no copy or re-layout of a
    device's part of the cache or of a layer's window inside it.  As XLA
    ops the scores and the sum want the window in two layouts and XLA
    re-lays the whole cache twice an iteration (chip run, PR 38: 10.9 ms
    an iteration)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import BATCH_AXES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    b, sp, st = 64, 256, 1280
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, v5e_chips[: pc.world_size])

    def placed(x, spec):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding.named(mesh, spec))

    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    params = jax.tree.map(placed, shapes, sharding.param_pspecs(shapes))
    rows = placed(jax.ShapeDtypeStruct((b,), jnp.int32), P(BATCH_AXES))
    # What the engine hands the decode step (`_row_kernel`), and whether
    # the expert leaves can be read in place (not where fsdp splits them).
    row_kernel = None if pc.world_size == 1 else mesh

    def loop(params, tok, plen):
        cache = tfm.init_kv_cache(big, b, st, dtype=jnp.bfloat16)

        def body(state):
            step, tok, cache = state
            logits, cache = tfm.decode_step(
                params, big, tok, plen + step, cache, sp + step, sp - plen,
                experts_in_place=mode != "f2", row_kernel=row_kernel)
            return step + 1, jnp.argmax(logits, -1).astype(jnp.int32), cache

        return jax.lax.while_loop(
            lambda s: s[0] < 1024, body, (0, tok, cache))[1]

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(loop).lower(params, rows, rows).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "%latent_decode" in text
    for rows_here in {b, b // pc.world_size}:  # the whole or a device's part
        window = f"{rows_here},{st},{big.latent_dim}]"
        copies = [
            line.strip()[:160] for line in text.splitlines()
            if window in line.split(" = ")[-1].split("(")[0]
            and (" copy(" in line or " transpose(" in line
                 or " all-gather(" in line)
        ]
        assert not copies, copies[:3]


# --------------------------------- the cell, rehearsed on the CPU at toy size

# `glm47f-rollout64-1k` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

"""LFM2-MoE (LiquidAI/LFM2-8B-A1B, `lfm2_moe`: gated short-convolution
layers beside a few softmax-attention layers, two leading dense layers, a
sigmoid router with a choice bias) at toy size on the CPU, seeded random
weights, fp32: the plan `((SCONV, MLP),) x 2 + ((ATTENTION, MOE), (SCONV,
MOE) x 3) x 2`, one expert-parallel rank's share — against the plain
reference of `benchmark/references/lfm2_moe.py` (the conv as three shifted
products, dense masks, no cache), through the train forward over packed
rows (the conv restarts at every segment start), the static prefill +
decode through the two-row tails (rows left- and right-aligned, a row
shorter than the kernel, prefill in waves against one prefill), the loss
and its gradients; the ten controls each failing its bound; the ranks'
parts against the uncut layer; the leaf counts at the published widths; the
HF reader both ways; the frozen choice bias through a train step; the named
refusals; the three flash kernels interpreted at heads of 64 against the
dense mask.  Logits and log-probabilities are compared, never sampled
tokens.

Tolerances: TOL (5e-4) is fp32 matmul reassociation through ten layers at
hidden size 64; the gradient bound (2e-3 of a leaf's largest entry) is what
the recomputed forward under `jax.checkpoint` moves; the reference's own
fp32 bound (1e-4 mean, 1e-3 max on log-probs) must FAIL each control.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import generator as generator_mod
from areal_tpu.models import short_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import (
    ATTENTION, FROZEN_LEAVES, MLP, MOE, SCONV, ModelConfig)
from areal_tpu.models.hf import registry
from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops.pallas import flash_attention as fa
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import lfm2_moe as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "lfm2-8b-a1b-e8.json"
FAMILY = registry.HF_FAMILIES["lfm2_moe"]


def _toy_hf(held=4):
    """The benchmark configuration's keys at its `toy` sizes; `held`
    experts of the router's 8 (8: the whole layer, no share)."""
    config = files.load_json("configs", CONFIG)
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout32-ctx4k-512.json"))
    config["num_experts"] = held
    if held == 8:
        del config["share"]
    # Two periods, so that the layer scan makes two steps of the unit.
    config["num_hidden_layers"] = 10
    config["layer_types"] = config["layer_types"] + config["layer_types"][2:]
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales (the per-head q and k
    norms among them), so that a norm left out cannot pass, and a choice
    bias large enough that choosing by score alone picks other experts."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = [n for n in p["blocks"] if "ln" in n or "norm" in n]
    for k, name in zip(
            jax.random.split(jax.random.PRNGKey(seed + 1), len(names)), names):
        leaf = p["blocks"][name]
        p["blocks"][name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
    bias = p["blocks"]["router_bias"]
    p["blocks"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(seed + 2), bias.shape)
    return p


@pytest.fixture(scope="module")
def params(cfg):
    return _params(cfg)


def _sequences(cfg, lens=(70, 50, 2), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _logprobs(logits, seq):
    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return np.asarray(lp[np.arange(len(seq) - 1), seq[1:]])


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
    }
    assert {k: config[k] for k in published} == published
    whole = ("c c A c c c A c c c A c c c A c c c A c c A c c").split()
    names = {"c": "conv", "A": "full_attention"}
    n = config["num_hidden_layers"]
    assert n in (10, 6)  # both leading layers and two whole periods, or one
    assert config["layer_types"] == [names[c] for c in whole[:n]]
    group = config["benchmark"]
    assert sorted(group["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_experts"], config["vocab_size"]) == (8, 16384)
    share = config["share"]
    assert (share["chips_per_layer"], share["rank"]) == (4, 0)
    assert share["router_num_experts"] == share["published_num_experts"] == 32
    assert share["published_vocab_size"] == 4 * config["vocab_size"]
    assert share["published_num_hidden_layers"] == len(whole) == 24
    for key in ("tie_word_embeddings", "head_dim", "qk_norm", "in_proj_order",
                "precision", "router_bias_draw", "router_aux_loss_coef",
                "embedding_draw", "tensor_names"):
        assert key in group["assumed"], key
    for key in ("deployment", "stands_for", "unused_keys", "toy"):
        assert group[key]
    tol = group["tolerance"]
    assert set(tol["rows"]) == set(tol["fp32"]["rows"]) == {
        "tail_rel_err_max", "rows_rel_err_unrouted", "rows_rel_err_max"}


@pytest.mark.parametrize("layers,leaves", [(10, 982_084_096), (6, 568_647_936)])
def test_the_leaf_count_at_the_published_widths(layers, leaves):
    """What `init_params` allocates for the cut, from shapes alone."""
    config = files.load_json("configs", CONFIG)
    config["num_hidden_layers"] = layers
    config["layer_types"] = (
        "conv conv full_attention conv conv conv full_attention conv conv "
        "conv").split()[:layers]
    cfg = FAMILY.config_from_hf(config)
    assert (cfg.hidden_dim, cfg.head_dim, cfg.intermediate_dim,
            cfg.moe_intermediate_dim, cfg.router_width) == (
        2048, 64, 7168, 1792, 32)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == leaves
    b = shapes["blocks"]
    per_layer = lambda names, pre="": sum(  # noqa: E731
        b[pre + n].size // b[pre + n].shape[0] for n in names)
    conv = per_layer(short_conv.SCONV_LEAVES)
    assert conv == 16_783_360 == per_layer(short_conv.SCONV_LEAVES, "dense_")
    assert per_layer(("wq", "wk", "wv", "wo", "q_norm", "k_norm")) == 10_485_888
    assert per_layer(("wg", "wu", "wd"), "dense_") == 44_040_192
    assert per_layer(("router", "router_bias", "wg", "wu", "wd")) == 88_145_952
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, 32, 4608))
    n_attn = {10: 2, 6: 1}[layers]
    assert cache.k.shape == (n_attn, 32, 4608, 8, 64)
    assert cache.conv.shape == (layers - n_attn, 32, 2, 2048)
    assert cache.conv.dtype == jnp.bfloat16 and cache.state is None


def test_config_both_ways_and_the_plan(cfg):
    assert cfg.plan.prefix == ((SCONV, MLP),) * 2
    assert cfg.plan.unit == ((ATTENTION, MOE),) + ((SCONV, MOE),) * 3
    assert cfg.plan.repeats == 2
    assert (cfg.n_sconv_layers, cfg.n_attn_layers, cfg.n_moe_layers) == (8, 2, 8)
    assert not cfg.has_recurrent_state and not cfg.n_window_layers
    assert (cfg.moe_score_func, cfg.moe_norm_topk_eps) == ("sigmoid", 1e-6)
    assert cfg.tied_embeddings and cfg.qk_norm_per_head and cfg.sconv_kernel == 3
    assert (cfg.router_width, cfg.n_experts, cfg.expert_offset) == (8, 4, 0)
    assert registry.infer_model_type(cfg) == "lfm2_moe"
    back = FAMILY.config_from_hf(FAMILY.config_to_hf(cfg))
    assert dataclasses.replace(
        back, param_dtype="float32",
        router_bias_init_std=cfg.router_bias_init_std) == cfg
    # The sigmoid router of the other families keeps its own epsilon.
    glm = registry.HF_FAMILIES["glm4_moe_lite"].config_from_hf(
        files.load_json("configs", "glm-4.7-flash-l7-e8.json"))
    assert glm.moe_norm_topk_eps == 1e-20
    # The published pattern's tail does not repeat: one unit of 22 layers.
    whole = dataclasses.replace(
        cfg, n_layers=24, window_pattern="CCFCCCFCCCFCCCFCCCFCCFCC")
    assert (len(whole.plan.unit), whole.plan.repeats) == (22, 1)


@pytest.mark.parametrize("hf,error,match", [
    (dict(conv_bias=True), NotImplementedError, "conv_bias"),
    (dict(use_expert_bias=False), NotImplementedError, "use_expert_bias"),
    (dict(layer_types=["conv"] * 9 + ["sliding_attention"]), ValueError,
     "layer_types"),
])
def test_what_is_not_modelled_raises(hf, error, match):
    with pytest.raises(error, match=match):
        FAMILY.config_from_hf({**_toy_hf(), **hf})


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    d, f, fm = cfg.hidden_dim, cfg.intermediate_dim, cfg.moe_intermediate_dim
    want = {
        "model.embed_tokens.weight": (cfg.vocab_size, d),
        "model.embedding_norm.weight": (d,),
        "model.layers.0.operator_norm.weight": (d,),
        "model.layers.0.ffn_norm.weight": (d,),
        "model.layers.0.conv.in_proj.weight": (3 * d, d),
        "model.layers.1.conv.conv.weight": (d, 1, 3),
        "model.layers.1.conv.out_proj.weight": (d, d),
        "model.layers.0.feed_forward.w1.weight": (f, d),
        "model.layers.1.feed_forward.w2.weight": (d, f),
        "model.layers.1.feed_forward.w3.weight": (f, d),
        "model.layers.2.self_attn.q_proj.weight": (cfg.q_dim, d),
        "model.layers.6.self_attn.k_proj.weight": (cfg.kv_dim, d),
        "model.layers.6.self_attn.v_proj.weight": (cfg.kv_dim, d),
        "model.layers.6.self_attn.out_proj.weight": (d, cfg.q_dim),
        "model.layers.2.self_attn.q_layernorm.weight": (cfg.head_dim,),
        "model.layers.2.self_attn.k_layernorm.weight": (cfg.head_dim,),
        "model.layers.9.conv.in_proj.weight": (3 * d, d),
        "model.layers.2.feed_forward.gate.weight": (cfg.router_width, d),
        "model.layers.2.feed_forward.expert_bias": (cfg.router_width,),
        "model.layers.9.feed_forward.experts.3.w1.weight": (fm, d),
        "model.layers.9.feed_forward.experts.0.w2.weight": (d, fm),
        "model.layers.5.feed_forward.experts.2.w3.weight": (fm, d),
    }
    for name, shape in want.items():
        assert sd[name].shape == shape, name
    assert "lm_head.weight" not in sd  # tied
    assert "model.layers.2.conv.in_proj.weight" not in sd
    assert "model.layers.0.feed_forward.gate.weight" not in sd
    assert len(sd) == 2 + 10 * 2 + 8 * 3 + 2 * 6 + 2 * 3 + 8 * (2 + 3 * 4)
    # taps [K, C], oldest first <-> the published [C, 1, K]
    np.testing.assert_array_equal(
        sd["model.layers.1.conv.conv.weight"][:, 0, :].T,
        params["blocks"]["dense_sc_conv"][1])
    np.testing.assert_array_equal(
        sd["model.layers.4.conv.conv.weight"][:, 0, 0],
        params["blocks"]["sc_conv"][1, 0])
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(flat, jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    # A rank that holds experts 4-7 reads and writes THEIR tensors.
    rank1 = dataclasses.replace(cfg, expert_offset=4)
    assert "model.layers.9.feed_forward.experts.7.w1.weight" in (
        FAMILY.params_to_sd(rank1, params))


# ------------------------------------------------ program against reference


@pytest.mark.parametrize("held", [4, 8])
def test_train_forward_over_packed_rows_matches_the_reference(held):
    """One packed row of three segments (two long, one shorter than the
    kernel) against the three run apart through the reference: positions,
    the causal mask and the conv restart at every segment start."""
    cfg = _cfg(held)
    params = _params(cfg)
    seqs = _sequences(cfg)
    tokens = jnp.asarray(np.concatenate(seqs + [np.zeros(6, np.int32)]))[None]
    seg = jnp.asarray(np.concatenate(
        [np.full(len(s), i + 1) for i, s in enumerate(seqs)]
        + [np.zeros(6)]).astype(np.int32))[None]
    got = np.asarray(tfm.forward(params, cfg, tokens, seg))[0]
    off = 0
    for s in seqs:
        want = np.asarray(reference.logits(params, cfg, s))
        np.testing.assert_allclose(got[off: off + len(s)], want, **TOL)
        off += len(s)


def _through_the_tails(cfg, params, plens=(40, 33, 1), sp=40, new=12,
                       s_max=64, left=False, waves=None):
    """Prompts through `prefill` (right-aligned as the static program lays
    them, or `left`-aligned; `waves`: that many rows a prefill, each into a
    cache of its own that lands in the whole one), then `new`
    `decode_step`s -> (rows' tokens, per row the logits at every position
    from the last prompt token on, the cache).  Left-aligned rows decode
    from the cache's slot sp on all the same: their pads lie between."""
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, cfg.vocab_size, p + new).astype(np.int32)
            for p in plens]
    plen = np.asarray(plens)
    prompt = np.zeros((len(plens), sp), np.int32)
    for i, (r, p) in enumerate(zip(rows, plens)):
        if left:
            prompt[i, :p] = r[:p]
        else:
            prompt[i, sp - p:] = r[:p]
    idx = np.arange(sp)[None]
    seg = (idx < plen[:, None] if left else idx >= (sp - plen)[:, None])
    seg = jnp.asarray(seg.astype(np.int32))
    cache = tfm.init_kv_cache(cfg, len(plens), s_max)
    if waves is None:
        logits, cache = tfm.prefill(
            params, cfg, jnp.asarray(prompt), seg, cache, use_flash=False)
    else:
        eng = type("E", (), dict(cfg=cfg, compute_dtype=jnp.float32,
                                 _use_flash=False))()
        logits, cache = generator_mod.GeneratorEngine._prefill_in_waves(
            eng, params, jnp.asarray(prompt), seg, cache, waves)
    got = [[np.asarray(logits[i])] for i in range(len(plens))]
    return rows, got, cache, plen


def _decode(cfg, params, rows, got, cache, plen, sp, new):
    step = jax.jit(lambda tok, pos, cache, slot: tfm.decode_step(
        params, cfg, tok, pos, cache, slot, jnp.asarray(sp - plen, jnp.int32)))
    for t in range(new):
        tok = jnp.asarray([r[p + t] for r, p in zip(rows, plen)], jnp.int32)
        logits, cache = step(tok, jnp.asarray(plen + t, jnp.int32), cache,
                             jnp.int32(sp + t))
        for i in range(len(plen)):
            got[i].append(np.asarray(logits[i]))
    return [np.stack(g) for g in got], cache


def test_prefill_then_decode_through_the_tails_matches_the_reference(cfg, params):
    """Prompts of 40, 33 and ONE token (shorter than the kernel: its tail
    starts as zeros) and 12 new ones against the reference's full forward
    pass of each row; then what the cache holds at the end: every conv
    layer's last two gated inputs, the attention layers' roped K and V."""
    plens, sp, new = (40, 33, 1), 40, 12
    rows, got, cache, plen = _through_the_tails(cfg, params, plens, sp, new)
    assert cache.conv.shape == (8, 3, 2, cfg.hidden_dim)
    assert cache.k.shape == (2, 3, 64, cfg.n_kv_heads, cfg.head_dim)
    assert cache.state is None and cache.latent is None and cache.wk is None
    got, cache = _decode(cfg, params, rows, got, cache, plen, sp, new)
    for r, p, g in zip(rows, plens, got):
        want = np.asarray(reference.logits(params, cfg, r))
        np.testing.assert_allclose(g, want[p - 1: p + new], **TOL)
    for i, (r, p) in enumerate(zip(rows, plens)):
        with jax.default_matmul_precision("highest"):
            _, left = reference._hidden_and_rows(params, cfg, jnp.asarray(r))
        n, first = p + new, sp - p
        layers, n_conv, n_attn = [], 0, 0
        for mixer in cfg.window_pattern:
            if mixer == "C":
                layers.append(cache.conv[n_conv, i])
                n_conv += 1
            else:
                layers.append(jnp.stack(
                    [cache.k[n_attn, i, first: first + n],
                     cache.v[n_attn, i, first: first + n]], axis=1))
                n_attn += 1
        readings = reference.rows_readings(cfg, layers, left, n)
        assert not reference.rows_problems(
            readings, reference.ROWS_TOLERANCE_FP32), readings


def test_prefill_leaves_the_last_valid_inputs_whichever_side_the_pads_lie(
        cfg, params):
    """Left-aligned rows (pads at the END of the prompt window): the tails
    are the gated inputs at each row's last two VALID tokens, not the
    window's last two, and the first decode steps read them."""
    plens, sp = (40, 33, 1, 2), 40
    right = _through_the_tails(cfg, params, plens, sp, new=1)
    left = _through_the_tails(cfg, params, plens, sp, new=1, left=True)
    np.testing.assert_allclose(left[2].conv, right[2].conv, **TOL)
    for a, b in zip(left[1], right[1]):
        np.testing.assert_allclose(a[0], b[0], **TOL)
    # The row of one token: an empty slot, then its own gated input.
    assert float(jnp.abs(right[2].conv[:, 2, 0]).max()) == 0.0
    assert float(jnp.abs(right[2].conv[:, 2, 1]).max()) > 0.0


def test_prefill_in_waves_lands_the_tails_in_the_whole_cache(cfg, params):
    plens, sp = (40, 33, 1, 20), 40
    rows, one, whole, plen = _through_the_tails(cfg, params, plens, sp, new=4)
    _, two, waves, _ = _through_the_tails(
        cfg, params, plens, sp, new=4, waves=2)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(waves)):
        np.testing.assert_allclose(a, b, **TOL)
    for a, b in zip(one, two):
        np.testing.assert_allclose(a[0], b[0], **TOL)
    got, _ = _decode(cfg, params, rows, two, waves, plen, sp, 4)
    for r, p, g in zip(rows, plens, got):
        want = np.asarray(reference.logits(params, cfg, r))
        np.testing.assert_allclose(g, want[p - 1: p + 4], **TOL)


def test_loss_and_gradients_match_the_reference(cfg, params):
    """Mean next-token log-likelihood of a packed row of two sequences and
    its gradient in every leaf, the program's (packed row, remat) against
    `jax.grad` of the reference over the two apart; the choice bias takes
    none."""
    seqs = _sequences(cfg, lens=(40, 21), seed=3)
    tokens = jnp.asarray(np.concatenate(seqs))[None]
    seg = jnp.asarray(np.concatenate(
        [np.full(40, 1), np.full(21, 2)]).astype(np.int32))[None]
    at = np.concatenate([np.arange(39), 40 + np.arange(20)])
    targets = np.concatenate([seqs[0][1:], seqs[1][1:]])

    def ours(p):
        logits = tfm.forward(p, cfg, tokens, seg, remat=True)[0]
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(lp[at, targets])

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            lps = []
            for s in seqs:
                x = reference.final_hidden(p, cfg, jnp.asarray(s))
                lp = jax.nn.log_softmax(x @ p["embed"].T, axis=-1)
                lps.append(lp[jnp.arange(len(s) - 1), s[1:]])
        return -jnp.mean(jnp.concatenate(lps))

    (l1, g1), (l2, g2) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (ours, theirs))
    assert float(l1) == pytest.approx(float(l2), abs=1e-4)
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    flat2 = jax.tree.leaves(g2)
    for (path, a), b in zip(flat1, flat2):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(b).max())
        if any(frozen in name for frozen in FROZEN_LEAVES):
            assert scale == 0.0 == float(jnp.abs(a).max()), name
            continue
        assert scale > 0, name
        assert float(jnp.abs(a - b).max()) <= 2e-3 * scale + 1e-7, name


# ----------------------------------------------------------------- controls


def _system_logprobs(cfg, params, seq):
    return _logprobs(tfm.forward(
        params, cfg, jnp.asarray(seq)[None],
        jnp.ones((1, len(seq)), jnp.int32))[0], seq)


def _outside(diff, tol):
    return diff.mean() > tol["mean_abs"] or diff.max() > tol["max_abs"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_of_the_reference_fails_the_fp32_bound(cfg, params, fault):
    """The program against the reference with ONE part of the mathematics
    wrong: taps newest first, B and C exchanged, the C gate left out, the
    q/k norm left out, the top 4 chosen without the bias, weights taken
    from score + bias, weights not renormalised, an expert block in a
    leading layer's place — each moves the log-probabilities past the bound
    the reference proper passes."""
    seq = _sequences(cfg, lens=(70,), seed=4)[0]
    got = _system_logprobs(cfg, params, seq)
    tol = reference.TOLERANCE_FP32
    proper = np.abs(got - _logprobs(reference.logits(params, cfg, seq), seq))
    assert not _outside(proper, tol)
    wrong = np.abs(
        got - _logprobs(reference.logits(params, cfg, seq, fault=fault), seq))
    assert _outside(wrong, tol), (fault, wrong.mean(), wrong.max())


@pytest.mark.parametrize("lower", ["lower", "lower:router", "lower:cache"])
def test_a_precision_lower_fails_the_fp32_bound(cfg, params, lower):
    seq = _sequences(cfg, lens=(70,), seed=4)[0]
    proper = _logprobs(reference.logits(params, cfg, seq), seq)
    low = np.abs(proper - _logprobs(
        reference.logits(params, cfg, seq, lower=lower), seq))
    assert _outside(low, reference.TOLERANCE_FP32)


def test_a_conv_that_reads_across_a_segment_start_fails(cfg, params, monkeypatch):
    """The ninth control, of the program: with the conv blind to segments
    the second sequence of a packed row starts on the first one's last two
    inputs; the first sequence does not notice."""
    seqs = _sequences(cfg, lens=(30, 25), seed=6)
    tokens = jnp.asarray(np.concatenate(seqs))[None]
    seg = jnp.asarray(np.concatenate(
        [np.full(30, 1), np.full(25, 2)]).astype(np.int32))[None]
    from areal_tpu.models import linear_attention

    blind = linear_attention.causal_conv  # where `conv_act` looks it up
    monkeypatch.setattr(
        linear_attention, "causal_conv",
        lambda x, taps, seg: blind(x, taps, jnp.ones_like(seg)))
    got = np.asarray(tfm.forward(params, cfg, tokens, seg))[0]
    first, second = (
        np.asarray(reference.logits(params, cfg, s)) for s in seqs)
    np.testing.assert_allclose(got[:30], first, **TOL)
    wrong = np.abs(_logprobs(got[30:], seqs[1]) - _logprobs(second, seqs[1]))
    assert _outside(wrong, reference.TOLERANCE_FP32)


@pytest.mark.parametrize("fault", ["stale", "unshifted"])
def test_a_tail_left_stale_or_unshifted_in_decode_fails(
        cfg, params, monkeypatch, fault):
    """The tenth control, of the program: a decode step that leaves the
    tails as prefill wrote them (`stale`), or writes the new input over
    the newest row without moving it up (`unshifted`), agrees with the
    reference for its first token and leaves the bound from the second."""
    update = jax.lax.dynamic_update_index_in_dim

    def broken(tails, window, li, axis):
        if fault == "stale":
            return tails
        tail = jax.lax.dynamic_index_in_dim(tails, li, axis, keepdims=False)
        return update(
            tails, jnp.concatenate([tail[:, :1], window[:, -1:]], 1), li, axis)

    monkeypatch.setattr(
        short_conv.jax.lax, "dynamic_update_index_in_dim", broken)
    plens, sp, new = (40, 33), 40, 4
    rows, got, cache, plen = _through_the_tails(cfg, params, plens, sp, new)
    got, _ = _decode(cfg, params, rows, got, cache, plen, sp, new)
    for r, p, g in zip(rows, plens, got):
        want = np.asarray(reference.logits(params, cfg, r))
        np.testing.assert_allclose(g[:2], want[p - 1: p + 1], **TOL)
        assert np.abs(g[2:] - want[p + 1: p + new]).max() > 1e-2


# ------------------------------------------------------- the ranks' shares


def test_the_four_ranks_parts_add_up_to_the_uncut_layer():
    """Ranks 0-3 of 4 hold experts [2r, 2r + 2) of the router's 8: the
    parts of one expert layer's routed sum they compute add up to what the
    reference gives for the layer with all 8 held."""
    whole = _cfg(held=8)
    params = _params(whole)
    moe = ("router", "router_bias", "wg", "wu", "wd")
    blk = {k: params["blocks"][k][1] for k in moe}
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(1, 24, whole.hidden_dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(h[0], blk, whole)
    total = 0
    for rank in range(4):
        part = dataclasses.replace(
            whole, n_experts=2, n_router_experts=8, expert_offset=2 * rank)
        held = dict(blk, **{
            n: blk[n][2 * rank: 2 * rank + 2] for n in ("wg", "wu", "wd")})
        out, _, counts = tfm._mlp_moe(h, held, part)
        assert counts.shape == (2,)
        total = total + out[0]
    np.testing.assert_allclose(total, want, **TOL)


# ------------------------------------------------------------- train step


def test_the_choice_bias_is_frozen_through_a_train_step(cfg):
    """No gradient, no moment, handed back unchanged; every other matrix
    moves; the step counts the short convolutions' restarts."""
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.api.model_api import FinetuneSpec
    from areal_tpu.engines import train
    from areal_tpu.ops import functional as F
    from tests import fixtures

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    engine = train.TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 8, 8))
    moments = [
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(engine.opt_state)[0]]
    assert any("sc_in" in m for m in moments)
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
    # Eight packed sequences, each a restart of every conv layer.
    assert stats["sconv/segment_restarts"] == 8 * cfg.n_sconv_layers
    assert "ssm/segment_restarts" not in stats
    after = jax.tree.map(np.asarray, engine.get_params())
    np.testing.assert_array_equal(
        after["blocks"]["router_bias"], before["blocks"]["router_bias"])
    assert np.abs(before["blocks"]["router_bias"]).min() > 0  # drawn non-zero
    for name in ("sc_in", "sc_conv", "sc_out", "dense_sc_in", "dense_sc_conv",
                 "dense_wg", "wq", "router", "wg"):
        assert (after["blocks"][name] != before["blocks"][name]).any(), name


# ----------------------------------------------------------------- refusals


def test_the_serving_plane_refuses_the_plan_by_name(cfg, params):
    refusal = tfm.plan_refusal(cfg, serving=True)
    assert isinstance(refusal, tfm.HybridLayoutError)
    assert "short convolution's tail has no slot" in str(refusal)
    with pytest.raises(tfm.HybridLayoutError, match="static decode program"):
        tfm.init_paged_kv_cache(cfg, 4, 16)
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=2)
    sample = SequenceSample.from_default(
        ids=["a"], seqlens=[6], data={"packed_prompts": np.arange(6)})
    g = GenerationHyperparameters(n=1, max_new_tokens=4)
    for kwargs in (
        dict(inflight=True),  # forced
        dict(g=dataclasses.replace(g, n=3)),  # 3 requests > 2 slots
        dict(g=dataclasses.replace(g, stop=((5, 6),))),
    ):
        gg = kwargs.pop("g", g)
        with pytest.raises(tfm.HybridLayoutError, match="inflight=True"):
            eng.generate(sample, MicroBatchSpec(), gg, **kwargs)


@pytest.mark.parametrize("layout", ["m2", "s2", "p2"])
def test_untested_mesh_layouts_are_refused_by_name(cfg, layout):
    from areal_tpu.parallel import sharding

    pc = ParallelConfig.from_str(layout)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    refusal = tfm.plan_refusal(cfg, serving=False)
    assert isinstance(refusal, tfm.HybridLayoutError)
    with pytest.raises(tfm.HybridLayoutError, match="short-convolution"):
        sharding.attn_dispatch(mesh, cfg)
    # The batch axes alone are fine, and every leaf has a rule.
    pc = ParallelConfig.from_str("d2f2")
    sharding.attn_dispatch(make_mesh(pc, jax.devices()[: pc.world_size]), cfg)
    specs = sharding.param_pspecs(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    assert "model" not in str(specs["blocks"]["sc_in"])
    assert "fsdp" in str(specs["blocks"]["dense_sc_out"])


@pytest.mark.parametrize("changes,match", [
    (dict(window_pattern="CCXC"), "window_pattern"),
    (dict(window_pattern="CCFC", sconv_kernel=1), "sconv_kernel"),
    (dict(window_pattern="SCFC", attn_window=4, first_k_dense=1, n_experts=2,
          moe_intermediate_dim=8), "leading dense layer's mixer"),
    (dict(window_pattern="CCFC", first_k_dense=1), "first_k_dense"),
    (dict(window_pattern="CCFC", layer_pattern="****"), "one-branch pattern"),
])
def test_a_config_that_states_no_model_raises(changes, match):
    base = dict(
        n_layers=4, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
        intermediate_dim=128, vocab_size=64)
    with pytest.raises((ValueError, NotImplementedError), match=match):
        ModelConfig(**{**base, **changes})


# ------------------------------------------------- the generator's program


def test_the_static_program_counts_its_tails_beside_the_attention_cache(
        cfg, params):
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4,
        donation_safe_swap=False)
    prompts = _sequences(cfg, lens=(30, 1, 11, 20), seed=7)
    toks, logps, gen_len, cache = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=12),
        jax.random.PRNGKey(1), with_cache=True)
    stats = eng.last_pool_stats
    b, s_total, kv = 4, 256, cfg.kv_dim * 2 * 4  # fp32 K and V a slot
    assert stats["conv_cache_bytes"] == 8 * b * 2 * cfg.hidden_dim * 4
    assert stats["kv_cache_bytes"] == 2 * b * s_total * kv
    assert stats["kv_cache_bytes_all_attention"] == 10 * b * s_total * kv
    assert "state_cache_bytes" not in stats
    # The program's own log-probs of the tokens it sampled, row by row.
    for r, p in enumerate(prompts):
        seq = np.concatenate([p, toks[r, :12]])
        want = _logprobs(reference.logits(params, cfg, seq), seq)
        np.testing.assert_allclose(logps[r, :12], want[len(p) - 1:], **TOL)
    from areal_tpu.base import monitor

    assert sum(n for n, b in monitor._layers_of(cfg) if b.attn_flops) == 2
    d = cfg.hidden_dim
    assert monitor.matmul_params(cfg) == (
        8 * (4 * d * d + 3 * d) + 2 * tfm.BRANCHES["attention"].matmul_params(cfg)
        + 2 * 3 * d * cfg.intermediate_dim
        + 8 * (3 * d * cfg.moe_intermediate_dim * 2 * 4 / 8 + d * 8)
        + d * cfg.vocab_size)


# ----------------------------------------------------- flash at heads of 64


def _ragged_rows(s=512):
    seg = np.zeros((2, s), np.int32)
    seg[0, :300], seg[0, 300:330], seg[0, 330:500] = 1, 2, 3
    seg[1, :40], seg[1, 40:470] = 1, 2
    return seg


def test_the_flash_kernels_at_heads_of_64_match_the_dense_mask():
    """Forward, dq and dkv, interpreted, at 8 query heads over 2 key/value
    heads of 64 (the model's 4 : 1), ragged packed rows."""
    seg = jnp.asarray(_ragged_rows())
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, 512, 8, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 512, 2, 64), jnp.float32)
    ct = jax.random.normal(ks[3], (2, 512, 8, 64), jnp.float32)
    real = (seg > 0)[..., None, None]

    def dense(q, k, v):
        out = attn_ops.packed_attention_reference(q, k, v, seg, causal=True)
        return jnp.where(real, out, 0.0)

    def flash(q, k, v):
        return jnp.where(real, fa.flash_attention(q, k, v, seg, causal=True), 0.0)

    want, pull_want = jax.vjp(dense, q, k, v)
    got, pull_got = jax.vjp(flash, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for a, b, name in zip(pull_got(ct), pull_want(ct), "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3, err_msg=name)


# ------------------------------------------- the decode loop compiled for v5e


def test_the_decode_loop_compiles_for_v5e_with_the_tails_shifted_in_place(
        v5e_chips, monkeypatch):
    """XLA:TPU and Mosaic for real, at the cell's size (32 rows, a
    4,608-slot window, the published widths): a conv layer is a handful of
    fusions — no kernel of its own is wanted — that shift the layer's tail
    through the loop's `dynamic-update-slice`, with no copy or re-layout of
    the tails or of the attention layer's K/V; `decode_attention` lowers at
    heads of 64; the experts' in-place matmuls are the Pallas kernel
    `grouped_decode_matmul` at [2,048, 1,792] (1,792 = 14 x 128 tiles
    badly for XLA's ragged kernel: `ragged_tiles_badly`)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.ops.pallas.grouped_matmul import ragged_tiles_badly

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    assert ragged_tiles_badly(big.hidden_dim, big.moe_intermediate_dim)
    b, sp, st = 32, 4096, 4608
    one = SingleDeviceSharding(v5e_chips[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(placed, jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0))))
    rows = placed(jax.ShapeDtypeStruct((b,), jnp.int32))

    def loop(params, tok, plen):
        cache = tfm.init_kv_cache(big, b, st, dtype=jnp.bfloat16)

        def body(state):
            step, tok, cache = state
            logits, cache = tfm.decode_step(
                params, big, tok, plen + step, cache, sp + step, sp - plen,
                experts_in_place=True)
            return step + 1, jnp.argmax(logits, -1).astype(jnp.int32), cache

        return jax.lax.while_loop(
            lambda s: s[0] < 512, body, (0, tok, cache))[1]

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(loop).lower(params, rows, rows).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    n_conv, n_attn = big.n_sconv_layers, big.n_attn_layers
    tails = f"bf16[{n_conv},{b},2,{big.hidden_dim}]"
    kv = f"bf16[{n_attn},{b},{st},{big.n_kv_heads},{big.head_dim}]"
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if any(s in line.split(" = ")[-1].split("(")[0] for s in (tails, kv))
        and (" copy(" in line or " transpose(" in line)
    ]
    assert not copies, copies[:3]
    shifts = [line for line in text.splitlines()
              if tails in line and "dynamic-update-slice(" in line]
    assert len(shifts) == n_conv
    assert "layer/sconv/conv" in text and "layer/sconv/in_proj" in text
    assert "%grouped_decode_matmul" in text


# --------------------------------- the cell, rehearsed on the CPU at toy size

# `lfm2-ctxrl32-4k` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

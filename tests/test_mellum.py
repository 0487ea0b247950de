"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B, `mellum`: the Qwen3-MoE block
with sliding-window layers beside full-attention layers) at toy size on the
CPU, seeded random weights, fp32: a window of 16 keys in three layers of
four, YaRN on the full layers alone, per-head q/k norm, a softmax router
with renormalised top-k weights, one expert-parallel rank's share —
against the plain reference of `benchmark/references/mellum.py` (dense
masks from positions, no cache, no ring), through the train forward over
packed rows, the static prefill + decode through the RING (contexts of
several windows: it wraps in prefill and in decode; a row shorter than the
window), the loss and its gradients; the windowed live schedule and the
three flash kernels, interpreted, against the dense mask; the tile counter
against the schedule; the eight controls each failing its bound; the
ranks' parts against the uncut layer; the HF reader both ways; the named
refusals.  Logits and log-probabilities are compared, never sampled tokens.

Tolerances: TOL (5e-4) is fp32 matmul reassociation through eight layers at
hidden size 64; the gradient bound (2e-3 of a leaf's largest entry) is what
the recomputed forward under `jax.checkpoint` moves; the reference's own
fp32 bound (1e-4 mean, 1e-3 max on log-probs) must FAIL each control.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import generator as generator_mod
from areal_tpu.engines import packing
from areal_tpu.engines.train import _grid_counts
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ATTENTION, MOE, WINDOW, ModelConfig
from areal_tpu.models.hf import registry
from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops.norms import yarn_inv_freq
from areal_tpu.ops.pallas import flash_attention as fa
from benchmark import files, peaks_swa
from benchmark import run as bench_run
from benchmark.references import mellum as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "mellum2-12b-a2.5b-l4-e16.json"
CELL = "mellum2-coderl32-4k"
FAMILY = registry.HF_FAMILIES["mellum"]
W = 16  # the toy group's window


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
    config["num_hidden_layers"] = 8
    config["layer_types"] = config["layer_types"] * 2
    config["mlp_layer_types"] = config["mlp_layer_types"] * 2
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales (the per-head q and k
    norms among them), so that a norm left out cannot pass."""
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


def _sequences(cfg, lens=(70, 50, 9), seed=0):
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
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 500000},
        },
    }
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"])  # one whole period
    assert config["mlp_layer_types"] == ["sparse"] * 4
    group = config["benchmark"]
    assert sorted(group["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 16)
    assert "9.08 GB" in group["reduced"]["num_hidden_layers"]  # why not 8
    share = config["share"]
    assert (share["chips_per_layer"], share["rank"]) == (4, 0)
    assert share["router_num_experts"] == share["published_num_experts"] == 64
    assert config["vocab_size"] * 4 == share["published_vocab_size"] == 98304
    assert share["published_num_hidden_layers"] == 28
    assert group["weights_seed"] == 44 and group["reference"] == "mellum"
    for key in ("qk_norm", "router_aux_loss_coef", "embedding_draw",
                "yarn_truncate"):
        assert key in group["assumed"], key
    for key in ("intermediate_size", "max_window_layers", "use_sliding_window"):
        assert key in group["unused_keys"], key
    assert any("MTP" in n for n in group["notes"])
    for key in ("deployment", "stands_for", "tolerance", "toy"):
        assert group[key], key
    cfg = bench_run.model_config(config)
    assert cfg.window_pattern == "SSSF" and cfg.attn_window == 1024
    assert (cfg.n_window_layers, cfg.n_attn_layers) == (3, 1)
    assert (cfg.n_experts, cfg.router_width, cfg.expert_offset) == (16, 64, 0)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.moe_norm_topk
    assert cfg.moe_aux_loss_coef == 0.001 and cfg.shared_expert_dim == 0
    assert (cfg.rope_theta, cfg.rope_yarn_factor, cfg.rope_yarn_original) == (
        500000.0, 16.0, 8192)
    assert cfg.rope_yarn_attention_factor == 1.2772588722239782
    # 595.15 M parameters: the arithmetic of `reduced`, from the shapes; the
    # two periods ISSUE 44 asked for first, 1,077.06 M.
    def leaves(c):
        return jax.eval_shape(lambda: tfm.init_params(c, jax.random.PRNGKey(0)))

    shapes = leaves(cfg)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 595_154_176
    per_layer = sum(x.size // 4 for x in shapes["blocks"].values())
    assert per_layer == 120_476_416
    assert peaks_swa.attn_params(cfg) == 21_233_664
    for name, x in shapes["blocks"].items():  # window and full stacked alike
        assert x.shape[0] == 4, name
    two = dataclasses.replace(cfg, n_layers=8, window_pattern="SSSFSSSF")
    assert sum(x.size for x in jax.tree.leaves(leaves(two))) == 1_077_059_840


def test_config_both_ways_and_the_plan(cfg):
    assert registry.infer_model_type(cfg) == "mellum"
    assert cfg.plan.unit == ((WINDOW, MOE),) * 3 + ((ATTENTION, MOE),)
    assert (cfg.plan.prefix, cfg.plan.repeats) == ((), 2)
    hf = FAMILY.config_to_hf(cfg)
    assert hf["layer_types"] == _toy_hf()["layer_types"]
    assert hf["rope_parameters"]["full_attention"]["rope_type"] == "yarn"
    assert hf["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000.0}
    back = FAMILY.config_from_hf(hf)
    assert dataclasses.replace(back, param_dtype="float32") == cfg
    whole = _cfg(held=8)  # no share group: 1 of 1
    assert not whole.expert_share and whole.router_width == 8
    assert "share" not in FAMILY.config_to_hf(whole)
    # Full attention in every layer and no YaRN: no window branch at all.
    plain = dict(_toy_hf(), layer_types=["full_attention"] * 8)
    plain["rope_parameters"] = {"full_attention": {
        "rope_type": "default", "rope_theta": 10000.0}}
    plain = FAMILY.config_from_hf(plain)
    assert plain.window_pattern == "" and plain.plan.unit == ((ATTENTION, MOE),)
    assert not plain.rope_yarn_factor and plain.attn_window == 0


def _with_rope(kind, **changes):
    hf = _toy_hf()
    rope = {k: dict(v) for k, v in hf["rope_parameters"].items()}
    rope[kind].update(changes)
    return dict(hf, rope_parameters=rope)


@pytest.mark.parametrize("hf,error,match", [
    (dict(_toy_hf(), attention_bias=True), NotImplementedError, "attention_bias"),
    (dict(_toy_hf(), hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(_toy_hf(), mlp_layer_types=["dense"] + ["sparse"] * 7),
     NotImplementedError, "dense MLP"),
    (dict(_toy_hf(), layer_types=["chunked_attention"] * 8), ValueError,
     "layer_types"),
    (dict(_toy_hf(), layer_types=["full_attention"] * 7), ValueError,
     "layer_types"),
    (dict(_toy_hf(), use_sliding_window=False), NotImplementedError,
     "use_sliding_window"),
    (_with_rope("sliding_attention", rope_type="yarn"), NotImplementedError,
     "plain rope"),
    (_with_rope("full_attention", rope_type="llama3"), NotImplementedError,
     "llama3"),
    (dict(_toy_hf(), sliding_window=None), ValueError, "attn_window"),
])
def test_what_is_not_modelled_raises(hf, error, match):
    with pytest.raises(error, match=match):
        FAMILY.config_from_hf(hf)


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d = cfg.hidden_dim
    for i in (0, 3, 7):  # a window layer, a full one, the last
        pre = f"model.layers.{i}."
        assert sd[pre + "self_attn.q_proj.weight"].shape == (cfg.q_dim, d)
        assert sd[pre + "self_attn.k_proj.weight"].shape == (cfg.kv_dim, d)
        assert sd[pre + "self_attn.q_norm.weight"].shape == (cfg.head_dim,)
        assert sd[pre + "self_attn.k_norm.weight"].shape == (cfg.head_dim,)
        assert sd[pre + "mlp.gate.weight"].shape == (8, d)  # the whole router
        assert pre + "mlp.experts.3.down_proj.weight" in sd
        assert pre + "mlp.experts.4.down_proj.weight" not in sd  # held 0-3
    assert not any(k.startswith("model.layers.8.") for k in sd)  # no MTP head
    np.testing.assert_array_equal(
        sd["model.layers.3.self_attn.o_proj.weight"],
        np.asarray(params["blocks"]["wo"][3]).T)


@pytest.mark.parametrize("window,pattern", [(4096, "SSS"), (None, "")])
def test_the_mistral_window_is_read_into_the_window_branch(window, pattern):
    """No longer full attention in silence: a non-null `sliding_window` makes
    every layer a window layer, null none; the writer states what it has."""
    fam = registry.HF_FAMILIES["mistral"]
    hf = {
        "model_type": "mistral", "num_hidden_layers": 3, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "vocab_size": 199, "sliding_window": window,
    }
    cfg = fam.config_from_hf(hf)
    assert cfg.window_pattern == pattern and cfg.attn_window == (window or 0)
    assert cfg.plan.count(WINDOW) == len(pattern)
    assert fam.config_to_hf(cfg)["sliding_window"] == window
    assert (tfm.plan_refusal(cfg, serving=True) is None) == (window is None)
    mixed = dataclasses.replace(cfg, window_pattern="SSF", attn_window=8)
    with pytest.raises(NotImplementedError, match="layer_types"):
        fam.config_to_hf(mixed)


def test_yarn_inverse_frequencies_against_hand_computed_values():
    """The published numbers: d 128, base 500,000, factor 16, original
    8,192, beta 32 / 1.  c(32) = 18.08 and c(1) = 34.98, so the ramp runs
    from 18 to 35: dimensions up to 18 keep theta's frequency, from 35 on
    take a sixteenth of it."""
    d, base = 128, 500000.0

    def c(r):
        return d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(base))

    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    inv = yarn_inv_freq(d, base, 16.0, 8192, 32.0, 1.0)
    plain = base ** (-np.arange(0, d, 2) / d)
    assert inv.shape == (64,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert inv[-1] == pytest.approx(base ** (-126 / 128) / 16, rel=1e-6)
    ramp = (26 - 18) / (35 - 18)  # dimension 26, inside the ramp
    assert inv[26] == pytest.approx(
        plain[26] / 16 * ramp + plain[26] * (1 - ramp), rel=1e-6)
    np.testing.assert_allclose(
        inv, reference.yarn_inv_freq(bench_run.model_config(
            files.load_json("configs", CONFIG))), rtol=1e-6)
    # cos and sin carry the attention factor; plain rope does not.
    pos = jnp.arange(5)
    cos, sin = tfm.rope_cos_sin(pos, d, base, (16.0, 8192, 32.0, 1.0, 1.25))
    assert float(cos[0, 0]) == 1.25 and float(sin[0, 0]) == 0.0
    assert float(tfm.rope_cos_sin(pos, d, base)[0][0, 0]) == 1.0


def test_the_two_rope_tables_of_a_forward(cfg):
    pos = jnp.arange(40)[None]
    (cos, sin), (wcos, wsin) = tfm._rope(cfg, pos)
    assert float(jnp.abs(cos - wcos).max()) > 0.1  # YaRN is the full layers'
    np.testing.assert_allclose(
        wcos, tfm.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)[0])
    plain = dataclasses.replace(cfg, window_pattern="", attn_window=0)
    assert tfm._rope(plain, pos)[1] is None


# ------------------------------------------------ program against reference


@pytest.mark.parametrize("held", [4, 8])
def test_train_forward_over_packed_rows_matches_the_reference(held):
    """One packed row of three segments (two of several windows, one
    shorter than a window) against the three run apart through the
    reference: positions, the causal mask and the window restart at every
    segment start."""
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


def _through_the_ring(cfg, params, plens=(40, 33, 9), sp=40, new=24, s_max=64):
    """Right-aligned prompts through `prefill`, then `new` `decode_step`s
    -> (rows' tokens, per row the logits at every position from the last
    prompt token on, the cache)."""
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, cfg.vocab_size, p + new).astype(np.int32)
            for p in plens]
    prompt = np.zeros((len(plens), sp), np.int32)
    for i, (r, p) in enumerate(zip(rows, plens)):
        prompt[i, sp - p:] = r[:p]
    plen = np.asarray(plens)
    seg = (np.arange(sp)[None] >= (sp - plen)[:, None]).astype(np.int32)
    cache = tfm.init_kv_cache(cfg, len(plens), s_max)
    logits, cache = tfm.prefill(
        params, cfg, jnp.asarray(prompt), jnp.asarray(seg), cache,
        use_flash=False)
    got = [[np.asarray(logits[i])] for i in range(len(plens))]
    step = jax.jit(lambda tok, pos, cache, slot: tfm.decode_step(
        params, cfg, tok, pos, cache, slot, jnp.asarray(sp - plen, jnp.int32)))
    for t in range(new):
        tok = jnp.asarray([r[p + t] for r, p in zip(rows, plens)], jnp.int32)
        logits, cache = step(tok, jnp.asarray(plen + t, jnp.int32), cache,
                             jnp.int32(sp + t))
        for i in range(len(plens)):
            got[i].append(np.asarray(logits[i]))
    return rows, [np.stack(g) for g in got], cache


def test_prefill_then_decode_through_the_ring_matches_the_reference(cfg, params):
    """Prompts of 40, 33 and 9 tokens and 24 new ones against the
    reference's full forward pass of each row: the ring of 16 slots wraps
    twice in prefill and once more in decode, and the shortest row fills
    its ring only during decode (stale entries from before its first slot
    are never read)."""
    plens, new = (40, 33, 9), 24
    rows, got, cache = _through_the_ring(cfg, params, plens, new=new)
    assert cache.wk.shape == (6, 3, W, cfg.n_kv_heads, cfg.head_dim)
    assert cache.k.shape == (2, 3, 64, cfg.n_kv_heads, cfg.head_dim)
    assert cache.state is None and cache.latent is None
    for r, p, g in zip(rows, plens, got):
        want = np.asarray(reference.logits(params, cfg, r))
        np.testing.assert_allclose(g, want[p - 1: p + new], **TOL)
    # What the rings hold at the end: the last 16 tokens' roped K and V of
    # every window layer, slot s at entry s mod 16.
    with jax.default_matmul_precision("highest"):
        _, ref_rows = reference._hidden_and_rows(
            params, cfg, jnp.asarray(rows[0]))
    end, first = 40 + new, 0  # row 0's slots of the cache are its tokens'
    at = np.arange(end - W, end)
    window_layers = [l for l, c in enumerate(cfg.window_pattern) if c == "S"]
    for n, l in enumerate(window_layers):
        np.testing.assert_allclose(
            cache.wk[n, 0, at % W], ref_rows[l, at - first, 0], **TOL)
        np.testing.assert_allclose(
            cache.wv[n, 0, at % W], ref_rows[l, at - first, 1], **TOL)
    np.testing.assert_allclose(cache.k[1, 0, :end], ref_rows[7, :end, 0], **TOL)


def test_a_cache_shorter_than_the_window_is_its_own_ring(cfg, params):
    """s_max under the window: the ring is the cache's length and never
    wraps; the numbers are the full layers' arithmetic."""
    wide = dataclasses.replace(cfg, attn_window=1024)
    rows, got, cache = _through_the_ring(
        wide, params, plens=(20, 7), sp=20, new=6, s_max=32)
    assert cache.wk.shape[2] == 32
    for r, p, g in zip(rows, (20, 7), got):
        want = np.asarray(reference.logits(params, wide, r))
        np.testing.assert_allclose(g, want[p - 1: p + 6], **TOL)


def test_ring_tail_and_ring_valid():
    x = jnp.arange(10.0).reshape(1, 10, 1, 1)
    ring = np.asarray(tfm._ring_tail(x, 4))[0, :, 0, 0]
    assert ring.tolist() == [8.0, 9.0, 6.0, 7.0]  # slot s at entry s mod 4
    assert np.asarray(tfm._ring_tail(x[:, :3], 4))[0, :, 0, 0].tolist() == [
        0.0, 1.0, 2.0, 0.0]
    live = np.asarray(tfm.ring_valid(
        jnp.int32(9), jnp.asarray([0, 7, 9, 3]), 4))
    # Slot 9 written: entries hold slots 8, 9, 6, 7.
    assert live.tolist() == [
        [True, True, True, True], [True, True, False, True],
        [False, True, False, False], [True, True, True, True]]
    early = np.asarray(tfm.ring_valid(jnp.int32(1), jnp.asarray([0]), 4))
    assert early.tolist() == [[True, True, False, False]]  # nothing older


def test_loss_and_gradients_match_the_reference(cfg, params):
    """Mean next-token log-likelihood of one sequence of several windows
    and its gradient in every leaf, the program's (packed row, remat) against
    `jax.grad` of the reference."""
    seq = _sequences(cfg, lens=(60,), seed=3)[0]
    tokens = jnp.asarray(seq)[None]
    seg = jnp.ones_like(tokens)

    def ours(p):
        logits = tfm.forward(p, cfg, tokens, seg, remat=True)[0]
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(lp[jnp.arange(59), tokens[0, 1:]])

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            x = reference.final_hidden(p, cfg, tokens[0])
            lp = jax.nn.log_softmax(x @ p["lm_head"], axis=-1)
        return -jnp.mean(lp[jnp.arange(59), tokens[0, 1:]])

    (l1, g1), (l2, g2) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (ours, theirs))
    assert float(l1) == pytest.approx(float(l2), abs=1e-4)
    flat1 = jax.tree_util.tree_flatten_with_path(g1)[0]
    flat2 = jax.tree.leaves(g2)
    for (path, a), b in zip(flat1, flat2):
        scale = float(jnp.abs(b).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(a - b).max()) <= 2e-3 * scale + 1e-7, (
            jax.tree_util.keystr(path))


# ------------------------------------------------ schedule, kernels, counter


def _ragged_rows(seed=0, s=512):
    """Two packed rows of ragged segments (a long one, short ones, pads)."""
    seg = np.zeros((2, s), np.int32)
    seg[0, :300], seg[0, 300:330], seg[0, 330:500] = 1, 2, 3
    seg[1, :40], seg[1, 40:470] = 1, 2
    return seg


def _dense_schedule(seg, block, window):
    """[B, nq, nk] tiles that hold an unmasked element, from the dense
    mask itself."""
    mask = np.asarray(attn_ops.make_packed_mask(
        jnp.asarray(seg), causal=True, window=window))[:, 0]
    b, s, _ = mask.shape
    n = s // block
    return mask.reshape(b, n, block, n, block).any(axis=(2, 4))


@pytest.mark.parametrize("window", [None, 1, 64, 100, 128, 129, 300, 1024])
def test_the_windowed_live_schedule_is_the_dense_masks_tiles(window):
    seg, block = _ragged_rows(), 64
    sched = fa.live_schedule(jnp.asarray(seg), block, block, True, window)
    live = _dense_schedule(seg, block, window)
    n = seg.shape[1] // block
    k_lo, k_hi, q_lo, q_hi = (np.asarray(x).reshape(2, n) for x in sched)
    for b in range(2):
        for i in range(n):
            ks = np.flatnonzero(live[b, i])
            want = (ks[0], ks[-1]) if ks.size else (0, -1)
            assert (k_lo[b, i], k_hi[b, i]) == want, (b, i)
            qs = np.flatnonzero(live[b, :, i])
            want = (qs[0], qs[-1]) if qs.size else (0, -1)
            assert (q_lo[b, i], q_hi[b, i]) == want, (b, i)
    # The host's counter counts the same tiles (at the kernels' 128).
    tiles = _dense_schedule(seg, 128, window)
    assert packing.flash_tile_counts(seg, window=window) == (
        int(tiles.sum()), 2 * 4 * 4)


@pytest.mark.parametrize("window", [1, 100, 128, 200])
def test_the_flash_kernels_under_a_window_match_the_dense_mask(window):
    """Forward and the two backward kernels, interpreted, over ragged
    packed rows with GQA, against the dense reference under the same mask;
    and the band schedule gives the all-tiles schedule's bits."""
    rng = np.random.default_rng(window)
    seg = jnp.asarray(_ragged_rows())
    b, s, hq, hkv, d = 2, 512, 4, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for h in (hq, hkv, hkv))
    do = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)

    def run(f):
        out, vjp = jax.vjp(lambda q, k, v: f(q, k, v), q, k, v)
        return (out, *vjp(do))

    got = run(lambda q, k, v: fa.flash_attention(q, k, v, seg, window=window))
    want = run(lambda q, k, v: attn_ops.packed_attention_reference(
        q, k, v, seg, window=window))
    real = np.asarray(seg > 0)[..., None, None]
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.where(real, g, 0), np.where(real, w, 0), rtol=2e-4, atol=2e-4)
    # Another window is another result: the mask is not a no-op.
    other = fa.flash_attention(q, k, v, seg, window=window + 50)
    assert float(jnp.abs(jnp.where(real, other - got[0], 0)).max()) > 1e-3


def test_no_window_traces_the_program_it_always_was():
    """A `None` window is a trace-time constant: the lowered text of the
    kernels' caller has no operand or constant more than before."""
    q = jax.ShapeDtypeStruct((1, 256, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 32), jnp.float32)
    seg = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    plain = jax.jit(fa.flash_attention).lower(q, kv, kv, seg).as_text()
    none = jax.jit(lambda *a: fa.flash_attention(*a, window=None)).lower(
        q, kv, kv, seg).as_text()
    band = jax.jit(lambda *a: fa.flash_attention(*a, window=64)).lower(
        q, kv, kv, seg).as_text()
    strip = lambda text: text.split("\n", 1)[1]  # the module's name line
    assert strip(plain) == strip(none) != strip(band)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(
            jnp.zeros(q.shape), jnp.zeros(kv.shape), jnp.zeros(kv.shape),
            jnp.ones(seg.shape, jnp.int32), causal=False, window=4)


def test_the_trainers_tile_counter_has_the_band_beside_the_triangle():
    seg = np.zeros((1, 4096), np.int32)
    seg[0, :4096] = 1
    assert packing.flash_tile_counts(seg) == (528, 1024)
    assert packing.flash_tile_counts(seg, window=1024) == (252, 1024)
    counted = _grid_counts([{"segment_ids": seg}], window=1024)
    assert counted["flash_live_tiles"] == 528
    assert counted["flash_live_tiles_window"] == 252
    assert "flash_live_tiles_window" not in _grid_counts([{"segment_ids": seg}])


# ----------------------------------------------------------------- controls


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_of_the_reference_fails_the_fp32_bound(cfg, params, fault):
    """The program against the reference with ONE part of the mathematics
    wrong: window left out, off by one either way, YaRN left out of the
    full layers, put on the sliding ones, its attention factor left out,
    the top-k weights not renormalised, the q/k norm left out — each moves
    the log-probabilities past the bound the reference proper passes."""
    seq = _sequences(cfg, lens=(70,), seed=4)[0]
    got = _logprobs(tfm.forward(
        params, cfg, jnp.asarray(seq)[None], jnp.ones((1, 70), jnp.int32))[0],
        seq)
    tol = reference.TOLERANCE_FP32
    proper = np.abs(got - _logprobs(reference.logits(params, cfg, seq), seq))
    assert proper.mean() <= tol["mean_abs"] and proper.max() <= tol["max_abs"]
    wrong = np.abs(
        got - _logprobs(reference.logits(params, cfg, seq, fault=fault), seq))
    assert wrong.mean() > tol["mean_abs"] or wrong.max() > tol["max_abs"], (
        fault, wrong.mean(), wrong.max())


@pytest.mark.parametrize("lower", ["lower", "lower:router", "lower:cache"])
def test_a_precision_lower_fails_the_fp32_bound(cfg, params, lower):
    seq = _sequences(cfg, lens=(70,), seed=4)[0]
    proper = _logprobs(reference.logits(params, cfg, seq), seq)
    low = np.abs(proper - _logprobs(
        reference.logits(params, cfg, seq, lower=lower), seq))
    tol = reference.TOLERANCE_FP32
    assert low.mean() > tol["mean_abs"] or low.max() > tol["max_abs"]


def test_a_ring_read_with_stale_entries_unmasked_fails(cfg, params, monkeypatch):
    """The eighth control, of the program: a row whose context is under
    the window reads only the entries it has written.  With every entry
    of the ring counted live (what reading it in slot order without
    `valid_from` would do) the short row's logits leave the bound; the
    long rows, whose rings are full, do not notice."""
    plens, new = (40, 9), 4
    monkeypatch.setattr(
        tfm, "ring_valid",
        lambda slot, valid_from, ring: jnp.ones((len(plens), ring), bool))
    rows, got, _ = _through_the_ring(cfg, params, plens, new=new)
    want = [np.asarray(reference.logits(params, cfg, r)) for r in rows]
    np.testing.assert_allclose(got[0][1:], want[0][40: 40 + new], **TOL)
    short = np.abs(got[1][1:] - want[1][9: 9 + new])
    assert short.max() > 1e-2


# ------------------------------------------------------- the ranks' shares


def test_the_four_ranks_parts_add_up_to_the_uncut_layer():
    """Ranks 0-3 of 4 hold experts [2r, 2r + 2) of the router's 8: the
    parts of one expert layer's routed sum they compute add up to what the
    reference gives for the layer with all 8 held."""
    whole = _cfg(held=8)
    params = _params(whole)
    blk = {k: v[1] for k, v in params["blocks"].items()}
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


# ----------------------------------------------------------------- refusals


def test_the_serving_plane_refuses_the_plan_by_name(cfg, params):
    refusal = tfm.plan_refusal(cfg, serving=True)
    assert isinstance(refusal, tfm.WindowLayoutError)
    assert "sliding-window layers as full ones" in str(refusal)
    with pytest.raises(tfm.WindowLayoutError, match="static decode program"):
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
        dict(g=dataclasses.replace(g, spec_decode_k=2)),
    ):
        gg = kwargs.pop("g", g)
        with pytest.raises(tfm.WindowLayoutError, match="inflight=True"):
            eng.generate(sample, MicroBatchSpec(), gg, **kwargs)


@pytest.mark.parametrize("layout", ["m2", "s2", "p2"])
def test_untested_mesh_layouts_are_refused_by_name(cfg, layout):
    from areal_tpu.parallel import sharding

    pc = ParallelConfig.from_str(layout)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    refusal = tfm.plan_refusal(cfg, serving=False)
    assert isinstance(refusal, tfm.WindowLayoutError)
    with pytest.raises(tfm.WindowLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)
    # The batch axes alone are fine.
    pc = ParallelConfig.from_str("d2f2")
    sharding.attn_dispatch(make_mesh(pc, jax.devices()[: pc.world_size]), cfg)


@pytest.mark.parametrize("changes,match", [
    (dict(window_pattern="SSXF"), "window_pattern"),
    (dict(window_pattern="SSSF", attn_window=0), "attn_window"),
    (dict(window_pattern="SF" * 4, n_layers=8, full_attn_interval=2,
          attn_window=4), "beside plain softmax-attention"),
    (dict(rope_yarn_factor=4.0), "rope_yarn_original"),
])
def test_a_config_that_states_no_model_raises(changes, match):
    base = dict(
        n_layers=8 if "n_layers" in changes else 4, hidden_dim=64,
        n_q_heads=4, n_kv_heads=2, head_dim=16, intermediate_dim=128,
        vocab_size=64)
    with pytest.raises((ValueError, NotImplementedError), match=match):
        ModelConfig(**{**base, **changes})


# ------------------------------------------------- the generator's program


def test_the_static_program_counts_its_rings_and_prefills_in_waves(
        cfg, params, monkeypatch):
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    g = GenerationHyperparameters(n=1, max_new_tokens=12)
    prompts = _sequences(cfg, lens=(30, 5, 11, 20), seed=7)

    def rollout():
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4,
            donation_safe_swap=False)
        return eng, eng.static_rollout(
            prompts, g, jax.random.PRNGKey(1), with_cache=True)

    eng, whole = rollout()
    stats = eng.last_pool_stats
    b, s_total, kv = 4, 256, cfg.kv_dim * 2 * 4  # fp32 K and V a slot
    assert stats["window_cache_bytes"] == 6 * b * W * kv
    assert stats["kv_cache_bytes"] == 2 * b * s_total * kv
    assert stats["kv_cache_bytes_unwindowed"] == 8 * b * s_total * kv
    assert stats["window_slots"] == b * W
    # Rows of 30, 5, 11 and 20 tokens: the second and third fill their
    # rings during the 12 steps (6..16 and 12..16 live entries).
    live = [min(n + t + 1, W) for n in (30, 5, 11, 20) for t in range(12)]
    assert stats["window_slots_live"] == pytest.approx(sum(live) / 12)
    assert eng._prefill_wave_rows(4, 128) == 4  # one prefill, as ever
    # Past the budget the rows go in waves, and nothing else changes.
    monkeypatch.setattr(generator_mod, "PREFILL_WAVE_TOKENS", 256)
    eng, waves = rollout()
    assert eng._prefill_wave_rows(4, 128) == 2
    np.testing.assert_array_equal(whole[0], waves[0])
    np.testing.assert_allclose(whole[1], waves[1], atol=1e-6)
    for a, c in zip(jax.tree.leaves(whole[3]), jax.tree.leaves(waves[3])):
        np.testing.assert_allclose(a, c, atol=1e-6)
    assert generator_mod.GeneratorEngine._prefill_wave_rows(
        eng, 32, 4096) == 1  # 256 tokens: under one row; never zero


# --------------------------------- the cell, rehearsed on the CPU at toy size

# `mellum2-coderl32-4k` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

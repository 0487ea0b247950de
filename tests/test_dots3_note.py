"""dots3_note (dots-studio/dots3-note-prev, the language model): latent
attention in two geometries by `window_pattern` — full layers behind a
learned token indexer, sliding layers over a ring of latent rows — with a
headwise gate, the latent rescale, unequal q/k and v head widths, a rank's
share of the heads and of the experts.  Toy sizes, seeded weights, the CPU:
the program (`models/latent_select.py`, the train stack, prefill and decode
through the caches) against the plain reference
(`benchmark/references/dots3_note.py`), which shares no code with it."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import latent_select as ls
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import (
    FROZEN_LEAVES,
    LATENT_SELECT,
    LATENT_WINDOW,
    MLP,
    MOE,
    ModelConfig,
)
from areal_tpu.models.hf import registry
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import dots3_note as reference
from benchmark.tests import test_dsa as _dsa_cases
from benchmark.tests.test_dsa import *  # noqa: F401,F403 — the cases (PR 64)


def test_the_dots3_cell_lists_what_it_reports(monkeypatch):  # noqa: F811
    """PR 64's case pins ITS cell and configuration as the last; PR 68
    appended a cell, a configuration and the cell's name to lists.  So:
    the case on the lists as they stood before (`benchmark/tests/` is not a
    model_config PR's to edit)."""
    spec = files.benchmark_json()
    last = spec["workloads"][-1]["name"]
    assert last == "sdar-rollout64-512"

    def without(m):
        if last not in m.get("workloads", ()):
            return m
        return dict(m, workloads=[w for w in m["workloads"] if w != last])

    before = dict(
        spec, workloads=spec["workloads"][:-1], configs=spec["configs"][:-1],
        end_to_end=[without(m) for m in spec["end_to_end"]],
        per_layer=[without(m) for m in spec["per_layer"]])
    monkeypatch.setattr(files, "benchmark_json", lambda: before)
    _dsa_cases.test_the_dots3_cell_lists_what_it_reports()

CONFIG = "dots3-note-prev-l5-e8-h8.json"
FAMILY = registry.HF_FAMILIES["dots3_note"]
TOL = dict(rtol=2e-4, atol=2e-5)
_FP32 = files.load_json("configs", CONFIG)["benchmark"]["tolerance"]["fp32"]


def _toy_hf(whole=False, **changes):
    """The benchmark configuration's keys at its `toy` sizes, the toy
    selection and window small enough to bite at these lengths; `whole`:
    the uncut layer (every head and expert, no share)."""
    config = files.load_json("configs", CONFIG)
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout8-ctx9k-14k-256.json"))
    config.update(index_topk=16, **changes)
    if whole:
        config.update(
            num_attention_heads=4, num_key_value_heads=4,
            swa_num_attention_heads=4, swa_num_key_value_heads=4,
            n_routed_experts=8)
        del config["share"]
    return config


def _cfg(whole=False, hf=None, **changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf(whole, **(hf or {})))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales and LayerNorm bias, so
    that a norm left out cannot pass."""
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


def _packed(seqs, width):
    tok = np.zeros((1, width), np.int32)
    seg = np.zeros((1, width), np.int32)
    at = 0
    for i, s in enumerate(seqs):
        tok[0, at: at + len(s)], seg[0, at: at + len(s)] = s, i + 1
        at += len(s)
    return jnp.asarray(tok), jnp.asarray(seg)


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    """Every key of the catalog row is in the file as published, but the
    keys `reduced` names; the cut is the one ISSUE 64 wrote; the parameter
    count is the builder's exact one."""
    config = files.load_json("configs", CONFIG)
    row = next(
        r for r in map(json.loads, open(
            "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "dots3-note-prev"
    ) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    bench = config["benchmark"]
    reduced = set(bench["reduced"])
    assert reduced == {
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads",
        "swa_num_attention_heads", "swa_num_key_value_heads", "vocab_size"}
    if row is not None:
        assert bench["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced:
                assert config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:5]
    share = config["share"]
    assert config["num_attention_heads"] * 8 == share[
        "published_num_attention_heads"] == 128
    assert config["swa_num_attention_heads"] * 8 == share[
        "published_swa_num_attention_heads"] == 64
    assert config["vocab_size"] * 8 == share["published_vocab_size"] == 152064
    assert (config["n_routed_experts"], share["router_num_experts"]) == (8, 256)
    assert {"apply_mla_qkv_lora_rescale", "attention_gate_type",
            "indexer"} <= set(bench["assumed"])
    cfg = bench_run.model_config(config)
    plan = cfg.plan
    assert plan.prefix == ((LATENT_SELECT, MLP),)
    assert plan.unit == ((LATENT_SELECT, MOE),) + ((LATENT_WINDOW, MOE),) * 3
    assert (cfg.head_dim, cfg.v_head_dim, cfg.index_topk, cfg.attn_window) == (
        192, 128, 2048, 513)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 1_390_831_104  # 11.13 GB of train state at 8 B a parameter


def test_config_both_ways(cfg):
    hf = FAMILY.config_to_hf(cfg)
    assert hf["model_type"] == "dots3_note"
    assert hf["layer_types"] == ["full_attention"] * 2 + [
        "sliding_attention"] * 3
    back = dataclasses.replace(
        FAMILY.config_from_hf(hf), param_dtype="float32",
        router_bias_init_std=cfg.router_bias_init_std)
    assert back == cfg
    assert registry.infer_model_type(cfg) == "dots3_note"
    whole = _cfg(whole=True)
    assert (whole.head_share, whole.expert_share) == (1, False)
    assert "share" not in FAMILY.config_to_hf(whole)
    with pytest.raises(NotImplementedError, match="tensor names"):
        FAMILY.params_to_sd(cfg, {})


@pytest.mark.parametrize("key,value", [
    ("attention_gate_type", "elementwise"), ("rope_scaling", {"type": "yarn"}),
    ("scoring_func", "softmax"), ("layer_types", ["linear_attention"] * 5),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError):
        FAMILY.config_from_hf(dict(_toy_hf(), **{key: value}))


def test_refusals_stay_by_name(cfg):
    """Unequal widths without a plan by `window_pattern`, a sliding layer
    without its geometry, the serving plane and a mesh over `model`."""
    with pytest.raises(NotImplementedError, match="one width"):
        dataclasses.replace(cfg, window_pattern="", first_k_dense=0)
    with pytest.raises(NotImplementedError, match="own geometry"):
        dataclasses.replace(cfg, swa_n_heads=0)
    with pytest.raises(NotImplementedError, match="latent attention's"):
        dataclasses.replace(
            cfg, kv_lora_rank=0, q_lora_rank=0, head_dim=16, first_k_dense=0,
            n_experts=0)
    assert isinstance(
        tfm.plan_refusal(cfg, serving=True), tfm.LatentLayoutError)
    assert isinstance(
        tfm.plan_refusal(cfg, serving=False), tfm.LatentLayoutError)


# ------------------------------------------- the program against the reference


@pytest.mark.parametrize("whole", [False, True], ids=["share", "whole"])
def test_train_forward_over_packed_rows_matches_the_reference(whole):
    """Both layer kinds over a packed row of three sequences with restarts
    (70 and 50 tokens select 16 of their keys and pass the window of 9; 30
    also; a fourth of 12 selects nothing: every key visible)."""
    cfg = _cfg(whole)
    params = _params(cfg)
    seqs = _sequences(cfg, lens=(70, 50, 30, 12))
    tok, seg = _packed(seqs, 192)
    with jax.default_matmul_precision("highest"):
        got = tfm.forward(params, cfg, tok, seg, remat="full")[0]
        at = 0
        for s in seqs:
            np.testing.assert_allclose(
                got[at: at + len(s)], reference.logits(params, cfg, s), **TOL)
            at += len(s)


def test_prefill_then_decode_through_the_caches_matches_the_reference(
        cfg, params):
    """Prefill of 40 tokens then 30 decode steps: latent rows and index
    keys for the full layers, a ring of 9 that wraps in prefill and in
    decode, the decode step's top-16 gather; and what the caches hold at
    the end is the reference's, the ring in ring order."""
    seq = _sequences(cfg, lens=(70,), seed=3)[0]
    n_prompt, s_max = 40, 96
    with jax.default_matmul_precision("highest"):
        want = reference.logits(params, cfg, seq)
        _, kept = reference._hidden_and_kept(params, cfg, jnp.asarray(seq))
        cache = tfm.init_kv_cache(cfg, 1, s_max)
        assert cache.k is None and cache.wk is None and cache.ring == 9
        assert cache.latent.shape == (2, 1, s_max, 40)
        assert cache.ikeys.shape == (2, 1, s_max, 16)
        assert cache.wlatent.shape == (3, 1, 9, 48)
        logits, cache = tfm.prefill(
            params, cfg, jnp.asarray(seq[:n_prompt])[None],
            jnp.ones((1, n_prompt), jnp.int32), cache)
        np.testing.assert_allclose(logits[0], want[n_prompt - 1], **TOL)
        step = jax.jit(lambda tok, t, cache: tfm.decode_step(
            params, cfg, tok, t[None], cache, t, jnp.zeros((1,), jnp.int32),
            with_counts=True))
        for t in range(n_prompt, len(seq)):
            logits, cache, given = step(
                jnp.asarray(seq[t: t + 1]), jnp.int32(t), cache)
            np.testing.assert_allclose(logits[0], want[t], **TOL)
        read, visible, scored = np.asarray(given[LATENT_SELECT]).sum(0)
        assert (read, visible, scored) == (2 * 16, 2 * 70, 2 * 70)
    n = len(seq)
    for i, l in enumerate((0, 1)):
        np.testing.assert_allclose(cache.latent[i, 0, :n], kept[l][0], **TOL)
        np.testing.assert_allclose(cache.ikeys[i, 0, :n], kept[l][1], **TOL)
    at = np.arange(n - 9, n)
    for i, l in enumerate((2, 3, 4)):
        np.testing.assert_allclose(
            cache.wlatent[i, 0, at % 9], kept[l][0][at], **TOL)


def test_selection_below_and_above_index_topk():
    """`topk_mask`: every visible key while there are no more than k; the k
    largest past that, ties to the LOWER position; and the bisection agrees
    with a sort."""
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.normal(size=(3, 40)), jnp.float32)
    visible = jnp.arange(40)[None, :] < jnp.asarray([[5], [17], [40]])
    got = np.asarray(ls.topk_mask(scores, visible, 8))
    assert got[0].sum() == 5 and got[0, :5].all()
    for r in (1, 2):
        n = int(visible[r].sum())
        want = np.argsort(-np.asarray(scores[r, :n]), kind="stable")[:8]
        assert sorted(np.flatnonzero(got[r])) == sorted(want)
    tied = jnp.zeros((1, 40), jnp.float32).at[0, 3].set(1.0)
    got = np.asarray(ls.topk_mask(tied, jnp.ones((1, 40), bool), 4))
    assert list(np.flatnonzero(got[0])) == [0, 1, 2, 3]
    assert ls.topk_mask(scores, visible, 64) is visible


@pytest.mark.parametrize("p", [1, 4, 32])
def test_a_blocks_selection_packs_to_words_and_back(p):
    mask = jnp.asarray(
        np.random.default_rng(p).random((2, 64, 40)) < 0.3)
    bits = ls._pack(mask, p)
    assert bits.shape == (2, 64 // p, 40) and bits.dtype == jnp.uint32
    assert (ls._unpack(bits, p) == mask).all()
    assert ls._pack_width(128) == 32 and ls._pack_width(60) == 4


def _avals(jaxpr):
    """Every value a jaxpr and the jaxprs inside it make."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_array_of_a_rows_square_is_made_whole(cfg, params):
    """Selection and attention over a row of 256 tokens, two blocks of 128
    queries, forward and gradient: no value of the program has the row's
    length on two axes — scores, choices and probabilities exist a block
    at a time — and what a block keeps of its selection for the backward
    pass is the packed words."""
    s, h = 256, 1  # one head: a block's choice over its heads is no square
    small = dataclasses.replace(cfg, index_topk=16)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(x, (1, s, h, 24)) for x in ks[:2])
    v = jax.random.normal(ks[2], (1, s, h, 16))
    qi = jax.random.normal(ks[3], (1, s, small.index_n_heads, 16))
    w = jax.random.normal(ks[4], (1, s, small.index_n_heads))
    ki = jax.random.normal(ks[5], (1, s, 16))
    seg = jnp.ones((1, s), jnp.int32)

    def f(q, k, v):
        return jnp.sum(ls.select_attention(
            small, q, k, v, qi, w, ki, seg, 24 ** -0.5))

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    shapes = {(a.shape, str(a.dtype)) for a in _avals(jaxpr.jaxpr)
              if hasattr(a, "shape")}
    assert ((1, 128 // 32, s), "uint32") in shapes  # a block's words
    square = [x for x in shapes if sum(n >= s for n in x[0]) > 1 or (
        x[1] in ("bool", "uint32") and np.prod(x[0]) >= s * s)]
    assert not square, square  # nor the blocks' choices stacked
    # nor does the whole stack's gradient program keep the blocks' choices
    # stacked (a block's remat as a `custom_vjp` did: its visibility, which
    # depends on no weight, was saved from the forward pass for every block)
    row = jnp.ones((1, 2 * s), jnp.int32)

    def loss(p, tok, seg):
        x, aux = tfm.hidden_states(p, small, tok, seg, remat="full")
        return jnp.sum(x) + aux

    stack = jax.make_jaxpr(jax.grad(loss))(params, row, row)
    kept = {(a.shape, str(a.dtype)) for a in _avals(stack.jaxpr)
            if getattr(a, "shape", ())[:1] == (2 * s // 128,)}
    assert ((4, 1, 4, 2 * s), "uint32") in kept
    assert not [x for x in kept if x[1] == "bool" and x[0][-1] == 2 * s], kept
    # and it is the plain thing: every query's softmax over its selection
    got = ls.select_attention(small, q, k, v, qi, w, ki, seg, 24 ** -0.5)
    vis = jnp.tril(jnp.ones((s, s), bool))[None]
    mask = ls.topk_mask(ls.index_scores(qi, w, ki), vis, 16)
    a = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    pr = jax.nn.softmax(jnp.where(mask[:, None], a, -jnp.inf), axis=-1)
    np.testing.assert_allclose(
        got, jnp.einsum("bhqk,bkhd->bqhd", pr, v), rtol=2e-5, atol=2e-5)


def test_the_indexers_leaves_take_no_gradient_and_the_rest_match(cfg, params):
    """d(sum of next-token log-probs)/d(params) through the walk under
    `jax.checkpoint` against autodiff of the plain reference; the indexer's
    leaves and the router's bias get none from either."""
    seq = _sequences(cfg, lens=(60,), seed=2)[0]
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
    frozen = 0
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        leaf = name.split("'")[-2].removeprefix(tfm.DENSE_PREFIX)
        scale = float(jnp.abs(w).max())
        if leaf in FROZEN_LEAVES:
            frozen += 1
            assert scale == 0 and float(jnp.abs(g).max()) == 0, name
            continue
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)
    assert frozen == 2 * 5 + 1  # two full layers' indexers, the bias


def test_a_train_step_leaves_the_indexer_bit_for_bit(cfg):
    """A train step moves every matrix and leaves the indexer's leaves (the
    leading layer's under `dense_` too) and the router's bias bit for bit;
    Adam keeps no moment for them."""
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
    assert any("sw_wq_a" in m for m in moments)
    assert not any("idx_" in m or "router_bias" in m for m in moments)
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
    assert "latent_select/select_on_kernel" in stats
    after = jax.tree.map(np.asarray, engine.get_params())
    for name, leaf in before["blocks"].items():
        moved = (after["blocks"][name] != leaf).any()
        if name.removeprefix(tfm.DENSE_PREFIX) in FROZEN_LEAVES:
            assert not moved, name
        elif leaf.ndim >= 3:
            assert moved, name


# ------------------------------------------------------------------ the shares


def test_the_ranks_head_shares_add_up_to_the_uncut_layer():
    """The guide's shares test for HEADS: with 4 heads over 2 ranks of 2,
    the two partial attention outputs (gate included; the low-rank
    down-projections, their norms and the indexer whole on each) sum to
    what the plain reference gives for the uncut layer — in both
    geometries."""
    whole = _cfg(whole=True)
    params = _params(whole)
    seq = _sequences(whole, lens=(48,), seed=4)[0]
    x = jnp.take(params["embed"], jnp.asarray(seq), axis=0)
    pos = jnp.arange(len(seq))[None]
    for l, kind, geom, branch in (
            (1, "F", ls.full_geom, LATENT_SELECT),
            (2, "S", ls.window_geom, LATENT_WINDOW)):
        w = reference._layer_weights(params["blocks"], l, whole)
        with jax.default_matmul_precision("highest"):
            u = reference._rms_norm(x, w["ln1"], whole.rms_norm_eps)
            want, _, _ = reference._attention(x, u, w, whole, kind)
            total = 0.0
            for rank in range(2):
                part = dataclasses.replace(
                    whole, n_q_heads=2, n_kv_heads=2, swa_n_heads=2,
                    head_share=2)
                g_all, g = geom(whole), geom(part)
                blk = _head_slice(
                    {g_all.leaf(n): w[n] for n in w}, g_all, g, rank)
                (cos, sin), wrope = tfm._rope(part, pos)
                ctx = tfm.Ctx(part, cos, sin, jnp.ones_like(pos), wrope,
                              use_flash=False)
                out, _ = tfm.BRANCHES[branch].packed(ctx, u[None], blk)
                total = total + out[0]
        np.testing.assert_allclose(total, want, **TOL)


def _head_slice(blk, whole: ls.Geom, part: ls.Geom, rank: int):
    """A tensor-parallel rank's leaves of one layer: its heads' columns of
    the up-projections and of the gate, their rows of `wo`; all else whole."""
    lo, hi = rank * part.n_heads, (rank + 1) * part.n_heads
    out = dict(blk)

    def cols(name, width):
        w = blk[whole.leaf(name)]
        out[whole.leaf(name)] = w.reshape(
            w.shape[0], whole.n_heads, width)[:, lo:hi].reshape(w.shape[0], -1)

    cols("wq_b", whole.qk), cols("wk_b", whole.nope), cols("wv_b", whole.v)
    cols("hgate", 1)
    wo = blk[whole.leaf("wo")]
    out[whole.leaf("wo")] = wo.reshape(
        whole.n_heads, whole.v, -1)[lo:hi].reshape(-1, wo.shape[-1])
    return out


def test_the_ranks_expert_shares_add_up_to_the_uncut_layer():
    """8 experts over 2 ranks of 4: the partial MoE outputs, the shared
    expert counted once, sum to the reference's whole layer."""
    whole = _cfg(whole=True)
    params = _params(whole)
    blk = {k: v[0] for k, v in params["blocks"].items()
           if not k.startswith(tfm.DENSE_PREFIX)}
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(2, 24, whole.hidden_dim)), jnp.float32)
    x = h.reshape(-1, whole.hidden_dim)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(
            x, reference._layer_weights(params["blocks"], 1, whole), whole)
        shared = (jax.nn.silu(x @ blk["ws_g"]) * (x @ blk["ws_u"])) @ blk["ws_d"]
        total = 0.0
        for rank in range(2):
            part = dataclasses.replace(
                whole, n_experts=4, n_router_experts=8, expert_offset=4 * rank)
            mine = dict(blk, **{n: blk[n][4 * rank: 4 * rank + 4]
                                for n in ("wg", "wu", "wd")})
            total = total + tfm._mlp_moe(h, mine, part)[0].reshape(x.shape)
    np.testing.assert_allclose(total - shared, want, **TOL)


# ----------------------------------- faults and a precision lower are refused


def _logprobs(logits, seq):
    lp = jax.nn.log_softmax(jnp.asarray(logits)[:-1], axis=-1)
    return np.asarray(
        jnp.take_along_axis(lp, jnp.asarray(seq)[1:, None], 1))[:, 0]


@pytest.fixture(scope="module")
def system_logprobs(cfg, params):
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    with jax.default_matmul_precision("highest"):
        logits = tfm.forward(
            params, cfg, jnp.asarray(seq)[None],
            jnp.ones((1, len(seq)), jnp.int32))[0]
    return seq, _logprobs(logits, seq)


@pytest.mark.parametrize(
    "control", [{"fault": f} for f in reference.FAULTS]
    + [{"lower": l} for l in ("lower", "lower:cache", "lower:router")],
    ids=lambda c: next(iter(c.values())))
def test_each_fault_and_a_precision_lower_fail_the_fp32_bound(
        cfg, params, system_logprobs, control):
    """The three assumption controls, the dense read, the other faults and
    the lower precisions each lie outside the `fp32` bound the CPU holds
    the generator to; the system itself sits inside it."""
    seq, got = system_logprobs
    with jax.default_matmul_precision("highest"):
        right = np.abs(got - _logprobs(reference.logits(params, cfg, seq), seq))
        wrong = np.abs(got - _logprobs(
            reference.logits(params, cfg, seq, **control), seq))
    assert right.mean() <= _FP32["mean_abs"] and right.max() <= _FP32["max_abs"]
    assert wrong.mean() > _FP32["mean_abs"] or wrong.max() > _FP32["max_abs"], (
        control, wrong.mean(), wrong.max())


def test_the_program_itself_under_a_control_is_refused(cfg, params):
    """The same controls made in the PROGRAM (`scripts/dots3_controls.py`
    runs them on the chip): no rescale, a dense read — each against the
    reference proper."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    with jax.default_matmul_precision("highest"):
        want = _logprobs(reference.logits(params, cfg, seq), seq)
        for change in (dict(latent_rescale=False), dict(index_topk=0)):
            other = dataclasses.replace(cfg, **change)
            p = {**params, "blocks": {
                k: v for k, v in params["blocks"].items()
                if other.index_topk or "idx_" not in k}}
            got = _logprobs(tfm.forward(
                p, other, jnp.asarray(seq)[None],
                jnp.ones((1, len(seq)), jnp.int32))[0], seq)
            d = np.abs(got - want)
            assert d.mean() > _FP32["mean_abs"] or d.max() > _FP32["max_abs"]


@pytest.mark.parametrize("wave_tokens,rows", [(None, 8), (None, 2), (128, 2)],
                         ids=["every_row", "one_prefill", "waves"])
def test_what_the_generators_own_program_leaves_is_the_references(
        cfg, params, monkeypatch, wave_tokens, rows):
    """`check_generator` on the CPU: the static program over two prompts
    cut from the sequence, four rows each, its caches and its selection
    under the `fp32` limits — with every row prefilled (`every_row`: the
    check's `src` withheld), and with a prompt prefilled once and landed at
    its group's rows, the last compared slot a landed copy: in ONE prefill,
    its 128 slots of prompt against 8 new tokens (`one_prefill`), and in
    waves, as the cell's 13 k-token prompts go; with the ring read a slot
    off it is refused."""
    from areal_tpu.engines import generator

    if wave_tokens:
        monkeypatch.setattr(generator, "PREFILL_WAVE_TOKENS", wave_tokens)
    if rows == 8:
        monkeypatch.setattr(
            generator.GeneratorEngine, "_shared_rows", lambda *a: None)
    monkeypatch.setattr(reference, "CHECK_NEW", 8)
    built, build = [], reference._engine
    monkeypatch.setattr(
        reference, "_engine", lambda *a: built.append(build(*a)) or built[-1])
    seq = _sequences(cfg, lens=(120,), seed=9)[0]
    readings, problems = reference.check_generator(params, cfg, seq)
    assert not problems, problems
    assert built[0].last_pool_stats["prefill_rows"] == rows
    assert readings["n_tokens"] == 2 * 8 and readings["select_flips"] == 0.0
    assert readings["select_keys_flipped"] == 0.0
    real = reference.rows_readings

    def shifted(layers, kept, cfg):
        layers = [(at - (keys is None), rows, keys) for at, rows, keys in layers]
        return real(layers, kept, cfg)

    monkeypatch.setattr(reference, "rows_readings", shifted)
    _, problems = reference.check_generator(params, cfg, seq)
    assert any("rows_rel_err_max" in p for p in problems)


def test_generate_reports_the_three_caches_and_what_a_step_read(cfg, params):
    """`last_pool_stats` after a static generate call: latent rows, index
    keys and ring beside each other, the selection on in this window, and
    `latent_rows_read` / `latent_rows_visible` at index_topk over the
    context."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4)
    prompts = _sequences(cfg, lens=(40, 33, 50, 24), seed=11)
    toks, logps, gen_len = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=6),
        jax.random.PRNGKey(0))
    stats = eng.last_pool_stats
    assert stats["select_on_kernel"] == 1 and stats["latent_ring_rows"] == 9
    assert stats["index_cache_bytes"] * 40 == stats["latent_cache_bytes"] * 16
    visible = sum(2 * (len(p) + t + 1) for p in prompts for t in range(6))
    assert stats["latent_rows_visible"] == visible
    assert stats["index_keys_scored"] == visible
    assert stats["latent_rows_read"] == 2 * 4 * 6 * 16
    assert (gen_len == 6).all()
    # ... and the sampled tokens' log-probs are the reference's.
    seq = np.concatenate([prompts[2], toks[2, :6]])
    with jax.default_matmul_precision("highest"):
        want = _logprobs(reference.logits(params, cfg, seq), seq)
    np.testing.assert_allclose(logps[2, :6], want[-6:], rtol=2e-3, atol=2e-4)


# ----------------------------------- the gradient program compiled for v5e
# (Mosaic and XLA:TPU for real with no chip attached, about a minute.  It
# had a file of its own from PR 64 to PR 71: a file of ONE long case is
# handed out last and was the tail of every run, 55 s of a cold one.)

ROW = 13_312  # the cell's longest row: one sequence
# `memory_analysis().temp_size_in_bytes` as this compile read it (PR 64:
# 4,437,567,488, with the selection a [13312, 13312] mask held whole and
# with it made and used a block at a time alike: the program's peak is not
# in the attention).  Beside 11.13 GB of train state the chip's 15.75 GB
# leave 4.6.
_GRAD_TEMP_BYTES = 4_437_567_488


def test_the_gradient_program_compiles_for_v5e_beside_its_state(
        v5e_chips, monkeypatch):
    """The one compile that sizes the cell: the gradient of the stack over
    one packed row of 13,312 tokens at every published width, `remat="full"`
    as the train engine has it.  Its temporaries are pinned (a hundredth of
    room): `peak_hbm_gb` reads 15.4 of 15.75 on the chip, so what grows
    them has to show here, before a chip call.  No value of the program
    holds the row's length on two axes as a choice (`pred`) or a score
    (`f32`): the selection is made and used a block of queries at a time,
    and kept for the backward pass as words of 32 queries."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    chip = SingleDeviceSharding(v5e_chips[0])
    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, sharding=chip),
        shapes)
    row = jax.ShapeDtypeStruct((1, ROW), jnp.int32, sharding=chip)

    def loss(p, tokens, seg):
        x, aux = tfm.hidden_states(p, big, tokens, seg, remat="full")
        return jnp.sum(x.astype(jnp.float32)) + aux

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.grad(loss)).trace(
            params, row, row).lower().compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= _GRAD_TEMP_BYTES * 1.01, temp
    text = compiled.as_text()
    assert f"u32[1,4,{ROW}]" in text  # a block's selection, packed
    for square in (f"pred[1,{ROW},{ROW}]", f"f32[1,{ROW},{ROW}]",
                   f"pred[{ROW // 128},1,128,{ROW}]"):  # whole, or stacked
        assert square not in text, square


# `dots3n-docrl8-longctx` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

"""OLMoE (allenai/OLMoE-1B-7B) at toy size on the CPU, seeded random
weights, fp32: the program's block — QK-norm over the whole projection, a
64-expert-style router whose top-k weights are NOT renormalised, dropless
grouped experts — against the plain reference of
`benchmark/references/olmoe.py`, through the train forward, the static
prefill + decode through the cache and the ragged paged stream; the HF
reader both ways; the sharding rules of the new leaves; the counters.
Logits and log-probabilities are compared, never sampled tokens.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.models.hf import registry
from areal_tpu.parallel import sharding
from benchmark.references import olmoe as reference

HF_TOY = {  # the published keys at toy sizes (benchmark config's `toy`)
    "model_type": "olmoe", "attention_bias": False, "clip_qkv": None,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
    "max_position_embeddings": 1024, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "router_aux_loss_coef": 0.01,
    "tie_word_embeddings": False, "vocab_size": 512,
}
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    cfg = registry.HF_FAMILIES["olmoe"].config_from_hf(HF_TOY)
    return dataclasses.replace(cfg, param_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    """Random weights with NON-trivial norm scales, so that a norm applied
    per head, or not at all, cannot pass for the whole-projection one."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(5))
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    for k, name in zip(ks, ("q_norm", "k_norm")):
        shape = p["blocks"][name].shape
        p["blocks"][name] = 1.0 + 0.3 * jax.random.normal(k, shape)
    return p


def _sequences(cfg, lens=(21, 13), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _logprobs(logits, targets):
    """log p(target) from fp32 logits: what the reference returns."""
    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return np.asarray(
        jnp.take_along_axis(lp, jnp.asarray(targets)[:, None], axis=-1)[:, 0]
    )


def _system_logprobs(params, cfg, seq):
    t = jnp.asarray(seq)[None]
    logits = tfm.forward(params, cfg, t, jnp.ones_like(t))[0]
    return _logprobs(logits[:-1], seq[1:])


def _diff_from_reference(params, cfg, ref_cfg=None, seed=0):
    """mean and max |system - reference| log-prob over two sequences."""
    d = np.concatenate([
        np.abs(_system_logprobs(params, cfg, s)
               - reference.next_token_logprobs(params, ref_cfg or cfg, s))
        for s in _sequences(cfg, seed=seed)
    ])
    return float(d.mean()), float(d.max())


# ----------------------------------------------------------- the HF reader


def test_config_both_ways_and_the_family_it_saves_as(cfg):
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_intermediate_dim,
            cfg.intermediate_dim) == (8, 2, 32, 32)
    assert cfg.qk_norm and not cfg.moe_norm_topk and not cfg.qkv_bias
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.rms_norm_eps) == (16, 4, 1e-5)
    assert cfg.moe_aux_loss_coef == 0.01 and not cfg.tied_embeddings
    assert cfg.moe_dispatch == "grouped"  # dropless: capacity is not OLMoE
    fam = registry.HF_FAMILIES["olmoe"]
    hf = fam.config_to_hf(cfg)
    assert registry.infer_model_type(cfg) == hf["model_type"] == "olmoe"
    for key, value in HF_TOY.items():
        if key != "rope_scaling":
            assert hf[key] == value, key
    back = dataclasses.replace(fam.config_from_hf(hf), param_dtype="float32")
    assert back == cfg
    # mixtral keeps today's behaviour: renormalised, no QK-norm.
    mix = registry.HF_FAMILIES["mixtral"].config_from_hf(dict(
        HF_TOY, model_type="mixtral", num_local_experts=8))
    assert mix.moe_norm_topk and not mix.qk_norm
    assert registry.infer_model_type(mix) == "mixtral"


@pytest.mark.parametrize("key,value", [("clip_qkv", 8.0),
                                       ("attention_bias", True)])
def test_what_is_not_implemented_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        registry.HF_FAMILIES["olmoe"].config_from_hf(dict(HF_TOY, **{key: value}))


def test_state_dict_round_trip_by_the_published_names(cfg, params, tmp_path):
    fam = registry.HF_FAMILIES["olmoe"]
    sd = fam.params_to_sd(cfg, params)
    for name, shape in {
        "model.layers.2.mlp.gate.weight": (8, 64),
        "model.layers.0.mlp.experts.7.gate_proj.weight": (32, 64),
        "model.layers.0.mlp.experts.7.up_proj.weight": (32, 64),
        "model.layers.1.mlp.experts.0.down_proj.weight": (64, 32),
        "model.layers.1.self_attn.q_norm.weight": (64,),
        "model.layers.1.self_attn.k_norm.weight": (64,),
        "model.layers.0.self_attn.q_proj.weight": (64, 64),
        "lm_head.weight": (512, 64),
    }.items():
        assert sd[name].shape == shape, name
    assert not any("bias" in k or "block_sparse_moe" in k for k in sd)
    registry.save_hf_checkpoint(
        str(tmp_path), cfg, params, model_type=registry.infer_model_type(cfg))
    cfg2, back = registry.load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert dataclasses.replace(cfg2, param_dtype="float32") == cfg
    a = jax.tree_util.tree_flatten_with_path(params)[0]
    b = jax.tree_util.tree_flatten_with_path(back)[0]
    assert [p for p, _ in a] == [p for p, _ in b] and len(a) == 15
    for (path, x), (_, y) in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6,
                                   err_msg=str(path))


# ------------------------------------------------- system vs the reference


def test_train_forward_over_packed_rows_matches_the_reference(cfg, params):
    """Two sequences packed into one row with a padded tail, as the train
    engine packs them: `forward` and the fused `hidden_states` +
    `per_token_output` head both give the reference's log-probs."""
    seqs = _sequences(cfg)
    s_len = 40
    tokens, seg = np.zeros((1, s_len), np.int32), np.zeros((1, s_len), np.int32)
    off = 0
    for i, s in enumerate(seqs):
        tokens[0, off: off + len(s)], seg[0, off: off + len(s)] = s, i + 1
        off += len(s)
    tokens, seg = jnp.asarray(tokens), jnp.asarray(seg)
    logits = tfm.forward(params, cfg, tokens, seg, remat="full")[0]
    x, aux, counts = tfm.hidden_states(
        params, cfg, tokens, seg, with_moe_counts=True)
    fused = np.asarray(tfm.per_token_output(params, cfg, x, tokens, seg))[0]
    off = 0
    for s in seqs:
        want = reference.next_token_logprobs(params, cfg, s)
        got = _logprobs(logits[off: off + len(s) - 1], s[1:])
        np.testing.assert_allclose(got, want, **TOL)
        np.testing.assert_allclose(fused[off: off + len(s) - 1], want, **TOL)
        off += len(s)
    # Every REAL token chose k experts in every layer; padding is not counted.
    assert counts.shape == (3, 8) and aux > 0
    assert (np.asarray(counts).sum(axis=1) == 2 * 34).all()


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        cfg, params):
    """The static route: right-aligned prompts of two lengths prefilled,
    then one token at a time through the cache; the logits of every step
    give the log-probs of the reference's full pass."""
    seqs = _sequences(cfg, lens=(20, 17))
    lens, sp, steps = [8, 5], 8, 12
    want = [reference.next_token_logprobs(params, cfg, s) for s in seqs]
    tokens, seg = np.zeros((2, sp), np.int32), np.zeros((2, sp), np.int32)
    for r, (n, s) in enumerate(zip(lens, seqs)):
        tokens[r, sp - n:], seg[r, sp - n:] = s[:n], 1
    cache = tfm.init_kv_cache(cfg, 2, sp + steps, dtype=jnp.float32)
    logits, cache = tfm.prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray(seg), cache)
    valid_from = jnp.asarray([sp - n for n in lens], jnp.int32)
    for step in range(steps):
        for r, (n, s) in enumerate(zip(lens, seqs)):
            got = _logprobs(logits[r][None], s[n + step][None])
            np.testing.assert_allclose(
                got, want[r][n + step - 1: n + step], **TOL,
                err_msg=f"row {r} step {step}")
        tok = jnp.asarray([s[n + step] for n, s in zip(lens, seqs)], jnp.int32)
        pos = jnp.asarray([n + step for n in lens], jnp.int32)
        logits, cache, counts = tfm.decode_step(
            params, cfg, tok, pos, cache, jnp.int32(sp + step), valid_from,
            with_counts=True)
        assert (np.asarray(counts["moe"]).sum(axis=1) == 2 * 2).all()  # rows x k


def test_the_ragged_paged_stream_matches_the_reference(cfg, params):
    """The serving route's forward: two sequences as one packed stream of
    lanes, each attending its own pages, K/V written as it goes."""
    seqs = _sequences(cfg, lens=(19, 11))
    page_size, max_pages = 8, 3
    cache = tfm.init_paged_kv_cache(cfg, 2 * max_pages, page_size,
                                    dtype=jnp.float32)
    page_table = jnp.arange(2 * max_pages, dtype=jnp.int32).reshape(2, -1)
    tokens = np.concatenate(seqs + [np.zeros(2, np.int32)])  # 2 dead lanes
    positions = np.concatenate(
        [np.arange(len(s)) for s in seqs] + [np.zeros(2)]).astype(np.int32)
    row_of = np.concatenate(
        [np.full(len(s), r) for r, s in enumerate(seqs)] + [np.full(2, 2)]
    ).astype(np.int32)
    logits, _ = tfm.decode_step_ragged_paged(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), cache,
        page_table, jnp.asarray(row_of))
    off = 0
    for s in seqs:
        got = _logprobs(logits[off: off + len(s) - 1], s[1:])
        np.testing.assert_allclose(
            got, reference.next_token_logprobs(params, cfg, s), **TOL)
        off += len(s)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_grouped_dispatch_equals_the_dense_oracle(cfg, params, norm_topk):
    """Dropless: sorting tokens by expert and `ragged_dot` give what every
    expert computing every token gives, whichever way the weights go."""
    cfg = dataclasses.replace(cfg, moe_norm_topk=norm_topk)
    seq = _sequences(cfg, lens=(33,))[0]
    t = jnp.asarray(seq)[None]
    grouped = tfm.forward(params, cfg, t, jnp.ones_like(t))
    dense = tfm.forward(
        params, dataclasses.replace(cfg, moe_dispatch="dense"), t,
        jnp.ones_like(t))
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), **TOL)
    # ... and the reference follows the config's `norm_topk_prob` too.
    mean, worst = _diff_from_reference(params, cfg)
    assert mean < 1e-4 and worst < 1e-3


def test_qk_norm_on_and_off_differ_and_each_matches_its_oracle(cfg, params):
    on = _system_logprobs(params, cfg, _sequences(cfg)[0])
    off_cfg = dataclasses.replace(cfg, qk_norm=False)
    off = _system_logprobs(params, off_cfg, _sequences(cfg)[0])
    assert np.abs(on - off).max() > 0.02
    mean, worst = _diff_from_reference(params, cfg)
    assert mean < reference.TOLERANCE_FP32["mean_abs"]
    assert worst < reference.TOLERANCE_FP32["max_abs"]
    # Off: the block without the norm is the mixtral-style block, whose
    # dense oracle it equals; the OLMoE reference refuses it.
    seq = jnp.asarray(_sequences(cfg)[0])[None]
    np.testing.assert_allclose(
        np.asarray(tfm.forward(params, off_cfg, seq, jnp.ones_like(seq))),
        np.asarray(tfm.forward(
            params, dataclasses.replace(off_cfg, moe_dispatch="dense"), seq,
            jnp.ones_like(seq))), **TOL)
    assert _diff_from_reference(params, off_cfg, ref_cfg=cfg)[0] > (
        reference.TOLERANCE["mean_abs"])


def _per_head_qk_norm(params, cfg):
    """The wrong reading: the norm's mean taken over each head's values."""
    def kv(h, blk, c, cos, sin, scope=None):
        b, s, _ = h.shape

        def norm(x, w, heads):
            x = x.reshape(b, s, heads, c.head_dim).astype(jnp.float32)
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + c.rms_norm_eps)
            return x * w.reshape(heads, c.head_dim)

        q = norm(h @ blk["wq"], blk["q_norm"], c.n_q_heads)
        k = norm(h @ blk["wk"], blk["k_norm"], c.n_kv_heads)
        v = (h @ blk["wv"]).reshape(b, s, c.n_kv_heads, c.head_dim)
        return (*tfm.apply_rotary(q, k, cos, sin), v)

    return kv


@pytest.mark.parametrize("wrong", [
    "renormalised_weights", "per_head_qk_norm", "dropped_tokens",
])
def test_the_tolerance_fails_what_is_not_olmoe(cfg, params, wrong, monkeypatch):
    """The bf16 tolerance of the chip's check (mean 0.015) is far looser
    than fp32 needs, and must still refuse each of these at toy size.  (A
    router whose logits are rounded to bf16 is NOT among them: it moves
    the weights by 2^-9 and swaps near-ties only, mean 0.003 here — the
    size of the rounding the tolerance exists to admit; see the reference's
    docstring.)"""
    sys_cfg = cfg
    if wrong == "renormalised_weights":
        sys_cfg = dataclasses.replace(cfg, moe_norm_topk=True)
    elif wrong == "dropped_tokens":  # capacity dispatch, one slot an expert
        sys_cfg = dataclasses.replace(
            cfg, moe_dispatch="topk", moe_capacity_factor=0.3)
    else:
        monkeypatch.setattr(tfm, "_block_kv", _per_head_qk_norm(params, cfg))
    worst_of_seeds = max(
        _diff_from_reference(params, sys_cfg, ref_cfg=cfg, seed=s)
        for s in range(3)
    )
    mean, worst = worst_of_seeds
    assert (mean > reference.TOLERANCE["mean_abs"]
            or worst > reference.TOLERANCE["max_abs"]), (mean, worst)


def test_the_reference_counts_routing_flips(cfg, params):
    """fp32 weights: the replay in the system's arithmetic IS fp32, so no
    pair flips; bf16 weights: pairs are counted, flips a small share."""
    seq = _sequences(cfg, lens=(40,))[0]
    reference.next_token_logprobs(params, cfg, seq)
    assert reference.LAST_ROUTING == {
        "router_pairs": 40 * 3, "router_flips": 0, "router_drift": 0}
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    reference.next_token_logprobs(low, cfg, seq)
    r = reference.LAST_ROUTING
    assert r["router_pairs"] == 120 and r["router_flips"] <= r["router_pairs"] // 10
    assert 0 <= r["router_drift"] <= r["router_pairs"] // 3


# ------------------------------------------------------- sharding, counters


@pytest.mark.parametrize("mode", ["d2", "m2", "d2f2m2"])
def test_a_sharded_build_places_the_new_leaves(cfg, params, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    specs = sharding.param_pspecs(params)
    assert specs["blocks"]["q_norm"] == specs["blocks"]["ln1"]
    assert specs["blocks"]["k_norm"] == specs["blocks"]["ln1"]
    assert len(specs["blocks"]["wg"]) == 4  # the expert leaves' own rule
    assert sharding.check_divisibility(params, mesh) is None
    placed = sharding.shard_params(params, mesh)
    t = jnp.asarray(np.stack(_sequences(cfg, lens=(16,) * 4)))
    got = jax.jit(lambda p, t: tfm.forward(p, cfg, t, jnp.ones_like(t)))(
        placed, jax.device_put(t, sharding.named(mesh, sharding.batch_pspec())))
    want = tfm.forward(params, cfg, t, jnp.ones_like(t))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_one_chip_hand_back_aliases_every_leaf(cfg, params):
    """`realloc.reshard` on one chip: every leaf of the 15 stays the
    trainer's own buffer, the expert leaves included."""
    from areal_tpu.parallel import realloc

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    placed = sharding.shard_params(params, mesh)
    shardings = sharding.tree_named(mesh, sharding.param_pspecs(placed))
    out, stats = realloc.reshard_counted(placed, shardings, jnp.float32)
    assert stats["leaves_aliased"] == 15
    assert stats["leaves_resharded"] == stats["leaves_put"] == 0
    assert out["blocks"]["wg"] is placed["blocks"]["wg"]


def test_counters_on_a_hand_made_routing():
    """Four tokens, eight experts, top-2, a router that reads the choice
    off the token: the rows per expert, what the decode loop sums of them
    and what the train step reports are what the hand count says."""
    from areal_tpu.models.branches import LoopStep
    from areal_tpu.engines.train import _moe_stats

    cfg = ModelConfig(
        n_layers=1, hidden_dim=8, n_q_heads=1, n_kv_heads=1, head_dim=8,
        intermediate_dim=4, vocab_size=16, n_experts=8, n_experts_per_tok=2,
        moe_intermediate_dim=4, moe_norm_topk=False, param_dtype="float32")
    blk = {
        "router": 10.0 * jnp.eye(8),
        "wg": jnp.ones((8, 8, 4)), "wu": jnp.ones((8, 8, 4)),
        "wd": jnp.ones((8, 4, 8)),
    }
    choice = [(0, 1), (0, 1), (0, 5), (2, 0)]  # expert 0: all four tokens
    h = np.zeros((1, 4, 8), np.float32)
    for t, (a, b) in enumerate(choice):
        h[0, t, a], h[0, t, b] = 2.0, 1.0
    out, aux, counts = tfm._mlp_moe(jnp.asarray(h), blk, cfg)
    assert np.asarray(counts).tolist() == [4, 2, 1, 0, 0, 1, 0, 0]
    assert out.shape == (1, 4, 8) and np.isfinite(float(aux))
    valid = jnp.asarray([[True, True, True, False]])
    _, _, real = tfm._mlp_moe(jnp.asarray(h), blk, cfg, valid=valid)
    assert np.asarray(real).tolist() == [3, 2, 0, 0, 0, 1, 0, 0]
    step = np.asarray(tfm.BRANCHES["moe"].counter.step(
        jnp.stack([counts, real]), cfg, LoopStep(None, None, None, 4)))
    assert step.tolist() == [(4 + 3) / 2, (4 + 3) / 2, 1.0]
    stats = _moe_stats(aux, jnp.stack([counts, real]), cfg, 8)
    assert float(stats["moe/aux_loss"]) == float(aux)
    # fullest expert over the mean per expert: 4 / (8/8) and 3 / (6/8).
    np.testing.assert_allclose(
        float(stats["moe/load_max_over_mean"]), (4.0 + 4.0) / 2)
    # Every expert is held here: no slab, and no counter of one.
    assert set(stats) == {"moe/aux_loss", "moe/load_max_over_mean"}
    assert set(_moe_stats(aux, None, cfg, 8)) == {"moe/aux_loss"}  # under PP


def test_generate_and_train_report_the_counters_and_dense_models_none(cfg):
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models.config import tiny_config

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    sample = SequenceSample(
        keys={"packed_prompts"}, ids=["a", "b"],
        seqlens={"packed_prompts": [[6], [9]]},
        data={"packed_prompts": np.arange(8, 23, dtype=np.int32)},
    )
    g = GenerationHyperparameters(n=2, max_new_tokens=5, greedy=True)
    stats = {}
    for name, c in (("olmoe", cfg), ("dense", tiny_config())):
        engine = GeneratorEngine(
            c, tfm.init_params(c, jax.random.PRNGKey(1)), mesh,
            eos_token_id=c.vocab_size)  # unreachable: every row runs 5 steps
        engine.generate(sample, MicroBatchSpec(), g, inflight=False)
        stats[name] = dict(engine.last_pool_stats)
    assert not any(k.startswith("moe_") for k in stats["dense"])
    moe = stats["olmoe"]
    assert moe["moe_decode_steps"] == 5
    # 4 rows x top-2 of 8 experts: between 2 and 8 touched, 1 to 4 rows.
    assert 2 <= moe["moe_experts_touched"] <= 8
    assert 1 <= moe["moe_rows_per_expert_max"] <= 4


# ------------------------- decode: the expert leaves reach ragged_dot in place


def _plain_decode_step(params, cfg, tok, pos, cache, slot, valid_from):
    """`decode_step` written plainly: a Python loop that slices every leaf
    at its layer and calls `_mlp_moe` on the slice — what the layer scan
    does on the sliced path (PR 27 keeps it here as the oracle)."""
    b = tok.shape[0]
    x = tfm._embed(params, cfg, tok, pos)[:, None, :]
    cos, sin = tfm.rope_cos_sin(pos[:, None], cfg.head_dim, cfg.rope_theta)
    kc, vc, counts = cache.k, cache.v, []
    for li in range(cfg.n_layers):
        blk = jax.tree.map(lambda a: a[li], params["blocks"])
        h = tfm._norm(x, blk["ln1"], blk.get("ln1_b"), cfg)
        q, k, v = tfm._block_kv(h, blk, cfg, cos, sin)
        kc = kc.at[li, :, slot].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[li, :, slot].set(v[:, 0].astype(vc.dtype))
        attn = tfm.decode_attention(q, kc[li], vc[li], valid_from, slot + 1)
        x = x + tfm._attn_out(attn.reshape(b, 1, cfg.q_dim), blk, cfg)
        h2 = tfm._norm(x, blk["ln2"], blk.get("ln2_b"), cfg)
        out, _, c = tfm._mlp_moe(h2, blk, cfg)
        x, counts = x + out, counts + [c]
    logits = tfm._head(params, cfg, tfm._final_norm(params, cfg, x))[:, 0]
    return logits, tfm.KVCache(k=kc, v=vc), jnp.stack(counts)


@pytest.mark.parametrize("n_layers", [1, 3])
def test_decode_in_place_equals_the_per_layer_formulation(cfg, n_layers):
    """Logits, cache and [L, E] counts of `decode_step` with the stacked
    expert leaves handed to `ragged_dot` whole (zero group sizes outside
    the layer) against slicing each layer: a step in which ONE expert pair
    gets every row (identical rows), then steps in which experts of a
    layer get no row."""
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    assert tfm.expert_leaves_in_place(cfg, params["blocks"])
    b, k, steps = 4, cfg.n_experts_per_tok, 3
    rng = np.random.default_rng(3)
    cache = plain = tfm.init_kv_cache(cfg, b, steps, dtype=jnp.float32)
    valid_from = jnp.zeros((b,), jnp.int32)
    saw_empty = False
    for step in range(steps):
        tok = (np.full(b, 7) if step == 0
               else rng.integers(0, cfg.vocab_size, size=b))
        tok = jnp.asarray(tok, jnp.int32)
        pos = jnp.full((b,), step, jnp.int32)
        logits, cache, counts = tfm.decode_step(
            params, cfg, tok, pos, cache, jnp.int32(step), valid_from,
            with_counts=True)
        want_logits, plain, want_counts = _plain_decode_step(
            params, cfg, tok, pos, plain, step, valid_from)
        counts = np.asarray(counts["moe"])
        assert counts.shape == (n_layers, cfg.n_experts)
        assert (counts == np.asarray(want_counts)).all()
        assert (counts.sum(axis=1) == b * k).all()
        if step == 0:  # identical rows: k experts hold all b rows each
            assert (np.sort(counts, axis=1)[:, -k:] == b).all()
        else:
            spread = ((counts > 0).sum(axis=1) > k).any()  # rows differ
            saw_empty |= bool(spread and (counts == 0).any())
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(want_logits), rtol=1e-5, atol=1e-5)
        for got, want in ((cache.k, plain.k), (cache.v, plain.v)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert saw_empty


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _decode_jaxpr(cfg, params, b=4, in_place=None):
    cache = tfm.init_kv_cache(cfg, b, 8, dtype=jnp.float32)
    z = jnp.zeros((b,), jnp.int32)
    return jax.make_jaxpr(
        lambda p, c: tfm.decode_step(
            p, cfg, z, z, c, jnp.int32(0), z, with_counts=True,
            experts_in_place=in_place)
    )(params, cache)


def _expert_slices(closed, cfg):
    """Values of one layer's expert-leaf shape [E, in, out] (or [1, E, in,
    out]) that a traced decode step makes by slicing: the scan's per-layer
    `xs` and any (dynamic_)slice / squeeze / gather result."""
    d, f, e = cfg.hidden_dim, cfg.intermediate_dim, cfg.n_experts
    shapes = {(e, d, f), (e, f, d), (1, e, d, f), (1, e, f, d)}
    found = []
    for _, eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name == "scan":
            n_xs = len(eqn.invars) - eqn.params["num_consts"] - eqn.params["num_carry"]
            found += [v.aval.shape for v in eqn.invars[len(eqn.invars) - n_xs:]
                      if v.aval.shape[1:] in shapes]
        elif eqn.primitive.name in ("dynamic_slice", "slice", "squeeze", "gather"):
            found += [v.aval.shape for v in eqn.outvars if v.aval.shape in shapes]
    return found


def test_traced_decode_step_slices_no_expert_leaf(cfg, params):
    """Structure: in the traced step each `ragged_dot`'s right operand is
    a reshape of a value the layer scan takes whole (a const of the scan:
    the parameter), [L*E, in, out]; nothing of one layer's [E, in, out]
    shape is sliced out.  The sliced path (what a sharded expert axis
    keeps) is the control: there the same search finds all three leaves."""
    le = cfg.n_layers * cfg.n_experts
    closed = _decode_jaxpr(cfg, params)
    assert _expert_slices(closed, cfg) == []
    ragged = [(j, e) for j, e in _eqns(closed.jaxpr)
              if e.primitive.name == "ragged_dot_general"
              or e.primitive.name == "ragged_dot"]
    assert len(ragged) == 3
    scan = [e for _, e in _eqns(closed.jaxpr) if e.primitive.name == "scan"
            and any(r[0] is e.params["jaxpr"].jaxpr for r in ragged)][0]
    body, n_consts = scan.params["jaxpr"].jaxpr, scan.params["num_consts"]
    for j, eqn in ragged:
        assert j is body
        rhs, sizes = eqn.invars[1], eqn.invars[2]
        assert rhs.aval.shape[0] == le and sizes.aval.shape == (le,)
        (made,) = [e for e in body.eqns if rhs in e.outvars]
        assert made.primitive.name == "reshape"
        src = made.invars[0]
        assert src in body.invars[:n_consts]  # closed over, not sliced
        outer = scan.invars[body.invars.index(src)]
        assert outer in closed.jaxpr.invars  # the parameter itself
        assert outer.aval.shape[:2] == (cfg.n_layers, cfg.n_experts)
    sliced = _decode_jaxpr(cfg, params, in_place=False)
    assert len(_expert_slices(sliced, cfg)) == 3


def test_decode_step_lowered_for_tpu_reshapes_the_parameter(cfg, params,
                                                            monkeypatch):
    """The same on the module `jax.export` lowers for the TPU (no chip
    needed): three ragged dots over [L*E, in, out], no [E, in, out]
    tensor anywhere in the step."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = tfm.init_kv_cache(cfg, 4, 8, dtype=jnp.float32)
    z = jnp.zeros((4,), jnp.int32)

    def step(p, c, in_place):
        return tfm.decode_step(p, cfg, z, z, c, jnp.int32(0), z,
                               with_counts=True, experts_in_place=in_place)

    d, f, e = cfg.hidden_dim, cfg.intermediate_dim, cfg.n_experts
    le = cfg.n_layers * e
    one_layer = (f"tensor<{e}x{d}x{f}xf32>", f"tensor<{e}x{f}x{d}xf32>")
    text = {
        flag: jax.export.export(
            jax.jit(lambda p, c: step(p, c, flag)), platforms=["tpu"]
        )(params, cache).mlir_module()
        for flag in (True, False)
    }
    assert text[True].count("ragged_dot") >= 3
    assert f"tensor<{le}x{d}x{f}xf32>" in text[True]
    assert f"tensor<{le}x{f}x{d}xf32>" in text[True]
    assert not any(t in text[True] for t in one_layer)
    assert all(t in text[False] for t in one_layer)  # the control


def test_a_dense_decode_step_traces_what_it_did():
    """A dense model has no expert leaves: no ragged dot, and the traced
    step is the same whichever way the question is answered — the program
    of the sliced path, letter for letter."""
    from areal_tpu.models.config import tiny_config

    dense = tiny_config()
    params = tfm.init_params(dense, jax.random.PRNGKey(2))
    assert not tfm.expert_leaves_in_place(dense, params["blocks"])
    asked = str(_decode_jaxpr(dense, params))
    assert asked == str(_decode_jaxpr(dense, params, in_place=False))
    assert "ragged_dot" not in asked


@pytest.mark.parametrize("mode,in_place", [("d1", 1), ("m2", 1), ("f2", 0)])
def test_static_generate_is_the_same_in_place_and_says_which(
        cfg, params, mode, in_place, monkeypatch):
    """Greedy static generate on the toy config: the route's counter says
    whether the ragged kernels read the parameters' own buffers (one
    chip; hidden axis sharded) or the scan's slices (expert axis sharded
    over fsdp), and tokens, log-probs and MoE counters are those of the
    sliced path on one chip."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    sample = SequenceSample(
        keys={"packed_prompts"}, ids=["a", "b"],
        seqlens={"packed_prompts": [[6], [9]]},
        data={"packed_prompts": np.arange(8, 23, dtype=np.int32)},
    )
    g = GenerationHyperparameters(n=2, max_new_tokens=5, greedy=True)

    def run(mode):
        pc = ParallelConfig.from_str(mode)
        mesh = make_mesh(pc, jax.devices()[: pc.world_size])
        engine = GeneratorEngine(cfg, params, mesh, eos_token_id=cfg.vocab_size)
        out = engine.generate(sample, MicroBatchSpec(), g, inflight=False)
        return out, dict(engine.last_pool_stats)

    out, stats = run(mode)
    assert stats["moe_expert_leaves_in_place"] == in_place
    monkeypatch.setattr(tfm, "expert_leaves_in_place", lambda *a: False)
    want, want_stats = run("d1")
    assert want_stats["moe_expert_leaves_in_place"] == 0
    assert (np.asarray(out.data["packed_input_ids"])
            == np.asarray(want.data["packed_input_ids"])).all()
    np.testing.assert_allclose(
        np.asarray(out.data["packed_logprobs"]),
        np.asarray(want.data["packed_logprobs"]), rtol=1e-4, atol=1e-5)
    for key in ("moe_experts_touched", "moe_rows_per_expert_max",
                "moe_decode_steps"):
        assert stats[key] == want_stats[key], key


# ---------------------------------------------- the cell's window, rehearsed

# `olmoe-decode-tail` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

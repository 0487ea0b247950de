"""SDAR-MoE (JetLM/SDAR-30B-A3B-Chat, `sdar_moe`: the Qwen3-MoE layer under
generation by DIFFUSION OVER BLOCKS) at toy size on the CPU, seeded random
weights, fp32 — against the plain reference of
`benchmark/references/sdar_moe.py` (dense masks from positions, one forward
a block, no cache, no packing): the two-stream train forward and
`inference` over packed rows of several sequences (tails 0-3, a response
that ends inside a block, ids-only and loss-mask calls); prefill and the
block loop through the cache (log-probs, the rows the commits left, the
sampler's trajectory replayed from the same uniforms; two and three
denoising steps a block, EOS inside a block, a budget that is no multiple of the block);
generator == trainer == reference; the eight ranks' parts against the uncut
layer; the HF reader and the parameter names both ways; the parameter
count; every fault, assumption control and lower precision refused under
the fp32 bounds; the refusals by name; and the guard that every
autoregressive model's programs lower to the text they lowered to.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import FinetuneSpec, GenerationHyperparameters
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import block_diffusion as bd
from areal_tpu.engines import packing
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.models.hf import registry
from areal_tpu.ops import sampling
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import sdar_moe as reference
from benchmark.tests.test_bd import *  # noqa: F401,F403 — the cases (PR 68)
from tests import lowered_programs

CONFIG = "sdar-30b-a3b-chat-l8-e16.json"
CELL = "sdar-rollout64-512"
FAMILY = registry.HF_FAMILIES["sdar_moe"]
B = 4  # the block
TOL = dict(rtol=5e-4, atol=5e-5)  # fp32 reassociation through the layers
FP32 = reference.TOLERANCE_FP32  # the reference's own bound on log-probs


def _toy_hf(held=4):
    config = files.load_json("configs", CONFIG)
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout64-512.json"))
    config["num_experts"] = held
    if held == 8:
        del config["share"]
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return tfm.init_params(cfg, jax.random.PRNGKey(0))


def _seqs(lens, seed=0, vocab=500):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _engine(cfg, params, slots=8, eos=None):
    from areal_tpu.engines.generator import GeneratorEngine

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    return GeneratorEngine(
        cfg, params, mesh, max_decode_batch=slots,
        eos_token_id=cfg.vocab_size if eos is None else eos,
        donation_safe_swap=False)


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    row = next(
        json.loads(l) for l in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if json.loads(l)["name"] == "SDAR-30B-A3B-Chat"
    ) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["file"].endswith(CONFIG))
    assert entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "mask_token_id"]
    if row is not None:
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["mask_token_id"]) == (
                8, 16, 18992, 18991)
    assert config["vocab_size"] * 8 == 151936
    assert config["share"]["router_num_experts"] == 128
    assert (config["block_length"], config["denoising_steps"],
            config["remasking_strategy"]) == (4, 2, "low_confidence_static")
    bench = config["benchmark"]
    assert set(bench["reduced"]) == set(entry["reduced"])
    assert {"block_length", "in_place_prediction", "mask_token_id",
            "noise_schedule"} <= set(bench["assumed"])
    assert bench["weights_seed"] == 68 and bench["reference"] == "sdar_moe"


def test_the_parameter_count_of_the_cut():
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    shapes = jax.eval_shape(lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 834_899_968
    layer = sum(int(np.prod(x.shape[1:])) for x in shapes["blocks"].values())
    assert layer == 94_638_336


def test_config_both_ways(cfg):
    hf = FAMILY.config_to_hf(cfg)
    assert hf["model_type"] == "sdar_moe" and hf["share"] == {
        "router_num_experts": 8, "rank": 0}
    assert (hf["block_length"], hf["mask_token_id"], hf["denoising_steps"],
            hf["remasking_strategy"]) == (4, 511, 2, "low_confidence_static")
    back = dataclasses.replace(
        FAMILY.config_from_hf(hf), param_dtype="float32")
    assert back == cfg
    assert registry.infer_model_type(cfg) == "sdar_moe"
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.moe_norm_topk
    # The published file states the layer alone: the family's defaults.
    bare = {k: v for k, v in _toy_hf().items() if k not in (
        "block_length", "mask_token_id", "denoising_steps",
        "remasking_strategy")}
    bare["vocab_size"] = 151936
    d = FAMILY.config_from_hf(bare)
    assert (d.block_length, d.mask_token_id, d.denoising_forwards) == (
        4, 151669, 4)


def test_a_configuration_that_names_another_unmasking_rule_is_refused():
    """The block loop reveals a fixed count of places a step; the family's
    rule by a confidence threshold is not built, and is not run as the
    static one."""
    hf = {**_toy_hf(), "remasking_strategy": "low_confidence_dynamic"}
    with pytest.raises(NotImplementedError, match="remasking_strategy"):
        FAMILY.config_from_hf(hf)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("use_sliding_window", True),
    ("hidden_act", "gelu"), ("rope_scaling", {"type": "yarn"}),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        FAMILY.config_from_hf(dict(_toy_hf(), **{key: value}))


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pre = "model.layers.1."
    assert sd[pre + "self_attn.q_norm.weight"].shape == (cfg.head_dim,)
    assert sd[pre + "self_attn.k_norm.weight"].shape == (cfg.head_dim,)
    assert sd[pre + "mlp.gate.weight"].shape == (8, cfg.hidden_dim)
    assert sd[pre + "mlp.experts.3.gate_proj.weight"].shape == (
        cfg.moe_intermediate_dim, cfg.hidden_dim)
    assert pre + "mlp.experts.3.down_proj.weight" in sd
    assert pre + "mlp.experts.4.up_proj.weight" not in sd  # held 0-3


# --------------------------------------------- the two-stream train forward

LENS = [13, 22, 8, 31, 17, 5, 12]  # lengths 0-3 mod 4
PROMPTS = [5, 9, 3, 14, 6, 2, 8]  # prompt tails 1, 1, 3, 2, 2, 2, 0


def _sample(cfg, with_mask):
    seqs = _seqs(LENS)
    data = {"packed_input_ids": np.concatenate(seqs)}
    masks = []
    for l, p in zip(LENS, PROMPTS):
        m = np.zeros(l, np.float32)
        m[p - 1: l - 1] = 1  # storage index j - 1: the response's tokens
        masks.append(m)
    if with_mask:
        data["loss_mask"] = np.concatenate(masks)
    return seqs, masks, SequenceSample(
        keys=set(data), ids=list(range(len(LENS))),
        seqlens={k: [[l] for l in LENS] for k in data}, data=data)


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "flash"])
@pytest.mark.parametrize("with_mask", [False, True], ids=["ids", "loss_mask"])
def test_the_two_stream_forward_matches_the_reference(
        cfg, params, with_mask, use_flash):
    seqs, masks, sample = _sample(cfg, with_mask)
    pk = packing.pack_sample(
        sample, "packed_input_ids",
        extra_keys=("loss_mask",) if with_mask else (),
        max_tokens_per_row=128, block_length=B,
        mask_token_id=cfg.mask_token_id,
        wanted_key="loss_mask" if with_mask else None)
    a = {k: jnp.asarray(v) for k, v in pk.arrays.items()}
    assert pk.n_rows >= 2  # several sequences a row, several rows
    # Every stream starts on a multiple of the block in its row.
    seg, stream = pk.arrays["segment_ids"], pk.arrays["stream_ids"]
    code = np.where(seg[0] > 0, seg[0] * 2 + stream[0], 0)
    starts = np.flatnonzero((np.diff(code, prepend=0) != 0) & (code > 0))
    assert len(starts) > 4 and (starts % B == 0).all()
    x, _ = tfm.hidden_states(
        params, cfg, a["tokens"], a["segment_ids"], positions=a["positions"],
        stream_ids=a["stream_ids"], use_flash=use_flash)
    out = tfm.block_token_output(
        params, cfg, x, a["labels"], a["label_mask"], a["head_index"])
    flat = pk.unpack(np.asarray(out))
    off = 0
    for seq, mask in zip(seqs, masks):
        l = len(seq)
        want = reference.block_logprobs(params, cfg, seq)[1:]
        got = flat[off: off + l]
        assert got[l - 1] == 0  # the trailing slot of the storage convention
        sel = mask[: l - 1] > 0 if with_mask else np.ones(l - 1, bool)
        np.testing.assert_allclose(got[: l - 1][sel], want[sel], **TOL)
        assert (got[: l - 1][~sel] == 0).all()
        off += l
    if with_mask:  # the extras ride at the masked stream's places
        np.testing.assert_array_equal(
            pk.unpack(pk.arrays["loss_mask"]), sample.data["loss_mask"])
    st = pk.stats
    assert st["clean_slots"] == sum(LENS)
    assert st["align_pad_slots"] == sum(-l % B for l in LENS)
    assert st["masked_slots"] % B == 0
    assert st["wanted_tokens"] == (
        int(sum(m.sum() for m in masks)) if with_mask
        else sum(LENS) - len(LENS))
    assert st["head_rows"] == pk.n_rows * pk.arrays["head_index"].shape[1]


def test_a_row_s_budget_counts_stream_slots(cfg):
    """Seven sequences of 642 tokens (prompt 130) take 1,160 slots each:
    642 clean + 2 of alignment + 129 masked blocks; seven fill a row of
    8,192, where fourteen sequences' tokens would."""
    lens, prompt = [642] * 8, 130
    mask = np.zeros(642, np.float32)
    mask[prompt - 1: 641] = 1
    sample = SequenceSample(
        keys={"packed_input_ids", "loss_mask"}, ids=list(range(8)),
        seqlens={k: [[l] for l in lens]
                 for k in ("packed_input_ids", "loss_mask")},
        data={"packed_input_ids": np.zeros(sum(lens), np.int32),
              "loss_mask": np.tile(mask, 8)})
    pk = packing.pack_sample(
        sample, "packed_input_ids", extra_keys=("loss_mask",),
        max_tokens_per_row=8192, block_length=B, mask_token_id=511,
        wanted_key="loss_mask")
    assert (pk.n_rows, pk.row_len) == (2, 8192)
    rows = [sum(1 for r, _, _ in pk.seq_map if r == i) for i in range(2)]
    assert rows == [7, 1]
    assert pk.stats["masked_slots"] == 8 * 129 * B
    assert (pk.stats["clean_slots"] + pk.stats["align_pad_slots"]
            + pk.stats["masked_slots"]) == 8 * 1160
    assert pk.arrays["head_index"].shape == (2, 3584)  # 7 x 512 wanted


def test_inference_and_a_train_step_through_the_engine(cfg, params):
    """`TrainEngine.forward` (the interface's `inference`) returns l_j at
    storage index j - 1 for ids alone, and a train step under a loss mask
    moves every matrix; the pack's counters say what the streams cost."""
    from areal_tpu.engines import train
    from areal_tpu.interfaces.ppo import _logprob_post, _mask_count

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    engine = train.TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 8, 8))
    seqs, masks, sample = _sample(cfg, with_mask=False)
    out = engine.forward(
        sample, MicroBatchSpec(max_tokens_per_mb=128), post_fn=_logprob_post,
        output_key="logprobs", token_key="packed_input_ids")
    got = np.asarray(out.data["logprobs"])
    assert out.seqlens["logprobs"] == [[l] for l in LENS]
    off = 0
    for seq in seqs:
        want = reference.block_logprobs(params, cfg, seq)[1:]
        np.testing.assert_allclose(
            got[off: off + len(seq) - 1], want, rtol=2e-3, atol=2e-4)
        off += len(seq)
    _, _, sample = _sample(cfg, with_mask=True)

    def loss_fn(logp, batch):
        loss = -(logp * (batch["loss_mask"] > 0)).sum()
        return loss, {"nll_sum": loss}

    before = jax.tree.map(np.asarray, engine.get_params())
    stats = engine.train_batch(
        sample, MicroBatchSpec(max_tokens_per_mb=128), loss_fn=loss_fn,
        loss_weight_fn=_mask_count, extra_keys=("loss_mask",))
    assert np.isfinite(stats["loss"]) and stats["grad_norm"] > 0
    assert stats["n_micro_batches"] >= 2  # a row a step
    after = engine.get_params()
    for name, leaf in before["blocks"].items():
        if leaf.ndim >= 3:
            assert (np.asarray(after["blocks"][name]) != leaf).any(), name
    pack = engine.last_pack_stats
    assert pack["bd/clean_slots"] == sum(LENS)
    assert 1.0 < pack["bd/stream_overhead"] < 2.0
    assert pack["bd/head_rows"] >= pack["bd/wanted_tokens"] > 0


def test_the_gradient_matches_the_reference(cfg, params):
    """d(sum of the response's log-probs) / d(params) through the
    two-stream forward (flash kernels, interpreted) against `jax.grad` of
    the reference's block-by-block forwards."""
    seq = _seqs([14], seed=3)[0]
    sample = SequenceSample(
        keys={"packed_input_ids"}, ids=[0],
        seqlens={"packed_input_ids": [[14]]}, data={"packed_input_ids": seq})
    pk = packing.pack_sample(
        sample, "packed_input_ids", block_length=B,
        mask_token_id=cfg.mask_token_id)
    a = {k: jnp.asarray(v) for k, v in pk.arrays.items()}

    def ours(p):
        x, _ = tfm.hidden_states(
            p, cfg, a["tokens"], a["segment_ids"], positions=a["positions"],
            stream_ids=a["stream_ids"], use_flash=True, remat="full")
        return jnp.sum(tfm.block_token_output(
            p, cfg, x, a["labels"], a["label_mask"], a["head_index"]))

    def theirs(p):
        total = 0.0
        for b0 in range(0, 14, B):
            logits = reference.masked_block_logits(
                p, cfg, seq[:b0], np.full(B, cfg.mask_token_id))
            lsm = jax.nn.log_softmax(logits, axis=-1)
            own = seq[b0: b0 + B]
            first = 1 if b0 == 0 else 0  # token 0 wants no log-prob
            total += jnp.sum(lsm[np.arange(first, len(own)), own[first:]])
        return total

    g, w = jax.grad(ours)(params), jax.grad(theirs)(params)
    for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(g)[0], jax.tree.leaves(w)):
        scale = float(jnp.abs(y).max()) + 1e-6
        assert float(jnp.abs(x - y).max()) <= 2e-3 * scale, path


# ---------------------------------------------- prefill and the block loop

GEN_PROMPTS = (8, 9, 10, 11, 5, 3, 14, 21)  # every tail, a prompt under B


@pytest.mark.parametrize("steps", [2, 3])
def test_the_block_loop_matches_the_reference(cfg, params, steps):
    """What the static program returns — tokens, log-probs, the step that
    revealed each token, the cache the commits left — against the
    reference: the log-probs are `block_logprobs` of the sampled tokens,
    the trajectory is `replay`'s from the same uniforms, the rows are the
    clean forward's roped K and V.  Three steps a block of four: two
    places at step 0, one at each later step."""
    cfg = dataclasses.replace(cfg, denoising_steps=steps)
    eng = _engine(cfg, params)
    prompts = _seqs(GEN_PROMPTS, seed=1)
    key = jax.random.PRNGKey(3)
    g = GenerationHyperparameters(n=1, max_new_tokens=10)  # no multiple of B
    toks, logps, gen_len, step_of, cache = eng._block_rollout(
        prompts, g, key, with_cache=True, steps=True)
    assert (gen_len == 10).all()
    sp = 128
    for r, prompt in enumerate(prompts):
        n, tail = len(prompt), len(prompt) % B
        seq = np.concatenate([prompt, toks[r, :10]])
        assert (seq != cfg.mask_token_id).all()
        want = reference.block_logprobs(params, cfg, seq)[n:]
        d = np.abs(want - logps[r, :10])
        assert d.mean() <= FP32["mean_abs"] and d.max() <= FP32["max_abs"]

        def uniforms(k, s, r=r):
            return np.asarray(jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(key, k), s),
                (len(prompts), B)))[r]

        rt, rs = reference.replay(
            params, cfg, prompt, uniforms, -(-(tail + 10) // B), steps)
        np.testing.assert_array_equal(rt[tail: tail + 10], toks[r, :10])
        np.testing.assert_array_equal(rs[tail: tail + 10], step_of[r, :10])
        # The cache: position p of row r lies at slot sp - whole + p.
        first = sp - n // B * B
        whole = len(seq) // B * B
        kv = jnp.stack([cache.k[:, r, first: first + whole],
                        cache.v[:, r, first: first + whole]], axis=2)
        _, rows = reference.clean_forward(params, cfg, seq)
        readings = reference.rows_readings(kv, rows[:, :whole])
        assert not reference.rows_problems(
            readings, reference.ROWS_TOLERANCE_FP32), readings
    stats = eng.last_pool_stats
    n_blocks = -(-(3 + 10) // B)
    assert stats["bd/blocks"] == n_blocks == stats["bd/commit_forwards"]
    assert stats["bd/first_block_forwards"] == 1
    assert stats["bd/tokens_kept"] == 80
    assert stats["bd/tokens_dropped"] == sum(
        n_blocks * B - (len(p) % B) - 10 for p in prompts)
    assert stats["bd/denoise_forwards"] == steps * n_blocks
    assert stats["bd/denoising_steps"] == steps
    assert stats["bd/revealed_by_step"][steps:] == [0.0] * (B - steps)
    assert stats["bd/tokens_per_forward"] == 80 / (
        8 * ((steps + 1) * n_blocks + 1))


@pytest.mark.parametrize("loop", ["block", "token"])
def test_the_block_loop_of_a_thin_share_gathers_a_slab_and_a_token_loop_none(
        loop):
    """A rank that holds 1 of its router's 8 experts, 128 rows: a block
    forward routes 128 x 4 x 2 = 1,024 pairs and `decode_slab_rows` reads
    512, so `block_step` (through `_walk`, stacked leaves) gathers one slab
    a layer and forward, says so in `last_pool_stats`, and its kept tokens'
    log-probs hold to the clean forward's as `check_generator` asks.  The
    token loop of the same share routes 256 pairs a step: no slab, and the
    counter vector its program carries has the shape it had."""
    cfg = _cfg(held=1)
    if loop == "token":
        cfg = dataclasses.replace(cfg, block_length=0, mask_token_id=-1)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params, slots=128)
    assert eng._expert_leaves_in_place
    prompts = _seqs([5 + r % 8 for r in range(128)], seed=7)
    toks, logps, gen_len = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=6),
        jax.random.PRNGKey(9))
    stats = eng.last_pool_stats
    rows = 128 * (B if loop == "block" else 1)
    steps, pairs = stats["moe_decode_steps"], rows * cfg.n_experts_per_tok
    assert stats["moe_rows_routed"] == steps * cfg.n_layers * pairs
    assert 0 < stats["moe_rows_local"] < stats["moe_rows_routed"] / 4
    width = tfm.decode_counters(cfg)["moe"].width(cfg, rows)
    if loop == "token":
        assert tfm.decode_slab_rows(cfg, pairs) == pairs == 256
        assert width == 5 == eng._decode_sums["moe"].size
        assert not {"moe_rows_gathered", "moe_slab_fill_max"} & set(stats)
        return
    assert tfm.decode_slab_rows(cfg, pairs) == 512 and width == 8
    assert stats["bd/blocks"] == 3 and steps == 1 + 3 * 3
    assert stats["moe_rows_gathered"] == steps * cfg.n_layers * 512
    assert 0 < stats["moe_slab_fill_max"] < 1
    for r in (0, 3, 77, 127):
        seq = np.concatenate([prompts[r], toks[r, :6]])
        want = reference.block_logprobs(params, cfg, seq)[len(prompts[r]):]
        d = np.abs(want - logps[r, :6])
        assert d.mean() <= FP32["mean_abs"] and d.max() <= FP32["max_abs"]


def test_a_prefix_through_the_cache_gives_the_full_forward_s_logits(
        cfg, params):
    """prefill of two blocks + one block step of the clean third block ==
    the block-causal forward of all twelve tokens, row for row; and the
    reference's clean forward agrees."""
    toks = jnp.asarray(_seqs([12], seed=5)[0])[None]
    seg = jnp.ones((1, 12), jnp.int32)
    full = tfm.forward(params, cfg, toks, seg)
    ref_logits, _ = reference.clean_forward(params, cfg, np.asarray(toks[0]))
    keep = np.arange(cfg.vocab_size) != cfg.mask_token_id
    np.testing.assert_allclose(
        np.asarray(full[0])[:, keep], np.asarray(ref_logits)[:, keep], **TOL)
    assert np.isneginf(np.asarray(full[0, :, cfg.mask_token_id])).all()
    cache = tfm.init_kv_cache(cfg, 1, 32)
    ptok = jnp.zeros((1, 16), jnp.int32).at[0, 8:].set(toks[0, :8])
    pseg = (jnp.arange(16) >= 8).astype(jnp.int32)[None]
    none, cache = tfm.prefill(params, cfg, ptok, pseg, cache, head=False)
    assert none is None
    logits, cache, counts = tfm.block_step(
        params, cfg, toks[:, 8:12], jnp.arange(8, 12)[None], cache, 16,
        jnp.array([8]))
    np.testing.assert_allclose(
        np.asarray(logits[0])[:, keep], np.asarray(full[0, 8:12])[:, keep],
        **TOL)
    assert counts["moe"].shape == (cfg.n_layers, cfg.n_experts)


def test_eos_inside_a_block_ends_the_row_there(cfg, params):
    """With EOS an ordinary token, every row ends at its first EOS: the
    tokens behind it in its block are dropped, `gen_len` counts the EOS."""
    eos = 7
    eng = _engine(cfg, params, eos=eos)
    # Greedy under a flat head never ends; sample at a high temperature
    # over a vocabulary cut to 16 tokens so that EOS comes up.
    g = GenerationHyperparameters(n=1, max_new_tokens=40, top_k=16)
    prompts = _seqs(GEN_PROMPTS, seed=2)
    toks, logps, gen_len = eng.static_rollout(
        prompts, g, jax.random.PRNGKey(11))
    ended = 0
    for r in range(len(prompts)):
        gl = int(gen_len[r])
        assert 1 <= gl <= 40
        assert eos not in toks[r, : gl - 1]
        assert (toks[r, gl:] == 0).all() and (logps[r, gl:] == 0).all()
        if gl < 40:
            assert toks[r, gl - 1] == eos
            ended += 1
    stats = eng.last_pool_stats
    assert stats["bd/tokens_kept"] == int(gen_len.sum())
    if ended == len(prompts):  # the loop stopped before its last block
        assert stats["bd/blocks"] <= -(-(3 + 40) // B)


def test_generator_trainer_and_reference_agree(cfg, params):
    """The RL step's three readings of one quantity: the generator's
    returned log-probs, the trainer's two-stream recomputation under the
    rollout's loss mask, and the reference, per token."""
    eng = _engine(cfg, params)
    prompts = _seqs(GEN_PROMPTS, seed=4)
    toks, logps, gen_len = eng.static_rollout(
        prompts, GenerationHyperparameters(n=1, max_new_tokens=9),
        jax.random.PRNGKey(5))
    seqs = [np.concatenate([p, toks[r, :9]]) for r, p in enumerate(prompts)]
    lens = [len(s) for s in seqs]
    masks = []
    for p, l in zip(prompts, lens):
        m = np.zeros(l, np.float32)
        m[len(p) - 1: l - 1] = 1
        masks.append(m)
    sample = SequenceSample(
        keys={"packed_input_ids", "loss_mask"}, ids=list(range(len(seqs))),
        seqlens={k: [[l] for l in lens]
                 for k in ("packed_input_ids", "loss_mask")},
        data={"packed_input_ids": np.concatenate(seqs),
              "loss_mask": np.concatenate(masks)})
    pk = packing.pack_sample(
        sample, "packed_input_ids", extra_keys=("loss_mask",),
        max_tokens_per_row=128, block_length=B,
        mask_token_id=cfg.mask_token_id, wanted_key="loss_mask")
    a = {k: jnp.asarray(v) for k, v in pk.arrays.items()}
    x, _ = tfm.hidden_states(
        params, cfg, a["tokens"], a["segment_ids"], positions=a["positions"],
        stream_ids=a["stream_ids"])
    flat = pk.unpack(np.asarray(tfm.block_token_output(
        params, cfg, x, a["labels"], a["label_mask"], a["head_index"])))
    off = 0
    for r, (p, seq) in enumerate(zip(prompts, seqs)):
        trainer = flat[off + len(p) - 1: off + len(seq) - 1]
        want = reference.block_logprobs(params, cfg, seq)[len(p):]
        np.testing.assert_allclose(logps[r, :9], trainer, **TOL)
        np.testing.assert_allclose(trainer, want, **TOL)
        off += len(seq)


def test_the_references_generator_check_passes_on_the_cpu(cfg, params,
                                                          monkeypatch):
    monkeypatch.setattr(reference, "CHECK_SLOTS", 8)
    monkeypatch.setattr(reference, "CHECK_NEW", 10)
    monkeypatch.setattr(reference, "CHECK_PROMPTS", (9, 37))
    monkeypatch.setattr(reference, "_CHECKED", [])
    readings, problems = reference.check_generator(
        params, cfg, _seqs([37], seed=6)[0])
    assert not problems, readings
    assert readings["logprob_max_abs"] <= FP32["max_abs"]
    assert readings["n_tokens"] == 20


def test_a_lower_precision_cache_is_refused_by_the_rows(cfg, params,
                                                        monkeypatch):
    """The harness's call with the reference's K and V in 8 bits: the rows
    the program's cache holds lie outside the limits, the log-probs come
    back NaN (not `correct`), and the check ran once for the weights."""
    monkeypatch.setattr(reference, "CHECK_SLOTS", 8)
    monkeypatch.setattr(reference, "CHECK_NEW", 10)
    monkeypatch.setattr(reference, "CHECK_PROMPTS", (9, 37))
    monkeypatch.setattr(reference, "_CHECKED", [])
    seq = _seqs([37], seed=6)[0]
    got = reference.next_token_logprobs(params, cfg, seq, lower="lower:cache")
    assert np.isnan(got).all()
    (_, low, (readings, problems)), = reference._CHECKED
    assert low == "lower:cache" and problems
    assert readings["rows_rel_err_unrouted"] > 1e-3
    calls = []
    monkeypatch.setattr(
        reference, "generator_rollouts", lambda *a, **k: calls.append(a))
    assert reference.check_generator(
        params, cfg, seq[:20], "lower:cache") == (readings, problems)
    assert not calls


def test_a_commit_left_out_is_refused_by_the_rows(cfg, params, monkeypatch):
    """The cache must hold what the COMMIT forward wrote: with the commit
    skipped the block's slots keep the last denoising forward's part-masked
    rows, and the rows check refuses them."""
    real = tfm.block_step

    def no_commit(params, cfg, x, pos, cache, slot, valid_from, head=True,
                  **kw):
        if head:
            return real(params, cfg, x, pos, cache, slot, valid_from, **kw)
        return None, cache, {"moe": jnp.zeros(
            (cfg.n_layers, cfg.n_experts), jnp.int32)}

    monkeypatch.setattr(tfm, "block_step", no_commit)
    monkeypatch.setattr(reference, "CHECK_SLOTS", 8)
    monkeypatch.setattr(reference, "CHECK_NEW", 10)
    monkeypatch.setattr(reference, "CHECK_PROMPTS", (9, 37))
    monkeypatch.setattr(reference, "_CHECKED", [])
    readings, problems = reference.check_generator(
        params, cfg, _seqs([37], seed=6)[0])
    assert problems and readings["rows_rel_err_max"] > 0.1


# ------------------------------------------------------------- the sampler


def test_the_static_rule_reveals_the_most_confident_places():
    conf = jnp.asarray([[0.2, 0.9, 0.9, 0.1], [0.5, 0.5, 0.5, 0.5]])
    masked = jnp.asarray([[True, True, True, False], [True, False, True, True]])
    got = sampling.reveal_by_confidence(conf, masked, 2)
    np.testing.assert_array_equal(got, [[False, True, True, False],
                                        [True, False, True, False]])
    # Fewer masked than asked for: all of them, none that was not masked.
    got = sampling.reveal_by_confidence(conf, masked, 4)
    np.testing.assert_array_equal(got, masked)


@pytest.mark.parametrize("steps,want", [
    (0, (1, 1, 1, 1)), (2, (2, 2)), (3, (2, 1, 1))])
def test_the_steps_share_a_block_s_places_the_remainder_first(
        cfg, steps, want):
    assert bd.reveals(dataclasses.replace(cfg, denoising_steps=steps)) == want


def test_a_draw_s_confidence_is_its_probability():
    logits = jnp.log(jnp.asarray([[0.1, 0.6, 0.3], [0.7, 0.2, 0.1]]))
    tok, conf = sampling.draw_with_confidence(
        logits, jnp.asarray([0.5, 0.95]))
    np.testing.assert_array_equal(tok, [1, 2])
    np.testing.assert_allclose(conf, [0.6, 0.1], rtol=1e-5)
    tok, conf = sampling.draw_with_confidence(
        logits, jnp.asarray([0.5, 0.95]), greedy=True)
    np.testing.assert_array_equal(tok, [1, 0])
    np.testing.assert_allclose(conf, [0.6, 0.7], rtol=1e-5)


# ------------------------------------------------------ faults and controls


@pytest.fixture(scope="module")
def control_seq():
    return _seqs([34], seed=9)[0]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_of_the_reference_fails_the_fp32_bound(
        cfg, params, control_seq, fault):
    want = reference.block_logprobs(params, cfg, control_seq)[1:]
    got = reference.next_token_logprobs(
        params, cfg, control_seq, fault=fault)
    d = np.abs(got - want)[B:]  # block 0 has no prefix to see wrongly
    assert d.mean() > FP32["mean_abs"] or d.max() > FP32["max_abs"], (
        d.mean(), d.max())


@pytest.mark.parametrize("lower", [
    reference.LOWER_PRECISION, "lower:router", "lower:cache"])
def test_a_precision_lower_fails_the_fp32_bound(
        cfg, params, control_seq, lower):
    want = reference.block_logprobs(params, cfg, control_seq)[1:]
    got = reference.block_logprobs(params, cfg, control_seq, lower=lower)[1:]
    d = np.abs(got - want)
    assert d.mean() > FP32["mean_abs"] or d.max() > FP32["max_abs"]


def test_the_mask_token_s_logit_is_left_out_everywhere(cfg, params):
    seq = _seqs([8], seed=8)[0]
    logits = reference.masked_block_logits(
        params, cfg, seq[:4], np.full(B, cfg.mask_token_id))
    assert np.isneginf(np.asarray(logits)[:, cfg.mask_token_id]).all()
    lsm = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(np.exp(lsm).sum(-1), 1.0, rtol=1e-5)
    from areal_tpu.ops.functional import fused_label_logprobs

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, cfg.hidden_dim))
    head = params["lm_head"]
    got = fused_label_logprobs(
        x, head, jnp.asarray([[1, 2, 3, 4]]), jnp.ones((1, 4)),
        exclude=cfg.mask_token_id)
    want = jax.nn.log_softmax(
        tfm.mask_logit_out(cfg, x[0] @ head), axis=-1)[
            np.arange(4), [1, 2, 3, 4]]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ the shares


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer():
    """Ranks 0-7 of 8 hold one expert each of the router's 8: the parts of
    one expert layer's routed sum they compute add up to what the reference
    gives for the layer with all 8 held."""
    whole = _cfg(held=8)
    params = tfm.init_params(whole, jax.random.PRNGKey(0))
    blk = {k: v[1] for k, v in params["blocks"].items()}
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(1, 24, whole.hidden_dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(h[0], blk, whole)
    total = 0
    for rank in range(8):
        part = dataclasses.replace(
            whole, n_experts=1, n_router_experts=8, expert_offset=rank)
        held = dict(blk, **{
            n: blk[n][rank: rank + 1] for n in ("wg", "wu", "wd")})
        out, _, counts = tfm._mlp_moe(h, held, part)
        assert counts.shape == (1,)
        total = total + out[0]
    np.testing.assert_allclose(total, want, rtol=5e-4, atol=5e-4)


# ----------------------------------------------------------------- refusals


def test_the_serving_plane_and_speculation_are_refused_by_name(cfg, params):
    refusal = tfm.plan_refusal(cfg, serving=True)
    assert isinstance(refusal, tfm.BlockLayoutError)
    assert "STATIC decode program" in str(refusal)
    eng = _engine(cfg, params, slots=2)
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
        with pytest.raises(tfm.BlockLayoutError, match="inflight=True"):
            eng.generate(sample, MicroBatchSpec(), gg, **kwargs)
    with pytest.raises(tfm.BlockLayoutError, match="min_new_tokens"):
        eng.static_rollout(
            [np.arange(6)], dataclasses.replace(g, min_new_tokens=2),
            jax.random.PRNGKey(0))
    out = eng.generate(sample, MicroBatchSpec(), g)  # the static program
    assert out.seqlens["packed_input_ids"] == [[10]]


@pytest.mark.parametrize("layout", ["m2", "s2", "p2"])
def test_untested_mesh_layouts_are_refused_by_name(cfg, layout):
    from areal_tpu.parallel import sharding

    pc = ParallelConfig.from_str(layout)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.BlockLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)


@pytest.mark.parametrize("changes,match", [
    (dict(window_pattern="SF", attn_window=4), "block-causal form"),
    (dict(kv_lora_rank=8), "block-causal form"),
    (dict(layer_pattern="M*"), "block-causal form"),
    (dict(full_attn_interval=2), "block-causal form"),
    (dict(is_critic=True), "block-causal form"),
    (dict(mask_token_id=-1), "mask_token_id"),
    (dict(mask_token_id=64), "mask_token_id"),
    (dict(denoising_steps=-1), "denoising_steps"),
    (dict(denoising_steps=5), "denoising_steps"),
])
def test_a_block_length_beside_what_has_no_block_causal_form_raises(
        changes, match):
    base = dict(
        n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
        intermediate_dim=128, vocab_size=64, block_length=4, mask_token_id=63)
    with pytest.raises((ValueError, NotImplementedError), match=match):
        ModelConfig(**{**base, **changes})


def test_the_sampler_s_steps_are_the_model_s_and_have_one_source(cfg):
    """`denoising_steps` is the model's own (the configuration's key): a
    request names none, and the loop, its counters and every FLOP count
    read `ModelConfig.denoising_forwards`."""
    from areal_tpu.base import monitor
    from benchmark import peaks_bd

    assert not {"denoising_steps", "remasking", "confidence_threshold"} & {
        f.name for f in dataclasses.fields(GenerationHyperparameters)}
    assert cfg.denoising_forwards == 2
    whole = dataclasses.replace(cfg, denoising_steps=0)
    assert whole.denoising_forwards == B == len(bd.reveals(whole))
    assert peaks_bd.forwards_of(whole, 10, 16) == (5, 20, 5)
    assert peaks_bd.forwards_of(cfg, 10, 16) == (5, 10, 5)
    for count in (monitor.flops_generate, peaks_bd.flops_generate):
        assert count(whole, [8], [16]) > count(cfg, [8], [16])


def test_the_flops_of_a_step_count_stream_slots(cfg):
    from areal_tpu.base import monitor

    plain = dataclasses.replace(cfg, block_length=0)
    head = 2.0 * cfg.hidden_dim * cfg.vocab_size
    fwd, base = (monitor.flops_forward(c, 100, 5000.0) for c in (cfg, plain))
    assert fwd == pytest.approx(2 * base - head * 100)
    gen = monitor.flops_generate(cfg, [8], [16])
    per_token = 2.0 * monitor.matmul_params(cfg)
    assert gen > 3 * (per_token - head) * 16  # T + 1 forwards a block


# ------------------------------------------- the unchanged-programs guard

# `python3 -m tests.lowered_programs` at 086071c, the commit before this
# mechanism came in.
_AT_THE_PARENT = {
    ("qwen2.5-math-1.5b", "grad"): "e9ea341f7b0070f007f83979e68c6011216b15015db3168a294e77476ae61fe7",
    ("qwen2.5-math-1.5b", "prefill"): "5cefd7e3b1b1adfe0e64fe4632ab8f92393b870b5556a5694c56b826bb959d0f",
    ("qwen2.5-math-1.5b", "decode"): "804242c63deb598f41ca516650af4544b00b90ebe347fca48b2966cb31c7fc49",
    ("mellum2-12b-a2.5b-l4-e16", "grad"): "18afa5bb22b359ee662e23a863c657611c6e3c18f2c1e0ff747f2ba7713bf027",
    ("mellum2-12b-a2.5b-l4-e16", "prefill"): "095c43169b0867856308a7123998771fbd4b1992ccf3088179295a29e8b15e71",
    ("mellum2-12b-a2.5b-l4-e16", "decode"): "d46e5f88f421e234a1a44ba26ecf24ff312c38f79444a89be88b168dd182f90a",
}


def test_an_autoregressive_model_s_inference_is_one_call_a_pack(
        cfg, params, monkeypatch):
    """`TrainEngine.forward` hands the forward program a row a device at a
    time for two streams alone: a model with `block_length == 0` whose
    pack's rows pass `max_tokens_per_mb` (the harness's 8,192 under a row
    of 13,312) is still ONE call over the whole pack, the program and the
    shapes it always had."""
    from areal_tpu.engines import train
    from areal_tpu.interfaces.ppo import _logprob_post

    plain = dataclasses.replace(cfg, block_length=0, mask_token_id=-1)
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    engine = train.TrainEngine(
        plain, params, mesh, ftspec=FinetuneSpec(1, 8, 8))
    packs, calls = [], []
    pack, upload = packing.pack_sample, engine._device_batch

    def counted_pack(*a, **k):
        packs.append(pack(*a, **k))
        return packs[-1]

    monkeypatch.setattr(packing, "pack_sample", counted_pack)
    engine._device_batch = lambda arrays: calls.append(
        arrays["tokens"].shape) or upload(arrays)
    # One group of sequences, as `reference_check` hands them over: the
    # splitter keeps it whole and the packer gives each long one a row.
    sample = SequenceSample(
        keys={"packed_input_ids"}, ids=[0],
        seqlens={"packed_input_ids": [LENS]},
        data={"packed_input_ids": np.concatenate(_seqs(LENS))})
    out = engine.forward(
        sample, MicroBatchSpec(max_tokens_per_mb=16), post_fn=_logprob_post,
        output_key="logprobs", token_key="packed_input_ids")
    assert out.seqlens["logprobs"] == [LENS]
    assert calls == [pk.arrays["tokens"].shape for pk in packs]
    assert any(rows > 1 and row > 16 for rows, row in calls)


@pytest.mark.parametrize("name", lowered_programs.CONFIGS)
def test_an_autoregressive_model_lowers_to_the_text_it_lowered_to(name):
    """With `block_length == 0` the gradient program (flash kernels and the
    fused head in it), prefill and the decode step of a dense and of a
    share configuration lower to the text they lowered to before this PR's
    code paths were reachable: the kernels take no new operand."""
    got = lowered_programs.programs(lowered_programs.toy_config(name))
    assert got == {
        program: sha for (n, program), sha in _AT_THE_PARENT.items()
        if n == name}


# `python3 -m tests.lowered_programs` at cc9a84d, the commit before the
# kernel `kv_decode` came in (PR 70).
_CACHE_KINDS_AT_THE_PARENT = {
    ("nemotron-3-nano-30b-a3b-l9-e16", "prefill"): "a70664c3bb3e6083d7144a1638a41b656fc283abcddf0a7e6aaef88f836227e1",
    ("nemotron-3-nano-30b-a3b-l9-e16", "decode"): "7c89f3038efcc4f947cc6215e208971fa982f72a5b817c8e815b363bdafeb9f9",
    ("glm-4.7-flash-l7-e8", "prefill"): "7f94aa7399082a343845a43c11d1b283af095ee5e172c72e065fdfd71a35e349",
    ("glm-4.7-flash-l7-e8", "decode"): "c524b5c5f2f2a4fdede5e0bfb92b51552c84a3b319bfc7277582a11fdf2d0887",
    ("minicpm-sala-l4-v8", "prefill"): "20274695b4754171f32c6092406a5d50db3f38c424d38ca228457dbe369ac68f",
    ("minicpm-sala-l4-v8", "decode"): "5be3cb517c131f00f2b68dc6c1e4f24255e2d519e433dd06b628b8d0bcdb6088",
}


@pytest.mark.parametrize("name,program", sorted(_CACHE_KINDS_AT_THE_PARENT))
def test_a_cache_that_holds_more_than_kv_lowers_to_the_text_it_lowered_to(
        name, program):
    """One toy configuration a kind of static cache — a recurrent `state`,
    a `latent` row, compressed keys `ck` beside a state; the rings `wk` are
    the share configuration above — keeps its `prefill` and its `decode`
    step at the text they had before the kernel `kv_decode`: it is the
    attention of the plans whose cache is k/v alone and of no other.  (Off
    a TPU the kernel is nobody's choice, so the dense and the share toy
    above keep all three.)"""
    assert name in lowered_programs.CACHE_KINDS.values()
    got = lowered_programs.programs(
        lowered_programs.toy_config(name), (program,))
    assert got[program] == _CACHE_KINDS_AT_THE_PARENT[(name, program)]


# `sdar-rollout64-512` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

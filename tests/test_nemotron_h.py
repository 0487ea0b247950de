"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B) at toy size on the
CPU, seeded random weights, fp32: a pattern of ONE-BRANCH layers
(`MEMEM*EME`: Mamba-2 mixers, ungated relu² experts behind the sigmoid
router, attention without positions), three stacks of leaves of different
lengths, a third population of the cache (fp32 Mamba state + conv tail) —
against the plain reference of `benchmark/references/nemotron_h.py` (the
recurrence token by token), through the train forward over packed rows,
the static prefill + decode through the cache, and the gradients; packed
segments against the segments run apart; the shares of all ranks against
the uncut layer; six faults and the lower-precision controls outside the
fp32 bound; the HF reader both ways; the sharding rules; the named
refusals; the counters; and that every other family lowers to the program
it lowered to.  Logits and log-probabilities are compared, never sampled
tokens.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import mamba
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import FROZEN_LEAVES, ModelConfig, tiny_config
from areal_tpu.models.hf import registry
from areal_tpu.parallel import sharding
from benchmark import files, peaks_ssm
from benchmark import run as bench_run
from benchmark.references import nemotron_h as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "nemotron-3-nano-30b-a3b-l9-e16.json"
CELL = "nemo3n-rollout64-512"


def _toy_hf(held=4):
    """The benchmark configuration's keys at its `toy` sizes; `held`
    experts of the router's 8 (8: the whole layer, no share)."""
    config, _ = bench_run.toy(
        files.load_json("configs", CONFIG),
        files.load_json("traffic", "rollout64-512.json"))
    config["n_routed_experts"] = held
    if held == 8:
        del config["share"]
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = registry.HF_FAMILIES["nemotron_h"].config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales, conv bias and D, so
    that a bias or a skip left out, or a norm over the wrong channels,
    cannot pass."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = ("ln1", "ssm_norm", "ssm_conv_b", "ssm_D", "ssm_dt_bias")
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


def _logprobs(logits, seq):
    lp = jax.nn.log_softmax(jnp.asarray(logits)[:-1], axis=-1)
    return np.asarray(
        jnp.take_along_axis(lp, jnp.asarray(seq)[1:, None], axis=1))[:, 0]


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    import json

    config = files.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    bench = config["benchmark"]
    assert bench["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(bench["reduced"]) == reduced
    for key, value in row["config"].items():
        if key in reduced or key == "hybrid_override_pattern":
            continue
        assert config[key] == value, key
    # The cut: the pattern's first nine layers, 16 of 128 experts, 1/8 of
    # the vocabulary; the deployment beside them.
    assert config["hybrid_override_pattern"] == "MEMEM*EME" == (
        row["config"]["hybrid_override_pattern"][:9])
    assert config["num_hidden_layers"] == 9
    share = config["share"]
    assert share["router_num_experts"] == 128 == (
        share["chips_per_layer"] * config["n_routed_experts"])
    assert config["vocab_size"] * 8 == share["published_vocab_size"] == 131072
    assert bench["weights_seed"] == 40
    for group in ("assumed", "stands_for", "deployment", "tolerance", "toy"):
        assert bench[group], group
    assert {"attention_positions", "state_precision", "ssm_init"} <= set(
        bench["assumed"])
    # The builder's own count, from the leaves the program allocates.
    cfg = bench_run.model_config(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 986_254_848
    assert cfg.ssm_inner_dim == 4096 and cfg.ssm_conv_dim == 6144
    assert cfg.ssm_in_dim == 10304
    assert (cfg.n_ssm_layers, cfg.n_moe_layers, cfg.n_attn_layers) == (4, 4, 1)
    assert cfg.pattern_unit == "MEMEM*EME" and cfg.n_periods == 1


def test_config_both_ways_and_a_published_config_is_the_whole_model(cfg):
    family = registry.HF_FAMILIES["nemotron_h"]
    assert family.config_from_hf(family.config_to_hf(cfg)) == dataclasses.replace(
        cfg, param_dtype="bfloat16", router_bias_init_std=0.0)
    assert registry.infer_model_type(cfg) == "nemotron_h"
    whole = _cfg(held=8)
    assert not whole.expert_share and "share" not in family.config_to_hf(whole)
    assert cfg.expert_share and cfg.router_width == 8
    assert (cfg.hidden_act, cfg.pos_emb, cfg.mlp_gated) == ("relu2", "none", False)
    # A pattern that repeats is scanned by its unit.
    twice = dataclasses.replace(cfg, n_layers=6, layer_pattern="ME*ME*")
    assert twice.pattern_unit == "ME*" and twice.n_periods == 2


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("use_conv_bias", False), ("mlp_hidden_act", "silu"),
    ("attention_bias", True), ("n_shared_experts", 2)])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        registry.HF_FAMILIES["nemotron_h"].config_from_hf(
            dict(_toy_hf(), **{key: value}))


def test_the_dense_layer_kind_and_a_wrong_pattern_are_refused_by_name(cfg):
    with pytest.raises(NotImplementedError, match="dense MLP layer kind"):
        dataclasses.replace(cfg, layer_pattern="MEMEM*EM-")
    with pytest.raises(ValueError, match="is not 9 characters"):
        dataclasses.replace(cfg, layer_pattern="MEMEM*EM")


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    family = registry.HF_FAMILIES["nemotron_h"]
    sd = family.params_to_sd(cfg, params)
    d, di = cfg.hidden_dim, cfg.ssm_inner_dim
    # Layers 0, 2, 4, 7 are Mamba; 1, 3, 6, 8 experts; 5 attention.
    assert sd["backbone.layers.0.mixer.in_proj.weight"].shape == (
        cfg.ssm_in_dim, d)
    assert sd["backbone.layers.2.mixer.conv1d.weight"].shape == (
        cfg.ssm_conv_dim, 1, cfg.ssm_conv_kernel)
    assert sd["backbone.layers.4.mixer.conv1d.bias"].shape == (cfg.ssm_conv_dim,)
    for name in ("A_log", "D", "dt_bias"):
        assert sd[f"backbone.layers.7.mixer.{name}"].shape == (cfg.ssm_n_heads,)
    assert sd["backbone.layers.7.mixer.norm.weight"].shape == (di,)
    assert sd["backbone.layers.7.mixer.out_proj.weight"].shape == (d, di)
    assert sd["backbone.layers.5.mixer.q_proj.weight"].shape == (cfg.q_dim, d)
    assert sd["backbone.layers.5.mixer.k_proj.weight"].shape == (cfg.kv_dim, d)
    assert sd["backbone.layers.1.mixer.gate.weight"].shape == (8, d)
    assert sd["backbone.layers.1.mixer.gate.e_score_correction_bias"].shape == (8,)
    assert sd["backbone.layers.3.mixer.experts.2.up_proj.weight"].shape == (
        cfg.moe_intermediate_dim, d)
    assert sd["backbone.layers.8.mixer.experts.3.down_proj.weight"].shape == (
        d, cfg.moe_intermediate_dim)
    assert "backbone.layers.8.mixer.experts.4.up_proj.weight" not in sd
    assert sd["backbone.layers.6.mixer.shared_experts.up_proj.weight"].shape == (
        cfg.shared_expert_dim, d)
    for i in range(9):
        assert sd[f"backbone.layers.{i}.norm.weight"].shape == (d,)
    assert {"backbone.embeddings.weight", "backbone.norm_f.weight",
            "lm_head.weight"} <= set(sd)
    # The conv's taps: [C, 1, K], the newest input last.
    np.testing.assert_array_equal(
        sd["backbone.layers.2.mixer.conv1d.weight"][:, 0, -1],
        np.asarray(params["blocks"]["ssm_conv"][1, -1]))
    back = family.params_from_sd(cfg, sd, dtype=jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(back)[0],
            jax.tree.leaves(params)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))
    # A rank that holds experts 4-7 reads and writes THEIR names.
    other = dataclasses.replace(cfg, expert_offset=4)
    assert "backbone.layers.1.mixer.experts.7.up_proj.weight" in (
        family.params_to_sd(other, params))


# ---------------------------------------------------- against the reference


@pytest.mark.parametrize("held", [4, 8])
def test_train_forward_over_packed_rows_matches_the_reference(held):
    """One packed row of three segments (70 + 50 + 30 tokens: segment and
    chunk boundaries nowhere aligned at a chunk of 8) and six pads against
    the reference's forward of each sequence alone."""
    cfg = _cfg(held)
    params = _params(cfg)
    seqs = _sequences(cfg)
    n = sum(len(s) for s in seqs)
    tokens = np.zeros((1, n + 6), np.int32)
    segs = np.zeros((1, n + 6), np.int32)
    off = 0
    for i, s in enumerate(seqs):
        tokens[0, off: off + len(s)] = s
        segs[0, off: off + len(s)] = i + 1
        off += len(s)
    got = tfm.forward(
        params, cfg, jnp.asarray(tokens), jnp.asarray(segs), use_flash=False)
    off = 0
    for s in seqs:
        np.testing.assert_allclose(
            got[0, off: off + len(s)], reference.logits(params, cfg, s), **TOL)
        off += len(s)


@pytest.mark.parametrize("chunk", [8, 16, 128])
def test_a_packed_row_of_three_segments_equals_the_three_run_apart(
        cfg, params, chunk):
    """State, decay and conv restart at a segment start: chunk boundaries
    inside a segment (chunk 8, 16) and a whole row inside one chunk (128),
    and the state a row ends on is its LAST segment's."""
    c = dataclasses.replace(cfg, ssm_chunk=chunk)
    blk = {n: w[1] for n, w in params["blocks"].items() if n in mamba.SSM_LEAVES}
    lens = (21, 16, 13)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(1, sum(lens), c.hidden_dim)), jnp.float32)
    segs = jnp.asarray(np.repeat([1, 2, 3], lens)[None].astype(np.int32))
    # One program a shape (five of them), not one a primitive.
    forward = jax.jit(
        lambda h, segs: mamba.ssm_forward(h, blk, c, segs, with_state=True))
    got, state, tail = forward(h, segs)
    off = 0
    for n in lens:
        one = jnp.ones((1, n), jnp.int32)
        want, s1, t1 = forward(h[:, off: off + n], one)
        np.testing.assert_allclose(got[:, off: off + n], want, **TOL)
        off += n
    np.testing.assert_allclose(state, s1, **TOL)
    np.testing.assert_allclose(tail, t1, **TOL)
    # Trailing pads are neutral: the state is the last VALID token's.
    padded, s2, t2 = forward(
        jnp.pad(h, ((0, 0), (0, 5), (0, 0))), jnp.pad(segs, ((0, 0), (0, 5))))
    np.testing.assert_allclose(padded[:, : sum(lens)], got, **TOL)
    np.testing.assert_allclose(s2, state, **TOL)
    np.testing.assert_allclose(t2, tail, **TOL)
    # Stepping the last segment token by token ends in the same state.
    s = jnp.zeros_like(state)[None]
    t = jnp.zeros_like(tail)[None]
    for i in range(sum(lens) - lens[-1], sum(lens)):
        y, s, t = mamba.ssm_step(h[:, i: i + 1], blk, c, s, t, 0)
    np.testing.assert_allclose(y[:, 0], got[:, -1], **TOL)
    np.testing.assert_allclose(s[0], state, **TOL)
    np.testing.assert_allclose(t[0], tail, **TOL)


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        cfg, params):
    """Right-aligned prompts of unequal length through `prefill`, then six
    `decode_step`s through the three populations of the cache, against
    the reference's full forward pass of each row."""
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
    # K/V for the ONE attention layer, state and conv for the four Mamba
    # layers, nothing for the expert layers.
    assert cache.k.shape == (1, 3, 64, cfg.n_kv_heads, cfg.head_dim)
    assert cache.state.shape == (4, 3, 4, 16, 16)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (4, 3, 3, cfg.ssm_conv_dim)
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
        assert counts["moe"].shape == (cfg.n_moe_layers, cfg.n_experts)
        for i, p in enumerate(plens):
            np.testing.assert_allclose(logits[i], want[i][p + t], **TOL)
    # What the cache ends on is what the reference's recurrence ends on.
    _, state, tail = reference._next_token_logprobs(
        params, cfg, rows[1], None, len(rows[1]))
    np.testing.assert_allclose(cache.state[:, 1], state, **TOL)
    np.testing.assert_allclose(cache.conv[:, 1], tail, **TOL)


def test_a_left_aligned_prompt_leaves_the_state_of_its_last_valid_token(
        cfg, params):
    seq = _sequences(cfg, lens=(29,), seed=8)[0]
    right = np.zeros((1, 40), np.int32)
    right[0, 11:] = seq
    left = np.zeros((1, 40), np.int32)
    left[0, :29] = seq
    out = []
    for tokens, seg in ((right, right != 0), (left, np.arange(40)[None] < 29)):
        seg = seg.astype(np.int32)
        seg[0, 11 if tokens is right else 0] = 1  # token id 0 is a token too
        out.append(tfm.prefill(
            params, cfg, jnp.asarray(tokens), jnp.asarray(seg),
            tfm.init_kv_cache(cfg, 1, 64), use_flash=False))
    (lg_r, c_r), (lg_l, c_l) = out
    np.testing.assert_allclose(lg_l, lg_r, **TOL)
    np.testing.assert_allclose(c_l.state, c_r.state, **TOL)
    np.testing.assert_allclose(c_l.conv, c_r.conv, **TOL)


def test_gradients_match_the_reference(cfg, params):
    """d(sum of next-token log-probs)/d(params) through the chunked scan
    under `jax.checkpoint` against autodiff of the plain reference; the
    router's choice bias takes none."""
    seq = _sequences(cfg, lens=(45,), seed=2)[0]
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
        if "router_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)


# --------------------------------------------- faults and a precision lower


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_lies_outside_the_fp32_bound(cfg, params, fault):
    """One part of the mathematics wrong — the D x skip left out, the gated
    norm over all channels instead of groups, the conv bias left out, the
    2.5 scale left out, the choice bias added into the weights, relu²
    replaced by a SiLU gate — moves log-probabilities past the bound the
    CPU rehearsal holds the generator to; the system sits inside it."""
    seq = _sequences(cfg, lens=(64,), seed=7)[0]
    want = _logprobs(reference.logits(params, cfg, seq), seq)
    got = _logprobs(tfm.forward(
        params, cfg, jnp.asarray(seq)[None],
        jnp.ones((1, len(seq)), jnp.int32))[0], seq)
    bad = _logprobs(reference.logits(params, cfg, seq, fault=fault), seq)
    tol = reference.TOLERANCE_FP32
    assert np.abs(got - want).mean() < tol["mean_abs"]
    assert np.abs(got - want).max() < tol["max_abs"]
    assert np.abs(bad - want).mean() > 10 * tol["mean_abs"], fault
    assert np.abs(bad - want).max() > 10 * tol["max_abs"], fault


def _reference_state(params, cfg, seq, lower=None):
    lp, state, tail = reference._next_token_logprobs(
        params, cfg, seq, lower, len(seq))
    return lp[: len(seq) - 1], state, tail


@pytest.mark.parametrize("lower,kept", [
    ("bfloat16", False), ("bfloat16:state", False),
    ("bfloat16:gates", True), ("bfloat16:router", True)])
def test_the_state_limit_refuses_the_reference_a_precision_lower(
        cfg, params, lower, kept):
    """The control of the configuration's `tolerance.state`: the reference
    with S (alone, or with dt, the decay and the router's logits) rounded
    to bfloat16 at every step ends on a state that bfloat16 holds exactly —
    residual 0 — and is refused under the chip's limits and the CPU's.
    dt / decay or the router alone leave S in float32 and pass this limit:
    the CPU's fp32 log-prob bound refuses every one of them."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    want, state, tail = _reference_state(params, cfg, seq)
    low, low_state, low_tail = _reference_state(params, cfg, seq, lower)
    readings = reference.state_readings(low_state, low_tail, state, tail)
    assert (readings["state_bf16_residual_min"] > 8e-4) == kept
    for tol in (reference.STATE_TOLERANCE, reference.STATE_TOLERANCE_FP32):
        refused = [p for p in reference.state_problems(readings, tol)
                   if "no more than bfloat16" in p]
        assert bool(refused) == (not kept), (readings, tol)
    fp32 = reference.TOLERANCE_FP32
    assert (np.abs(low - want).mean() > fp32["mean_abs"]
            or np.abs(low - want).max() > fp32["max_abs"])


def test_the_state_the_generators_own_program_leaves_is_the_references(
        cfg, params):
    """`check_generator`: a `GeneratorEngine` over the same weights, its
    static program at 64 slots, and for the first and the last slot the
    Mamba state and conv tail it left against the reference's over the
    tokens it sampled — what the CPU rehearsal runs."""
    seq = _sequences(cfg, lens=(40,), seed=7)[0]
    readings, problems = reference.check_generator(params, cfg, seq)
    assert problems == [], readings
    assert readings["n_tokens"] == 2 * 32
    assert 8e-4 < readings["state_bf16_residual_min"] < 2e-3
    assert readings["state_rel_err_max"] < 1e-4
    assert readings["logprob_max_abs"] < reference.TOLERANCE_FP32["max_abs"]


def test_a_decode_step_that_keeps_its_state_in_bf16_is_not_correct(
        cfg, params, monkeypatch):
    """What a later change might do for the bytes — S rounded to bfloat16
    as the decode step writes it — turns `next_token_logprobs` to NaN,
    which `checks.reference_check` reports as not `correct`."""
    seq = _sequences(cfg, lens=(40,), seed=7)[0]
    from areal_tpu.models import mamba

    inner = mamba.ssm_step

    def rounded(h, blk, c, states, tails, li):
        y, states, tails = inner(h, blk, c, states, tails, li)
        return y, jax.lax.reduce_precision(states, 8, 7), tails

    monkeypatch.setattr(mamba, "ssm_step", rounded)
    jax.clear_caches()
    try:
        got = reference.next_token_logprobs(params, cfg, seq)
        assert np.isnan(got).all() and got.shape == (len(seq) - 1,)
        # A control computation checks nothing of the system's.
        assert np.isfinite(reference.next_token_logprobs(
            params, cfg, seq, lower="bfloat16")).all()
    finally:
        monkeypatch.undo()
        jax.clear_caches()


# ------------------------------------------------- one rank's share of a layer


@pytest.mark.parametrize("dispatch", ["grouped", "dense", "topk"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(dispatch):
    """The guide's shares test: with the router's 8 experts over 4 ranks of
    2, the four partial expert-layer outputs — each with the shared expert,
    which every rank computes alike, so counted once — sum to what the
    plain reference gives for the whole layer."""
    whole = _cfg(held=8, moe_dispatch=dispatch, moe_capacity_factor=8.0)
    params = _params(whole)
    blk = {k: v[2] for k, v in params["blocks"].items()
           if k in tfm._MOE_LEAVES}
    assert set(blk) == {"router", "router_bias", "wu", "wd", "ws_u", "ws_d"}
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(2, 24, whole.hidden_dim)), jnp.float32)
    x = h.reshape(-1, whole.hidden_dim)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(x, blk, whole)
        shared = jnp.square(jax.nn.relu(x @ blk["ws_u"])) @ blk["ws_d"]
    total, local_rows = 0.0, 0
    for rank in range(4):
        part = dataclasses.replace(
            whole, n_experts=2, n_router_experts=8, expert_offset=2 * rank)
        mine = dict(blk, **{n: blk[n][2 * rank: 2 * rank + 2]
                            for n in ("wu", "wd")})
        out, aux, counts = tfm._mlp_moe(h, mine, part)
        total = total + out.reshape(x.shape)
        local_rows += int(counts.sum())
        assert counts.shape == (2,) and float(aux) == 0.0
    assert local_rows == x.shape[0] * whole.n_experts_per_tok
    np.testing.assert_allclose(total - 3 * shared, want, **TOL)
    # ... and the whole layer in one piece is the same layer.
    np.testing.assert_allclose(
        tfm._mlp_moe(h, blk, whole)[0].reshape(x.shape), want, **TOL)


def test_the_decode_program_reads_the_expert_leaves_in_place(cfg, params):
    """Two expert leaves, stacked over the FOUR expert layers: the decode
    step hands the ragged kernels the stacked leaves and picks the layer
    by group sizes, and says the same as the scan's slices."""
    assert tfm._expert_leaves(cfg) == ("wu", "wd")
    assert tfm.expert_leaves_in_place(cfg, params["blocks"])
    cache = tfm.init_kv_cache(cfg, 2, 16)
    args = (jnp.asarray([3, 5]), jnp.asarray([0, 0]), cache, 0,
            jnp.asarray([0, 0]))
    a, _ = tfm.decode_step(params, cfg, *args, experts_in_place=True)
    b, _ = tfm.decode_step(params, cfg, *args, experts_in_place=False)
    np.testing.assert_allclose(a, b, **TOL)


@pytest.mark.parametrize("r,k,n,n_layers,e,t,layer", [
    (48, 256, 192, 3, 4, 8, 1),  # K whole, N whole (1.5 lanes)
    (96, 384, 320, 2, 8, 16, 0),  # 384 = 3 lanes: one tile
    (30, 1024, 96, 1, 4, 5, 0),  # rows no multiple of 16; two K tiles
    (64, 128, 1024, 2, 4, 16, 1),  # two N tiles
])
def test_the_grouped_decode_kernel_is_a_ragged_dot_over_the_stacked_leaf(
        r, k, n, n_layers, e, t, layer):
    """`grouped_decode_matmul` (interpreted here) against `ragged_dot` and
    a row-by-row product: rows sorted by expert with HALF the choices held
    elsewhere (they sort last and come out zero), experts without rows, a
    layer picked out of the stacked [L x E, K, N] leaf."""
    from areal_tpu.ops.pallas.grouped_matmul import grouped_decode_matmul

    rng = np.random.default_rng(r)
    choices = np.stack(
        [rng.choice(2 * e, r // t, replace=False) for _ in range(t)])
    flat = np.where(choices < e, choices, e).reshape(-1)
    flat[flat == 1] = e  # expert 1 gets no row at all
    order = np.argsort(flat, kind="stable")
    sizes = np.bincount(flat, minlength=e + 1)[:e].astype(np.int32)
    assert sizes[1] == 0 and 0 < sizes.sum() < r and sizes.max() <= t
    xs = jnp.asarray(rng.standard_normal((r, k)), jnp.float32)
    w = jnp.asarray(
        rng.standard_normal((n_layers * e, k, n)) * k**-0.5, jnp.float32)
    got = grouped_decode_matmul(
        xs, w, jnp.asarray(sizes), jnp.int32(layer), max_rows=t)
    want = np.zeros((r, n), np.float32)
    for i, ex in enumerate(flat[order]):
        if ex < e:
            want[i] = np.asarray(xs[i]) @ np.asarray(w[layer * e + ex])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    live = int(sizes.sum())
    assert not np.asarray(got[live:]).any()  # rows past every group
    ragged = jax.lax.ragged_dot(
        xs, w[layer * e: (layer + 1) * e], jnp.asarray(sizes))
    np.testing.assert_allclose(got[:live], ragged[:live], rtol=1e-4, atol=1e-4)


def test_the_decode_step_with_the_kernel_equals_the_ragged_form(cfg, params):
    """`decode_step(expert_kernel=True)`: the four expert layers' in-place
    matmuls through the Pallas kernel (interpreted), to the last bit of
    the ragged form's logits at fp32; off a TPU the default is the ragged
    form, and only where XLA's kernel tiles the expert's matrix badly is
    the kernel the TPU's (`ragged_tiles_badly`)."""
    from areal_tpu.ops.pallas import grouped_matmul as gm

    cache = tfm.init_kv_cache(cfg, 5, 16)
    args = (jnp.asarray([3, 5, 7, 11, 13]), jnp.zeros((5,), jnp.int32), cache,
            0, jnp.zeros((5,), jnp.int32))
    a, _, counts = tfm.decode_step(
        params, cfg, *args, with_counts=True, expert_kernel=True)
    b, _ = tfm.decode_step(params, cfg, *args, expert_kernel=False)
    c, _ = tfm.decode_step(params, cfg, *args)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
    assert (np.asarray(counts["moe"]) == 0).any()  # an expert without rows
    assert gm.ragged_tiles_badly(2688, 1856) and gm.ragged_tiles_badly(2048, 1856)
    assert not gm.ragged_tiles_badly(2048, 1536)  # glm, olmoe, qwen3_next:
    assert not gm.ragged_tiles_badly(2048, 1024)  # XLA's kernel stays
    assert not gm.ragged_tiles_badly(2048, 512)
    assert gm.tiles(2688, 1856, 384, 2) == (384, 1856)  # as PR 40 left them
    assert gm.tiles(1856, 2688, 384, 2) == (1856, 384)


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
def test_layouts_the_pattern_cannot_run_are_refused_by_name(cfg, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.HybridLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)
    sharding.attn_dispatch(mesh, tiny_config())  # every other model: fine


def test_the_serving_plane_refuses_mamba_state_by_name(cfg, params):
    with pytest.raises(tfm.HybridLayoutError, match="serving plane"):
        tfm.init_paged_kv_cache(cfg, 4, 16)
    with pytest.raises(tfm.HybridLayoutError, match="Mamba-2"):
        tfm.decode_step_ragged_paged(
            params, cfg, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            None, jnp.zeros((1, 1), jnp.int32), jnp.zeros((2,), jnp.int32))


def test_generate_refuses_the_serving_plane_and_reports_both_caches(cfg, params):
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
    assert pool["moe_decode_steps"] == 5
    # The router's choices are counted over the FOUR expert layers.
    assert pool["moe_rows_routed"] == 5 * 4 * cfg.n_experts_per_tok * 4
    assert 0 < pool["moe_rows_local"] < pool["moe_rows_routed"]
    assert 1 <= pool["moe_experts_touched"] <= cfg.n_experts
    assert pool["moe_expert_leaves_in_place"] == 1
    # [4 Mamba layers, 4 rows]: fp32 state + conv tail; k/v of ONE layer.
    s_total = 256  # bucket_len(128 + 5)
    assert pool["state_cache_bytes"] == 4 * 4 * (
        4 * 16 * 16 * 4 + 3 * cfg.ssm_conv_dim * 4)
    assert pool["kv_cache_bytes"] == 2 * 4 * s_total * cfg.kv_dim * 4
    for kwargs in (
        dict(inflight=True),  # forced
        dict(g=dataclasses.replace(g, n=3)),  # 6 requests > 4 slots
        dict(g=dataclasses.replace(g, stop=((5, 6),))),
        dict(g=dataclasses.replace(g, spec_decode_k=2)),
        dict(g=dataclasses.replace(g, max_new_tokens=4096)),
    ):
        gg = kwargs.pop("g", g)
        with pytest.raises(tfm.HybridLayoutError, match="serving plane"):
            engine.generate(sample, MicroBatchSpec(), gg, **kwargs)


def test_the_train_step_counts_chunks_and_restarts_and_keeps_no_moment(cfg):
    """`ssm/chunks` and `ssm/segment_restarts` of a micro-batch, summed
    over the four Mamba layers; the router's choice bias is frozen (no
    Adam moment, handed back as it was) while A_log, D, dt_bias, the conv
    and the gated norm's weight are trained."""
    from areal_tpu.api.model_api import FinetuneSpec
    from areal_tpu.engines.train import TrainEngine, _trainable_mask
    from areal_tpu.ops import functional as F

    params = _params(cfg)
    mask = _trainable_mask(params)
    frozen = [n for n, t in mask["blocks"].items() if not t]
    # (the other frozen leaves are a token indexer's: none here, PR 64)
    assert frozen == ["router_bias"] == list(FROZEN_LEAVES[:1])
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    engine = TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 8, 8))
    seg = np.zeros((2, 40), np.int32)
    seg[0, :12], seg[0, 12:30], seg[1, :25] = 1, 2, 1
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, seg.shape), jnp.int32),
        "segment_ids": jnp.asarray(seg),
        "positions": tfm.positions_from_segments(jnp.asarray(seg)),
        "prompt_mask": jnp.zeros(seg.shape, bool),
    }
    grads, loss, stats = engine._get_grad_fn(F.sft_loss)[0](
        engine.params, batch, jnp.float32(1.0))
    assert float(stats["ssm/chunks"]) == 4 * 2 * 5  # 40 tokens / chunk 8
    assert float(stats["ssm/segment_restarts"]) == 4 * 3
    assert np.isfinite(float(loss))
    for name in ("ssm_A_log", "ssm_D", "ssm_dt_bias", "ssm_conv",
                 "ssm_conv_b", "ssm_norm"):
        assert np.asarray(grads["blocks"][name]).any(), name
    assert not np.asarray(grads["blocks"]["router_bias"]).any()


def test_flops_and_bytes_follow_the_layer_kinds(cfg):
    from areal_tpu.base import monitor

    # The program's own count = the benchmark's, but for the recurrence
    # (2 multiply-adds a state element there, 5 FLOPs here).
    rec = cfg.n_ssm_layers * cfg.ssm_inner_dim * cfg.ssm_state_dim
    assert monitor.matmul_params(cfg) - 2 * rec == pytest.approx(
        peaks_ssm.matmul_params(cfg))
    assert sum(n for n, b in monitor._layers_of(cfg) if b.attn_flops) == 1
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    # ISSUE 40's arithmetic: 38.74 M a Mamba layer, 9.98 M an expert,
    # 23.40 M the attention layer; a decode step's bytes by part.
    assert peaks_ssm.ssm_params(big) + 5 * 6144 + 4096 + 3 * 64 == pytest.approx(
        38.74e6, rel=1e-3)
    assert 2 * big.hidden_dim * big.moe_intermediate_dim == pytest.approx(
        9.98e6, rel=1e-3)
    assert peaks_ssm.attn_params(big) == pytest.approx(23.40e6, rel=1e-3)
    assert peaks_ssm.experts_per_token_held(big) == 0.75
    assert peaks_ssm.experts_expected(big, 64) == pytest.approx(15.3, abs=0.05)
    assert peaks_ssm.ssm_decode_bytes(big, 64) == pytest.approx(
        1.07e9 + 0.31e9 + 0.02e9, rel=0.01)
    assert peaks_ssm.decode_bytes(big, [400] * 64) == pytest.approx(
        2.93e9, rel=0.02)
    assert peaks_ssm.flops_forward(big, [640]) / 640 == pytest.approx(
        0.67e9, rel=0.03)


# ------------------------------------------ every other family's program stays


def _glm_toy():
    from tests import test_glm4_moe_lite as glm

    return glm._cfg()


# sha256 of the StableHLO text of GLM's toy programs (the results' names left
# out: `_program_sha`) as printed at the parent of PR 57 (c41906c), the texts
# of this test's first parent (2e27f7a): the ungated expert paths and the pattern's branches leave a
# gated, two-branch family's programs as they were.  The dense, OLMoE and
# hybrid families' are pinned in tests/test_glm4_moe_lite.py.
_PARENT_PROGRAMS = {
    "grad": "be18f979f0a58387dbecbd6ecf677fed8687791e2588c6ed65706f2e20025e31",
    "gen": "c6139758834cebd19e5b2fcebd498d22422e458fef59f9efb61d594ee314ddd4",
}


@pytest.mark.parametrize("program", sorted(_PARENT_PROGRAMS))
def test_the_latent_family_lowers_to_the_parents_program(program):
    from tests.test_glm4_moe_lite import _program_sha

    assert _program_sha(_glm_toy(), program) == _PARENT_PROGRAMS[program]


# ------------------------- the gradient program's scan on the Pallas sweep

# The Mamba widths the sweep can cut (heads of 64 channels, a state of 128
# columns, chunks of 128), the rest of the toy as it is.
_WIDE = dict(ssm_n_heads=8, ssm_head_dim=64, ssm_state_dim=128,
             ssm_n_groups=2, ssm_chunk=128)
# `_program_sha(_cfg(**_WIDE), "gen")` as printed at the parent of PR 58
# (6ead7dd): prefill + a decode step at those widths.
_PARENT_WIDE_GEN = (
    "af3125c9f1f3f93f903e813656c7ca8925521f7781a7e4519c5fb4341538dfff")
# ... and of the mixer alone under `with_state` on bf16 leaves, as the cell
# holds them (a cast that moves in the trace shows only there).
_PARENT_WIDE_MIXER_PREFILL = (
    "7e23b15e84ef444950191640fb926a360d5e828b811a1ecb05d0d5032e9961ed")


def test_the_train_step_on_the_forced_sweep_is_the_jnp_forms(monkeypatch):
    """The toy model's loss and the gradient of every leaf with the chunked
    scan on `ssd_chunk` against `ssd_chunked`, inside this file's fp32
    bounds (the case's body: `tests/test_ssd_chunk_kernel.py`); the counter
    says which form ran."""
    from tests.test_ssd_chunk_kernel import (
        train_step_on_the_sweep_is_the_jnp_forms,
    )

    cfg = _cfg(**_WIDE)
    seg = train_step_on_the_sweep_is_the_jnp_forms(
        cfg, _params(cfg), monkeypatch)
    stats = mamba.BRANCH.train_stats(cfg, 4, seg, True)
    assert float(stats["ssm/chunks_on_kernel"]) == float(
        stats["ssm/chunks"]) == 4 * 3
    assert float(mamba.BRANCH.train_stats(cfg, 4, seg, None)[
        "ssm/chunks_on_kernel"]) == 0  # a CPU backend


def test_prefill_keeps_the_parents_program_at_the_sweeps_widths(monkeypatch):
    """Prefill reads the final state (`with_state`): at widths the sweep
    takes in the gradient program, prefill + a decode step lower to the
    text they lowered to at the parent of PR 58, and the mixer under
    `with_state` lowers to that ONE text whatever backend JAX reports."""
    import hashlib

    from tests.test_glm4_moe_lite import _program_sha

    cfg = _cfg(**_WIDE)
    assert _program_sha(cfg, "gen") == _PARENT_WIDE_GEN
    blk = jax.eval_shape(lambda: {
        k: v[0].astype(jnp.bfloat16) for k, v in tfm.init_params(
            cfg, jax.random.PRNGKey(0))["blocks"].items()
        if k in mamba.SSM_LEAVES})
    h = jax.ShapeDtypeStruct((2, 256, cfg.hidden_dim), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((2, 256), jnp.int32)

    def text():
        return jax.jit(lambda h, blk, seg: mamba.ssm_forward(
            h, blk, cfg, seg, with_state=True)).lower(h, blk, seg).as_text()

    here = text()
    assert hashlib.sha256(
        here.encode()).hexdigest() == _PARENT_WIDE_MIXER_PREFILL
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mamba.ssd_kernel_form(cfg) and text() == here


# --------------------------------- the cell, rehearsed on the CPU at toy size

# `nemo3n-rollout64-512` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

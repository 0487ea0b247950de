"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B) at toy size on the CPU, seeded
random weights, fp32: Gated DeltaNet with beta in (0, 2) at heads of d_v =
2 d_k, three to one with position-free full attention whose q and k are
normed over the whole projection, a dense MLP behind each, every branch's
norm on its OUTPUT — against the plain reference of
`benchmark/references/olmo_hybrid.py` (the delta rule token by token),
through the train forward over packed rows, the static prefill + decode
through the hybrid cache, the state that leaves and the gradients; the
eight faults and the lower precision the tolerance has to refuse; the HF
reader both ways.  Logits and log-probabilities are compared, never
sampled tokens.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import linear_attention as la
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.models.hf import registry
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import olmo_hybrid as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "olmo-hybrid-7b-l4-v8.json"
FAMILY = registry.HF_FAMILIES["olmo_hybrid"]


def _toy_hf():
    """The benchmark configuration's keys at its `toy` sizes."""
    config, _ = bench_run.toy(
        files.load_json("configs", CONFIG),
        files.load_json("traffic", "rollout64-512.json"))
    return config


def _cfg(**changes) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(_toy_hf()), param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    """Random weights with NON-trivial norm scales and gates, so that a
    norm left out, or a gate, cannot pass."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(5))
    names = ("ln1", "ln2", "q_norm", "k_norm", "la_norm", "la_dt_bias")
    keys = jax.random.split(jax.random.PRNGKey(6), len(names) + 1)
    for k, name in zip(keys, names):
        leaf = p["blocks"][name]
        p["blocks"][name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
    p["final_ln"] = p["final_ln"] + 0.3 * jax.random.normal(
        keys[-1], p["final_ln"].shape)
    return p


def _sequences(cfg, lens=(70, 50, 30), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _system_logprobs(params, cfg, seq):
    logits = tfm.forward(
        params, cfg, jnp.asarray(seq)[None], jnp.ones((1, len(seq)), jnp.int32))
    lp = jax.nn.log_softmax(logits[0, :-1], axis=-1)
    return np.asarray(jnp.take_along_axis(lp, jnp.asarray(seq)[1:, None], 1))[:, 0]


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    assert {k: config[k] for k in published} == published
    cut = {"num_hidden_layers": 4, "vocab_size": 12544,
           "layer_types": ["linear_attention"] * 3 + ["full_attention"]}
    assert {k: config[k] for k in cut} == cut
    bench = config["benchmark"]
    assert sorted(bench["reduced"]) == sorted(cut)
    assert config["vocab_size"] * 8 == bench["published"]["vocab_size"]
    # The three conventions are named as assumptions, with their reasons.
    for name in ("norm_on_branch_output", "qk_norm_over_whole_projection",
                 "no_positions_in_full_attention"):
        assert bench["assumed"][name].startswith("ASSUMPTION"), name
    cfg = bench_run.model_config(config)
    assert (cfg.branch_norm, cfg.pos_emb, cfg.linear_neg_eigval) == (
        "output", "none", True)
    assert cfg.qk_norm and not cfg.qk_norm_per_head and not cfg.attn_gate
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.full_attn_interval) == (128, 30, 4)
    assert cfg.linear_key_dim == 2880 and cfg.linear_value_dim == 5760
    assert not cfg.is_moe and not cfg.tied_embeddings and not cfg.qkv_bias
    # The arithmetic of `reduced`, from the shapes `init_params` allocates.
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == bench["leaf_count"]["parameters"] == 928_862_196


def test_config_both_ways(cfg):
    again = FAMILY.config_from_hf(FAMILY.config_to_hf(cfg))
    assert dataclasses.replace(again, param_dtype="float32") == cfg
    assert registry.infer_model_type(cfg) == "olmo_hybrid"
    # The toy keeps d_v = 2 d_k: unequal widths in every CPU test.
    assert (cfg.linear_k_head_dim, cfg.linear_v_head_dim) == (12, 24)
    # A theta that is a number is a rotary table.
    roped = FAMILY.config_from_hf(
        dict(_toy_hf(), rope_parameters={"rope_theta": 500000.0}))
    assert (roped.pos_emb, roped.rope_theta) == ("rope", 500000.0)


@pytest.mark.parametrize("key,value,error", [
    ("attention_bias", True, NotImplementedError),
    ("clip_qkv", 8.0, NotImplementedError),
    ("layer_types", ["full_attention"] + ["linear_attention"] * 3,
     NotImplementedError),
    ("layer_types", ["linear_attention"] * 3 + ["sliding_attention"],
     ValueError),
])
def test_what_is_not_modelled_raises(key, value, error):
    with pytest.raises(error, match=key):
        FAMILY.config_from_hf(dict(_toy_hf(), **{key: value}))


def test_state_dict_round_trip_by_the_assumed_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    d, kd, vd = cfg.hidden_dim, cfg.linear_key_dim, cfg.linear_value_dim
    pre = "model.layers.{}.".format
    assert sd[pre(0) + "linear_attn.q_proj.weight"].shape == (kd, d)
    assert sd[pre(1) + "linear_attn.v_proj.weight"].shape == (vd, d)
    assert sd[pre(2) + "linear_attn.g_proj.weight"].shape == (vd, d)
    assert sd[pre(2) + "linear_attn.a_proj.weight"].shape == (
        cfg.linear_n_v_heads, d)
    assert sd[pre(0) + "linear_attn.k_conv1d.weight"].shape == (kd, 1, 4)
    assert sd[pre(3) + "self_attn.q_norm.weight"].shape == (cfg.q_dim,)
    assert sd[pre(3) + "post_feedforward_layernorm.weight"].shape == (d,)
    assert pre(3) + "linear_attn.A_log" not in sd
    assert pre(0) + "self_attn.q_proj.weight" not in sd
    assert pre(0) + "input_layernorm.weight" not in sd
    np.testing.assert_array_equal(  # b before a, v's taps behind q's and k's
        sd[pre(1) + "linear_attn.a_proj.weight"],
        np.asarray(params["blocks"]["la_wba"][1]).T[cfg.linear_n_v_heads:])
    np.testing.assert_array_equal(
        sd[pre(1) + "linear_attn.v_conv1d.weight"][:, 0],
        np.asarray(params["blocks"]["la_conv"][1]).T[2 * kd:])
    back = FAMILY.params_from_sd(cfg, sd)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(flat, jax.tree.leaves(back)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))


# -------------------------------------------- the program against the reference


def test_train_forward_over_packed_rows_matches_the_reference(cfg, params):
    """One packed row of three segments (two chunk boundaries inside
    segments, two segment starts inside chunks) against the three run
    apart through the reference: the recurrence and the conv restart at
    every segment start, and the full layer sees no position."""
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


def test_prefill_then_decode_through_the_hybrid_cache_matches_the_reference(
        cfg, params):
    """Right-aligned prompts of unequal length through `prefill`, then six
    `decode_step`s through both populations of the cache (K/V at as many
    heads as queries, fp32 states of [12, 24]), against the reference's
    full forward pass of each row."""
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
    assert cache.k.shape[:2] == (1, 3) and cache.k.shape[-2:] == (4, 16)
    assert cache.state.shape == (3, 3, 4, 12, 24)
    assert cache.state.dtype == jnp.float32
    logits, cache = jax.jit(
        lambda p, t, s, c: tfm.prefill(p, cfg, t, s, c, use_flash=False)
    )(params, jnp.asarray(prompt), jnp.asarray(seg), cache)
    for i, p in enumerate(plens):
        np.testing.assert_allclose(logits[i], want[i][p - 1], **TOL)
    step = jax.jit(lambda p, tok, pos, c, slot, vf: tfm.decode_step(
        p, cfg, tok, pos, c, slot, vf))
    for t in range(new):
        tok = jnp.asarray([r[p + t] for r, p in zip(rows, plens)], jnp.int32)
        logits, cache = step(
            params, tok, jnp.asarray(plen + t, jnp.int32), cache,
            jnp.int32(sp + t), jnp.asarray(sp - plen, jnp.int32))
        for i, p in enumerate(plens):
            np.testing.assert_allclose(logits[i], want[i][p + t], **TOL)


def test_gradients_match_the_reference(cfg, params):
    """d(sum of next-token log-probs)/d(params) through the chunked rule
    and the norms on the branch outputs under the `dots_small` policy —
    the saved tensor is the NORMED output (`_layer_of`) — against autodiff
    of the plain reference."""
    seq = _sequences(cfg, lens=(90,), seed=2)[0]
    toks = jnp.asarray(seq)

    def score(logits):
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return jnp.sum(jnp.take_along_axis(lp, toks[1:, None], axis=-1))

    def system(p):
        return score(tfm.forward(
            p, cfg, toks[None], jnp.ones((1, len(seq)), jnp.int32),
            remat="dots_small")[0])

    got = jax.jit(jax.grad(system))(params)
    want = jax.grad(lambda p: score(reference.logits(p, cfg, seq)))(params)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=jax.tree_util.keystr(path))


def test_a_beta_above_one_is_reached(cfg, params):
    """The range the field opens is exercised on the seeded weights: some
    beta_t lies above 1 in every linear layer (and none at or above 2), so
    I - beta k k^T flips a direction's sign somewhere in every test above;
    the program's gates give the same beta."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    beta_max = np.asarray(reference.final_state(params, cfg, seq)[2])
    assert beta_max.shape == (3,)
    assert (beta_max > 1.0).all() and (beta_max < 2.0).all(), beta_max
    ba = jnp.asarray(np.random.default_rng(0).normal(size=(5, 8)), jnp.float32)
    blk = {k: params["blocks"][k][0] for k in ("la_A_log", "la_dt_bias")}
    beta, _ = la._gates(ba, blk, cfg)
    plain, _ = la._gates(
        ba, blk, dataclasses.replace(cfg, linear_neg_eigval=False))
    np.testing.assert_allclose(beta, 2.0 * jax.nn.sigmoid(ba[:, :4]), rtol=1e-6)
    np.testing.assert_array_equal(beta, 2.0 * plain)


# ------------------------------------------ what the tolerance has to refuse


@pytest.fixture(scope="module")
def scored(cfg, params):
    """(a sequence, the system's log-probs of it, the reference's)."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    return (seq, _system_logprobs(params, cfg, seq),
            reference.next_token_logprobs(params, cfg, seq))


def test_the_system_sits_inside_the_fp32_tolerance(scored):
    _, got, want = scored
    tol = reference.TOLERANCE_FP32
    assert np.isfinite(want).all()  # `check_state` passed too
    assert np.abs(got - want).mean() < tol["mean_abs"]
    assert np.abs(got - want).max() < tol["max_abs"]
    assert tol == {"mean_abs": 0.0001, "max_abs": 0.001}


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_fp32_tolerance_refuses_each_fault(cfg, params, scored, fault):
    """Eight departures from the layer's equations — the three assumed
    conventions undone each its own way, beta left in (0, 1), the output
    gate, the conv's SiLU and the final norm left out — each move the
    log-probabilities by more than ten times the bound the CPU holds the
    system to, by the mean and by the maximum."""
    seq, got, _ = scored
    wrong = reference.next_token_logprobs(params, cfg, seq, fault=fault)
    tol = reference.TOLERANCE_FP32
    assert np.abs(got - wrong).mean() > 10 * tol["mean_abs"], fault
    assert np.abs(got - wrong).max() > 10 * tol["max_abs"], fault


def test_the_faults_are_the_eight_of_the_issue():
    assert len(reference.FAULTS) == len(set(reference.FAULTS)) == 8


@pytest.mark.parametrize("lower,kept", [
    ("bfloat16", False), ("bfloat16:state", False), ("bfloat16:gates", True)])
def test_the_limits_refuse_the_reference_a_precision_lower(
        cfg, params, scored, lower, kept):
    """The `LOWER_PRECISION` control: S (alone or with the gates) rounded
    to bfloat16 at every step ends on a state bfloat16 holds exactly —
    residual 0, refused by `state_bf16_residual_min` under the chip's
    limits and the CPU's; the gates alone leave S in float32 and are
    refused by the CPU's fp32 log-prob bound alone, as every one is."""
    seq, _, want = scored
    _, (state, tail, _) = reference._next_token_logprobs(
        params, cfg, seq, None, len(seq))
    low, (low_state, low_tail, _) = reference._next_token_logprobs(
        params, cfg, seq, lower, len(seq))
    readings = reference.state_readings(low_state, low_tail, state, tail)
    assert (readings["state_bf16_residual_min"] > 8e-4) == kept
    for tol in (reference.STATE_TOLERANCE, reference.STATE_TOLERANCE_FP32):
        refused = [p for p in reference.state_problems(readings, tol)
                   if "no more than bfloat16" in p]
        assert bool(refused) == (not kept), (readings, tol)
    assert np.abs(low[: len(seq) - 1] - want).max() > (
        reference.TOLERANCE_FP32["max_abs"])
    assert reference.LOWER_PRECISION == "bfloat16"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_state_the_decode_program_leaves_is_the_references(
        cfg, params, dtype):
    """`check_state`: prefill over the first half of a sequence, decode
    steps over the rest, and the cache's S [3, 4, 12, 24] and conv inputs
    against what the reference's token recurrence ends on — in float32 to
    rounding; with bf16 weights and activations inside TWICE the chip's
    limits (at hidden 64 a bf16 rounding is a larger share of a norm's sum
    than at 3,840, where the chip reads a third of its limits); either way
    the state holds what bfloat16 could not."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    p = params if dtype == "float32" else jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), params)
    state, tail, _ = reference.final_state(p, cfg, seq)
    got_state, got_tail = reference.system_state(p, cfg, seq)
    assert got_state.shape == state.shape == (3, 4, 12, 24)
    assert got_state.dtype == jnp.float32 and got_tail.shape == tail.shape
    readings = reference.state_readings(got_state, got_tail, state, tail)
    tol = dict(reference.STATE_TOLERANCE_FP32)
    if dtype == "bfloat16":
        tol = {k: v * (1 if k == "state_bf16_residual_min" else 2)
               for k, v in reference.STATE_TOLERANCE.items()}
    assert reference.state_problems(readings, tol) == [], readings
    assert 8e-4 < readings["state_bf16_residual_min"] < 2e-3


# ----------------------------------------------- the forms, as the program says


def test_the_programs_say_which_form_of_the_rule_they_run(cfg, monkeypatch):
    """The two numbers `gdn_kernel_forms` adds up: off a TPU both forms
    are the `jnp` ones; on one the published 96 x 192 head is stepped by
    `gdn_delta_step` as q3next's 128 x 128 is (rows in whole sublane
    tiles, a lane tile and a half of columns: PR 60) and swept by
    `gdn_chunk` on zero columns, and the toys' 12 x 24 by neither."""
    seg = jnp.ones((2, 64), jnp.int32)
    stats = la.BRANCH.train_stats(cfg, 3, seg, None)
    assert float(stats["linear_attn/rule_on_kernel"]) == 0.0
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, 2, 64))
    pool = la.BRANCH.cache_stats(cfg, cache, 2, 64)
    assert pool["gdn_step_on_kernel"] == 0
    assert pool["state_cache_bytes"] == 3 * 2 * (4 * 12 * 24 * 4 + 3 * 192 * 4)
    assert pool["kv_cache_bytes"] == 2 * 2 * 64 * 4 * 16 * 4
    from areal_tpu.ops.pallas import delta_chunk, delta_step

    assert delta_chunk.fits(96, 192) and delta_step.fits(96, 192)
    assert delta_chunk.fits(128, 128) and delta_step.fits(128, 128)
    assert not delta_step.fits(cfg.linear_k_head_dim, cfg.linear_v_head_dim)
    # On a TPU backend the published widths' pool says the kernel, the
    # toy's still the `jnp` form: the shapes decide, nothing else.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.BRANCH.cache_stats(cfg, cache, 2, 64)["gdn_step_on_kernel"] == 0
    big = bench_run.model_config(
        files.load_json("configs", "olmo-hybrid-7b-l4-v8.json"))
    assert (big.linear_k_head_dim, big.linear_v_head_dim) == (96, 192)
    assert la.step_kernel_form(big) == (True, None)
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(big, 2, 64))
    assert la.BRANCH.cache_stats(big, cache, 2, 64)["gdn_step_on_kernel"] == 1


def test_refusals_keep_their_name(cfg):
    """Data and fsdp layouts, the static decode program: everything else
    is refused as `HybridLayoutError`, as for every plan with state."""
    assert isinstance(tfm.plan_refusal(cfg, serving=True), tfm.HybridLayoutError)
    assert isinstance(
        tfm.plan_refusal(cfg, serving=False), tfm.HybridLayoutError)


# ---------------------------------------------- the cell's window, rehearsed

# `olmoh-rollout64-512` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

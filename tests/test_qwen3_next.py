"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B) at toy size on the CPU, seeded
random weights, fp32: a layer pattern scanned by period (3 Gated DeltaNet
blocks to 1 gated softmax-attention block), recurrent state beside the KV
cache, a shared expert, one expert-parallel rank's share of the routed
experts — against the plain reference of
`benchmark/references/qwen3_next.py` (the delta rule token by token),
through the train forward over packed rows, the static prefill + decode
through the hybrid cache, and the gradients; the shares of all ranks
against the uncut layer; the HF reader both ways; the sharding rules; the
named refusals; and that every other family is still a period of one.
Logits and log-probabilities are compared, never sampled tokens.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import linear_attention as la
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig, tiny_config
from areal_tpu.models.hf import registry
from areal_tpu.parallel import sharding
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import qwen3_next as reference

TOL = dict(rtol=5e-4, atol=5e-4)


def _toy_hf(held=4):
    """The benchmark configuration's keys at its `toy` sizes; `held`
    experts of the router's 8 (8: the whole layer, no share)."""
    config = files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json")
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout64-512.json"))
    config["num_experts"] = held
    if held == 8:
        del config["share"]
    return config


def _cfg(held=4, **changes) -> ModelConfig:
    cfg = registry.HF_FAMILIES["qwen3_next"].config_from_hf(_toy_hf(held))
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales and gates, so that a
    (1 + w) norm read as w, or a gate left out, cannot pass."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = ("ln1", "ln2", "q_norm", "k_norm", "la_norm", "la_dt_bias")
    for k, name in zip(jax.random.split(jax.random.PRNGKey(seed + 1), 6), names):
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


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    assert {k: config[k] for k in published} == published
    cut = {"num_hidden_layers": 4, "num_experts": 64, "vocab_size": 18992}
    assert {k: config[k] for k in cut} == cut
    assert sorted(config["benchmark"]["reduced"]) == sorted(cut)
    assert config["share"]["router_num_experts"] == 512
    assert config["vocab_size"] * 8 == config["share"]["published_vocab_size"]
    cfg = bench_run.model_config(config)
    assert (cfg.n_experts, cfg.router_width, cfg.expert_offset) == (64, 512, 0)
    assert (cfg.n_periods, cfg.n_linear_layers, cfg.rotary_dim) == (1, 3, 64)
    assert cfg.linear_conv_dim == 8192 and cfg.linear_value_dim == 4096
    # 1.028 B parameters: the arithmetic of `reduced`, from the shapes.
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(n - 1.028e9) < 2e6, n


def test_config_both_ways_and_a_published_config_is_the_whole_model(cfg):
    family = registry.HF_FAMILIES["qwen3_next"]
    again = family.config_from_hf(family.config_to_hf(cfg))
    assert dataclasses.replace(again, param_dtype="float32") == cfg
    assert registry.infer_model_type(cfg) == "qwen3_next"
    whole = _cfg(held=8)  # no share group: 1 of 1
    assert not whole.expert_share and whole.router_width == 8
    assert "share" not in family.config_to_hf(whole)


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("attention_bias", True), ("rope_scaling", {"type": "yarn"}),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        registry.HF_FAMILIES["qwen3_next"].config_from_hf(
            dict(_toy_hf(), **{key: value}))


def test_state_dict_round_trip_by_the_published_names(cfg, params):
    family = registry.HF_FAMILIES["qwen3_next"]
    sd = family.params_to_sd(cfg, params)
    d, hk = cfg.hidden_dim, cfg.linear_n_k_heads
    dk, dv = cfg.linear_k_head_dim, cfg.linear_v_head_dim
    r = cfg.linear_n_v_heads // hk
    # Layers 0-2 are linear, layer 3 full attention; the fused shapes.
    assert sd["model.layers.0.linear_attn.in_proj_qkvz.weight"].shape == (
        hk * (2 * dk + 2 * r * dv), d)
    assert sd["model.layers.2.linear_attn.in_proj_ba.weight"].shape == (
        2 * cfg.linear_n_v_heads, d)
    assert sd["model.layers.1.linear_attn.conv1d.weight"].shape == (
        cfg.linear_conv_dim, 1, 4)
    assert sd["model.layers.3.self_attn.q_proj.weight"].shape == (
        2 * cfg.q_dim, d)
    assert "model.layers.3.linear_attn.A_log" not in sd
    assert "model.layers.0.self_attn.q_proj.weight" not in sd
    assert sd["model.layers.0.mlp.gate.weight"].shape == (8, d)
    assert "model.layers.0.mlp.experts.3.up_proj.weight" in sd
    assert "model.layers.0.mlp.experts.4.up_proj.weight" not in sd  # held: 0-3
    # The fused orderings, as HF splits them: per KEY head [q, k, v x r,
    # z x r]; per attention head [query, gate].
    qkvz = sd["model.layers.1.linear_attn.in_proj_qkvz.weight"].reshape(
        hk, 2 * dk + 2 * r * dv, d)
    ours = np.asarray(params["blocks"]["la_wqkv"][1]).T  # [C, D]
    kd = cfg.linear_key_dim
    np.testing.assert_array_equal(qkvz[1, :dk], ours[dk: 2 * dk])  # q, head 1
    np.testing.assert_array_equal(
        qkvz[1, dk: 2 * dk], ours[kd + dk: kd + 2 * dk])
    np.testing.assert_array_equal(  # its value heads: 2 and 3
        qkvz[1, 2 * dk: 2 * dk + r * dv],
        ours[2 * kd + r * dv: 2 * kd + 2 * r * dv])
    np.testing.assert_array_equal(
        qkvz[0, 2 * dk + r * dv:], np.asarray(params["blocks"]["la_wz"][1]).T[: r * dv])
    q_proj = sd["model.layers.3.self_attn.q_proj.weight"].reshape(
        cfg.n_q_heads, 2, cfg.head_dim, d)
    np.testing.assert_array_equal(
        q_proj[2, 1],
        np.asarray(params["blocks"]["wqg"][0]).T[2 * cfg.head_dim: 3 * cfg.head_dim])
    back = family.params_from_sd(cfg, sd)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(flat, jax.tree.leaves(back)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))


# -------------------------------------------- the program against the reference


@pytest.mark.parametrize("held", [4, 8])
def test_train_forward_over_packed_rows_matches_the_reference(held):
    """One packed row of three segments against the three run apart
    through the reference: the recurrence and the conv restart at every
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


def test_the_chunked_delta_rule_equals_the_token_recurrence(cfg, params):
    """`linear_attn_forward` (chunks of 64, WY form) over a packed row of
    three segments — two chunk boundaries inside segments, two segment
    starts inside chunks — against the reference's `lax.scan` over tokens,
    each segment apart; and the state it leaves against the decode step's."""
    rng = np.random.default_rng(3)
    lens = (70, 50, 30)
    h = jnp.asarray(rng.normal(size=(1, sum(lens), cfg.hidden_dim)), jnp.float32)
    seg = jnp.asarray(np.concatenate(
        [np.full(n, i + 1) for i, n in enumerate(lens)]).astype(np.int32))[None]
    blk = {k: v[1] for k, v in params["blocks"].items() if k in la.LINEAR_LEAVES}
    got, state, tail = la.linear_attn_forward(h, blk, cfg, seg, with_state=True)
    off = 0
    with jax.default_matmul_precision("highest"):
        for n in lens:
            want, ref_state, ref_tail = reference._delta_net(
                h[0, off: off + n], params["blocks"], 1, cfg)
            np.testing.assert_allclose(got[0, off: off + n], want, **TOL)
            off += n
    # What the row's last segment leaves is what the reference ends on.
    np.testing.assert_allclose(state[0], ref_state, **TOL)
    np.testing.assert_allclose(tail[0], ref_tail, **TOL)
    # Stepping the last segment token by token ends in the same state.
    s = jnp.zeros_like(state)[None]  # one layer's caches
    t = jnp.zeros_like(tail)[None]
    for i in range(sum(lens) - lens[-1], sum(lens)):
        y, s, t = la.linear_attn_step(h[:, i: i + 1], blk, cfg, s, t, 0)
    np.testing.assert_allclose(y[:, 0], got[:, -1], **TOL)
    np.testing.assert_allclose(s[0], state, **TOL)
    np.testing.assert_allclose(t[0], tail, **TOL)


def test_prefill_then_decode_through_the_hybrid_cache_matches_the_reference(
        cfg, params):
    """Right-aligned prompts of unequal length through `prefill`, then six
    `decode_step`s through both kinds of state, against the reference's
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
    assert cache.k.shape[0] == 1 and cache.state.shape[:2] == (3, 3)
    assert cache.state.dtype == jnp.float32
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
        assert counts["moe"].shape == (cfg.n_layers, cfg.n_experts)
        for i, p in enumerate(plens):
            np.testing.assert_allclose(logits[i], want[i][p + t], **TOL)


def test_gradients_match_the_reference(cfg, params):
    """d(sum of next-token log-probs)/d(params) through the chunked scan
    under `jax.checkpoint` against autodiff of the plain reference."""
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
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=jax.tree_util.keystr(path))


def test_the_fp32_tolerance_fails_a_state_kept_in_bf16(cfg, params):
    """What the configuration states in float32 — the recurrent state, the
    gates, the router's logits — rounded to bfloat16 at every step moves
    log-probabilities by far more than the fp32 bound the CPU rehearsal
    holds the generator to; the system itself sits inside it.  (On the
    chip the bf16 activations cost more than this does: the config file's
    `tolerance.why`.)"""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    want = reference.next_token_logprobs(params, cfg, seq)
    low = reference.next_token_logprobs(
        params, cfg, seq, lower=reference.LOWER_PRECISION)
    logits = tfm.forward(
        params, cfg, jnp.asarray(seq)[None], jnp.ones((1, len(seq)), jnp.int32))
    lp = jax.nn.log_softmax(logits[0, :-1], axis=-1)
    got = np.asarray(jnp.take_along_axis(lp, jnp.asarray(seq)[1:, None], 1))[:, 0]
    tol = reference.TOLERANCE_FP32
    assert np.abs(got - want).mean() < tol["mean_abs"]
    assert np.abs(got - want).max() < tol["max_abs"]
    assert np.abs(low - want).mean() > 10 * tol["mean_abs"]
    assert np.abs(low - want).max() > 10 * tol["max_abs"]
    assert reference.TOLERANCE == {"mean_abs": 0.02, "max_abs": 0.25}


def _bf16(tree):
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)


def _reference_state(params, cfg, seq, lower=None):
    """(log-probs, S, conv inputs) the reference ends on after `seq`."""
    lp, state, tail = reference._next_token_logprobs(
        params, cfg, seq, lower, len(seq))
    return lp[: len(seq) - 1], state, tail


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_state_the_decode_program_leaves_is_the_references(
        cfg, params, dtype):
    """`check_state`: prefill over the first half of a sequence, decode
    steps over the rest, and the cache's S and conv inputs against what the
    reference's token recurrence ends on — in float32 to rounding, with
    bf16 weights and activations inside the chip's limits; either way the
    state holds what bfloat16 could not (`state_bf16_residual_min`)."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    p = params if dtype == "float32" else _bf16(params)
    _, state, tail = _reference_state(p, cfg, seq)
    got_state, got_tail = reference.system_state(p, cfg, seq)
    assert got_state.shape == state.shape == (3, 4, 16, 16)
    assert got_state.dtype == jnp.float32 and got_tail.shape == tail.shape
    readings = reference.state_readings(got_state, got_tail, state, tail)
    tol = (reference.STATE_TOLERANCE_FP32 if dtype == "float32"
           else reference.STATE_TOLERANCE)
    assert reference.state_problems(readings, tol) == [], readings
    assert 8e-4 < readings["state_bf16_residual_min"] < 2e-3
    if dtype == "float32":  # what the CPU rehearsal runs
        assert reference.check_state(p, cfg, seq, state, tail) == (readings, [])


@pytest.mark.parametrize("lower,kept", [
    ("bfloat16", False), ("bfloat16:state", False),
    ("bfloat16:gates", True), ("bfloat16:router", True)])
def test_the_state_limit_refuses_the_reference_a_precision_lower(
        cfg, params, lower, kept):
    """The control of the configuration's `tolerance.state`: the reference
    with S (alone, or with the gates and the router's logits) rounded to
    bfloat16 at every step ends on a state that bfloat16 holds exactly —
    residual 0 — and is refused under the chip's limits and the CPU's,
    although its S lies NEARER the reference proper than the system's own
    bf16 activations put it (why no error bound can refuse it).  Gates or
    router alone leave S in float32 and pass this limit: on the chip only
    the CPU's fp32 log-prob bound refuses those (PERF.md section 6)."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    want, state, tail = _reference_state(params, cfg, seq)
    low, low_state, low_tail = _reference_state(params, cfg, seq, lower)
    readings = reference.state_readings(low_state, low_tail, state, tail)
    assert (readings["state_bf16_residual_min"] > 8e-4) == kept
    for tol in (reference.STATE_TOLERANCE, reference.STATE_TOLERANCE_FP32):
        refused = [p for p in reference.state_problems(readings, tol)
                   if "no more than bfloat16" in p]
        assert bool(refused) == (not kept), (readings, tol)
    system = reference.state_readings(
        *reference.system_state(_bf16(params), cfg, seq), state, tail)
    assert readings["state_rel_err_max"] < system["state_rel_err_max"]
    # The CPU's log-prob bound refuses every one of them.
    assert np.abs(low - want).max() > reference.TOLERANCE_FP32["max_abs"]


def test_a_decode_step_that_keeps_its_state_in_bf16_is_not_correct(
        cfg, params, monkeypatch):
    """What a later change might do for the bytes — S rounded to bfloat16
    as the decode step writes it — turns `next_token_logprobs` to NaN,
    which `checks.reference_check` reports as not `correct`."""
    seq = _sequences(cfg, lens=(96,), seed=7)[0]
    assert np.isfinite(reference.next_token_logprobs(params, cfg, seq)).all()
    from areal_tpu.models import linear_attention

    inner = linear_attention.linear_attn_step

    def rounded(h, blk, c, states, tails, li, *kernel):
        y, states, tails = inner(h, blk, c, states, tails, li, *kernel)
        return y, jax.lax.reduce_precision(states, 8, 7), tails

    monkeypatch.setattr(linear_attention, "linear_attn_step", rounded)
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


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(dispatch):
    """The guide's shares test: with the router's 8 experts over 4 ranks of
    2, the four partial MoE outputs — each with the shared expert, which
    every rank computes alike, so counted once — sum to what the plain
    reference gives for the whole layer."""
    whole = _cfg(held=8, moe_dispatch=dispatch)
    params = _params(whole)
    blocks = params["blocks"]
    blk = {k: v[2] for k, v in blocks.items()
           if k in ("router", "wg", "wu", "wd", "ws_g", "ws_u", "ws_d", "ws_gate")}
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(2, 24, whole.hidden_dim)), jnp.float32)
    x = h.reshape(-1, whole.hidden_dim)
    with jax.default_matmul_precision("highest"):
        want = reference._moe(x, blocks, 2, whole)
        shared = jax.nn.sigmoid(x @ blk["ws_gate"]) * (
            (jax.nn.silu(x @ blk["ws_g"]) * (x @ blk["ws_u"])) @ blk["ws_d"])
    total, local_rows = 0.0, 0
    for rank in range(4):
        part = dataclasses.replace(
            whole, n_experts=2, n_router_experts=8, expert_offset=2 * rank)
        mine = dict(blk, **{n: blk[n][2 * rank: 2 * rank + 2]
                            for n in ("wg", "wu", "wd")})
        out, aux, counts = tfm._mlp_moe(h, mine, part)
        total = total + out.reshape(x.shape)
        local_rows += int(counts.sum())
        assert counts.shape == (2,)
        # The load-balancing loss is over the router's whole width: every
        # rank computes the same one.
        np.testing.assert_allclose(aux, tfm._mlp_moe(h, blk, whole)[1], rtol=1e-5)
    assert local_rows == x.shape[0] * whole.n_experts_per_tok
    np.testing.assert_allclose(total - 3 * shared, want, **TOL)
    # ... and the whole layer in one piece is the same layer.
    np.testing.assert_allclose(
        tfm._mlp_moe(h, blk, whole)[0].reshape(x.shape), want, **TOL)


def test_rows_routed_elsewhere_cost_no_rows_here(cfg, params):
    """Group sizes cover the held experts' rows alone: what the ragged
    kernels are asked to multiply is the local rows, not T x k."""
    h = jnp.asarray(
        np.random.default_rng(5).normal(size=(1, 32, cfg.hidden_dim)),
        jnp.float32)
    blk = {k: v[0] for k, v in params["blocks"].items()
           if not k.startswith("la_") and k not in tfm._FULL_ATTN_LEAVES}
    x = h.reshape(-1, cfg.hidden_dim)
    top_w, top_idx, one_hot, _ = tfm._moe_route(x, blk, cfg)
    held = np.asarray(top_idx) < cfg.n_experts
    assert 0 < held.sum() < held.size  # some choices fell to absent experts
    assert int(one_hot.sum()) == held.sum()
    assert np.asarray(top_idx).max() == cfg.n_experts  # the sentinel


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
def test_layouts_a_hybrid_pattern_cannot_run_are_refused_by_name(cfg, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.HybridLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)
    sharding.attn_dispatch(mesh, tiny_config())  # every other model: fine


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
    assert pool["moe_rows_routed"] == 5 * 4 * cfg.n_experts_per_tok * cfg.n_layers
    assert 0 < pool["moe_rows_local"] < pool["moe_rows_routed"]
    assert 1 <= pool["moe_experts_touched"] <= cfg.n_experts
    # [3 linear layers, 4 rows]: fp32 state + conv tail; k/v of ONE layer.
    s_total = 256  # bucket_len(128 + 5)
    assert pool["state_cache_bytes"] == 3 * 4 * (
        4 * 16 * 16 * 4 + 3 * cfg.linear_conv_dim * 4)
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


def test_the_train_step_counts_segment_starts_and_flops_follow_the_kinds(cfg):
    from areal_tpu.base import monitor
    from benchmark import peaks_hybrid

    # The program's own count = the benchmark's, but for the recurrence
    # (3 multiply-adds a state element there, 7 FLOPs here).
    rec = cfg.n_linear_layers * cfg.linear_n_v_heads * 16 * 16
    assert monitor.matmul_params(cfg) - 3 * rec == pytest.approx(
        peaks_hybrid.matmul_params(cfg))
    # A dense twin with softmax attention in every layer counts 4 of them.
    assert sum(n for n, b in monitor._layers_of(cfg) if b.attn_flops) == 1
    assert sum(n for n, b in monitor._layers_of(tiny_config()) if b.attn_flops) == tiny_config().n_layers
    big = bench_run.model_config(
        files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json"))
    # Section "The cut" of ISSUE 32: bytes a decode step's mixers move.
    assert peaks_hybrid.gdn_decode_bytes(big, 64) == pytest.approx(
        3 * (2 * 33.75e6 + 2 * 134.2e6 + 2 * 3.1e6), rel=0.01)
    assert peaks_hybrid.experts_per_token_held(big) == 1.25


# ------------------------------------- every other family is a period of one


# |leaf| sums of `init_params(cfg, PRNGKey(7))` at the commit before the
# period scan (51013b4): paths and seeded weights must not move.
_PINNED = {
    "dense": {"['blocks']['bk']": 0.0, "['blocks']['bq']": 0.0, "['blocks']['bv']": 0.0, "['blocks']['ln1']": 256.0, "['blocks']['ln2']": 256.0, "['blocks']['wd']": 2100.189, "['blocks']['wg']": 2964.115, "['blocks']['wk']": 741.186, "['blocks']['wo']": 1490.928, "['blocks']['wq']": 1465.073, "['blocks']['wu']": 2971.521, "['blocks']['wv']": 742.191, "['embed']": 2935.536, "['final_ln']": 64.0, "['lm_head']": 2972.106},
    "moe": {"['blocks']['k_norm']": 128.0, "['blocks']['ln1']": 256.0, "['blocks']['ln2']": 256.0, "['blocks']['q_norm']": 256.0, "['blocks']['router']": 89.521, "['blocks']['wd']": 5923.322, "['blocks']['wg']": 5948.118, "['blocks']['wk']": 741.186, "['blocks']['wo']": 1490.928, "['blocks']['wq']": 1465.073, "['blocks']['wu']": 5938.193, "['blocks']['wv']": 742.191, "['embed']": 23484.285, "['final_ln']": 64.0, "['lm_head']": 2972.106},
}


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_a_period_of_one_keeps_its_paths_and_its_seeded_weights(name):
    cfg = tiny_config() if name == "dense" else dataclasses.replace(
        tiny_config(n_experts=4), qk_norm=True, qkv_bias=False)
    assert not cfg.is_hybrid and cfg.n_periods == cfg.n_layers
    p = tfm.init_params(cfg, jax.random.PRNGKey(7))
    got = {
        jax.tree_util.keystr(k): round(
            float(jnp.sum(jnp.abs(v.astype(jnp.float32)))), 3)
        for k, v in jax.tree_util.tree_flatten_with_path(p)[0]
    }
    assert got.keys() == _PINNED[name].keys()
    assert got == pytest.approx(_PINNED[name], rel=1e-5)
    cache = tfm.init_kv_cache(cfg, 2, 16)
    assert cache.state is None and cache.conv is None
    assert len(jax.tree.leaves(cache)) == 2


# ---------------------------------------------- the cell's window, rehearsed

# `q3next-rollout64-512` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

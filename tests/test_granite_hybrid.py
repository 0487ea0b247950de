"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro, `granitemoehybrid`) at
toy size on the CPU, seeded random weights, fp32: Mamba-2 mixers in
TWO-BRANCH layers with a dense SwiGLU MLP, attention without positions,
four multipliers, a tied head — against the plain reference of
`benchmark/references/granitemoehybrid.py` (the recurrence token by token),
through the train forward over packed rows, its gradients, the static
prefill + decode through the cache, and the SERVING PLANE: the ragged
recurrence's hazards (prompt slices of every width, a reused slot, slots
with no lane, dead lanes, more requests than slots), greedy token for token
against the static program, what the chunk leaves in its slots; each
multiplier shown to be applied; the HF reader both ways; the refusals by
name; and that the dense chunk lowers to the program it lowered to.  Logits
and log-probabilities are compared, never sampled tokens (the greedy case
compares the tokens of two programs of ONE system).
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base import monitor
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import generator as gen_mod
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.models import mamba
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import MLP, SSM, ModelConfig, tiny_config
from areal_tpu.models.hf import registry
from areal_tpu.parallel import sharding
from benchmark import files
from benchmark import run as bench_run
from benchmark.references import granitemoehybrid as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "granite-4.0-h-micro-l10.json"
FAMILY = registry.HF_FAMILIES["granitemoehybrid"]


def _toy_hf(**changes):
    """The benchmark configuration's keys at its `toy` sizes."""
    config, _ = bench_run.toy(
        files.load_json("configs", CONFIG),
        files.load_json("traffic", "waves-over-slots.json"))
    return dict(config, **changes)


# The toy's layers: Mamba-2 on both sides of the attention layer.
LAYERS = ["mamba", "mamba", "attention", "mamba", "mamba"]


def _cfg(**changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf())
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


# The programs under test, jitted (the model's config is static).
_forward = jax.jit(
    lambda p, c, t, s: tfm.forward(p, c, t, s, use_flash=False),
    static_argnums=1)
_prefill = jax.jit(
    lambda p, c, t, s, cache: tfm.prefill(p, c, t, s, cache, use_flash=False),
    static_argnums=1)
_decode = jax.jit(tfm.decode_step, static_argnums=1)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales, conv bias and D, so
    that a bias or a skip left out cannot pass."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = ("ln1", "ln2", "ssm_norm", "ssm_conv_b", "ssm_D", "ssm_dt_bias")
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


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
    bench = config["benchmark"]
    assert bench["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "layer_types"}
    assert set(bench["reduced"]) == reduced
    for key, value in row["config"].items():
        if key not in reduced:
            assert config[key] == value, key
    # The cut is depth alone: one whole period, 9 : 1 as published.
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["layer_types"].count("mamba") == 9
    assert config["num_hidden_layers"] == 10
    assert bench["weights_seed"] == 53
    for group in ("assumed", "stands_for", "deployment", "tolerance", "toy"):
        assert bench[group], group
    assert {"tensor_names", "state_precision", "ssm_init", "attention_scale",
            "logits_scaling"} <= set(bench["assumed"])
    # The builder's own count, from the leaves the program allocates.
    cfg = bench_run.model_config(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 951_991_232
    assert cfg.plan.unit == ((SSM, MLP),) * 5 + (("attention", MLP),) + (
        (SSM, MLP),) * 4 and cfg.plan.repeats == 1 and not cfg.plan.prefix
    assert (cfg.ssm_inner_dim, cfg.ssm_conv_dim, cfg.ssm_in_dim) == (
        4096, 4352, 8512)
    assert (cfg.head_dim, cfg.attn_scale, cfg.logits_scaling) == (64, 1 / 64, 8)
    assert cfg.tied_embeddings and cfg.pos_emb == "none"
    # The serving plane's slots at the cell's 64: 1.21 GB of state.
    pool = jax.eval_shape(
        lambda: tfm.init_paged_kv_cache(cfg, 8, 128, n_slots=64))
    assert [a.shape for a in pool.state] == [(1, 64, 64, 64, 128)] * 9
    assert {a.dtype for a in pool.state} == {jnp.dtype(jnp.float32)}
    assert [a.shape for a in pool.conv] == [(1, 64, 3, 4352)] * 9
    assert pool.k.shape[0] == 1  # pages for the attention layer ALONE


def test_config_both_ways(cfg):
    hf = FAMILY.config_to_hf(cfg)
    back = dataclasses.replace(FAMILY.config_from_hf(hf), param_dtype="float32")
    assert back == cfg
    assert registry.infer_model_type(cfg) == "granitemoehybrid"
    assert hf["layer_types"] == LAYERS and hf["logits_scaling"] == 8


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("attention_bias", True),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("hidden_act", "gelu"), ("position_embedding_type", "alibi"),
    ("rope_scaling", {"type": "yarn"}),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        FAMILY.config_from_hf(_toy_hf(**{key: value}))


def test_state_dict_round_trip_by_the_assumed_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    d, f = cfg.hidden_dim, cfg.intermediate_dim
    assert sd["model.layers.0.mamba.in_proj.weight"].shape == (cfg.ssm_in_dim, d)
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (
        cfg.ssm_conv_dim, 1, cfg.ssm_conv_kernel)
    assert sd["model.layers.2.self_attn.q_proj.weight"].shape == (cfg.q_dim, d)
    assert sd["model.layers.2.shared_mlp.input_linear.weight"].shape == (2 * f, d)
    assert "model.layers.2.mamba.in_proj.weight" not in sd
    assert "lm_head.weight" not in sd  # tied
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


# ----------------------------------------- train forward, loss and gradients


def _packed(seqs, pads=6):
    n = sum(len(s) for s in seqs)
    tokens = np.zeros((1, n + pads), np.int32)
    segs = np.zeros((1, n + pads), np.int32)
    off = 0
    for i, s in enumerate(seqs):
        tokens[0, off: off + len(s)] = s
        segs[0, off: off + len(s)] = i + 1
        off += len(s)
    return jnp.asarray(tokens), jnp.asarray(segs)


def test_train_forward_over_packed_rows_matches_the_reference(cfg, params):
    seqs = _sequences(cfg)
    tokens, segs = _packed(seqs)
    got = _forward(params, cfg, tokens, segs)
    off = 0
    for s in seqs:
        np.testing.assert_allclose(
            got[0, off: off + len(s)], reference.logits(params, cfg, s), **TOL)
        off += len(s)


def test_the_fused_logprob_head_divides_by_logits_scaling(cfg, params):
    """What the trainer and the inference engine score with."""
    seq = _sequences(cfg, lens=(40,), seed=3)[0]
    tokens, segs = _packed([seq], pads=0)
    x, _ = tfm.hidden_states(params, cfg, tokens, segs, use_flash=False)
    got = tfm.per_token_output(params, cfg, x, tokens, segs)
    want, _, _ = reference._next_token_logprobs(
        params, cfg, reference._padded(seq), None, len(seq))
    np.testing.assert_allclose(got[0, : len(seq) - 1], want[: len(seq) - 1], **TOL)


def test_loss_and_gradients_match_the_reference(cfg, params):
    seq = _sequences(cfg, lens=(45,), seed=2)[0]
    toks = jnp.asarray(seq)

    def score(logits):
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return jnp.sum(jnp.take_along_axis(lp, toks[1:, None], axis=-1))

    def system(p):
        return score(tfm.forward(
            p, cfg, toks[None], jnp.ones((1, len(seq)), jnp.int32),
            remat="full")[0])

    loss, got = jax.jit(jax.value_and_grad(system))(params)
    ref_loss, want = jax.jit(jax.value_and_grad(
        lambda p: score(reference.logits(p, cfg, seq))))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-4)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)


@pytest.mark.parametrize("multiplier", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling",
])
def test_each_multiplier_is_applied(cfg, params, multiplier):
    """The program with ONE multiplier at its neutral value lies outside
    the bound that holds it to the reference; so does the decode path."""
    seq = _sequences(cfg, lens=(48,), seed=4)[0]
    neutral = 0.0 if multiplier == "attention_multiplier" else 1.0
    off = dataclasses.replace(cfg, **{multiplier: neutral})
    want = np.asarray(reference.logits(params, cfg, seq))
    tokens, segs = _packed([seq], pads=0)
    for c, fine in ((cfg, True), (off, False)):
        got = _forward(params, c, tokens, segs)[0]
        assert bool(np.allclose(got, want, **TOL)) is fine
        cache = tfm.init_kv_cache(c, 1, 64)
        lg, cache = _prefill(params, c, tokens[:, :40], segs[:, :40], cache)
        assert bool(np.allclose(lg[0], want[39], **TOL)) is fine
        lg, _ = _decode(
            params, c, tokens[:, 40], jnp.asarray([40]), cache, 40,
            jnp.zeros((1,), jnp.int32))
        assert bool(np.allclose(lg[0], want[40], **TOL)) is fine


# ------------------------------------------------- static program, the cache


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        cfg, params):
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
    assert cache.k.shape == (1, 3, 64, cfg.n_kv_heads, cfg.head_dim)
    assert cache.state.shape == (4, 3, 4, 16, 16)
    logits, cache = _prefill(
        params, cfg, jnp.asarray(prompt), jnp.asarray(seg), cache)
    for i, p in enumerate(plens):
        np.testing.assert_allclose(logits[i], want[i][p - 1], **TOL)
    for t in range(new):
        tok = jnp.asarray([r[p + t] for r, p in zip(rows, plens)], jnp.int32)
        logits, cache = _decode(
            params, cfg, tok, jnp.asarray(plen + t, jnp.int32), cache, sp + t,
            jnp.asarray(sp - plen, jnp.int32))
        for i, p in enumerate(plens):
            np.testing.assert_allclose(logits[i], want[i][p + t], **TOL)
    _, state, tail = reference._next_token_logprobs(
        params, cfg, rows[1], None, len(rows[1]))
    np.testing.assert_allclose(cache.state[:, 1], state, **TOL)
    np.testing.assert_allclose(cache.conv[:, 1], tail, **TOL)


# ------------------------------------- the serving plane: ragged recurrence

N_SLOTS, PAGE, LANES = 3, 8, 12
TABLE = jnp.arange(N_SLOTS * 8, dtype=jnp.int32).reshape(N_SLOTS, 8)


def _stream(cfg, params, pool, lanes, width):
    """One inner step: `lanes` = [(slot, sequence, first position, n)] packed
    in slot order, the rest of the LANES dead."""
    tok, pos, row = [], [], []
    for slot, seq, p0, n in lanes:
        tok += list(seq[p0: p0 + n])
        pos += list(range(p0, p0 + n))
        row += [slot] * n
    dead = LANES - len(tok)
    return _ragged(
        params, cfg, jnp.asarray(tok + [0] * dead, jnp.int32),
        jnp.asarray(pos + [0] * dead, jnp.int32), pool, TABLE,
        jnp.asarray(row + [N_SLOTS] * dead, jnp.int32), width)


_ragged = jax.jit(
    lambda p, c, tok, pos, pool, table, row, width:
    tfm.decode_step_ragged_paged(
        p, c, tok, pos, pool, table, row, paged_kernel=False,
        slot_lanes=width),
    static_argnums=(1, 7))


def _pool(cfg):
    return tfm.init_paged_kv_cache(
        cfg, N_SLOTS * 8, PAGE, dtype=jnp.float32, n_slots=N_SLOTS)


@pytest.mark.parametrize("width", [1, 3, 8, 12])
def test_prompt_slices_of_any_width_give_one_state(cfg, params, width):
    """A prompt of 11 tokens consumed `width` lanes an inner step in slot
    1, beside a decoding slot 0, then three decode lanes: the logits are
    the reference's at every lane and the state and tail it leaves are the
    reference's S and conv inputs, whatever the slices were."""
    a, b = _sequences(cfg, lens=(30, 14), seed=6)
    want_a = np.asarray(reference.logits(params, cfg, a))
    want_b = np.asarray(reference.logits(params, cfg, b))
    pool, at, pos_a = _pool(cfg), 0, 0
    while at < len(b):
        n = min(width, 11 - at) if at < 11 else 1
        logits, pool = _stream(
            cfg, params, pool, [(0, a, pos_a, 1), (1, b, at, n)], width)
        np.testing.assert_allclose(logits[0], want_a[pos_a], **TOL)
        for i in range(n):
            np.testing.assert_allclose(logits[1 + i], want_b[at + i], **TOL)
        at, pos_a = at + n, pos_a + 1
    _, state, tail = reference._next_token_logprobs(
        params, cfg, b, None, len(b))
    got_state, got_tail = pool.slot_state(1)
    np.testing.assert_allclose(got_state, state, **TOL)
    np.testing.assert_allclose(got_tail, tail, **TOL)
    assert not np.asarray(pool.slot_state(2)[0]).any()  # never held a lane


@pytest.mark.parametrize("hazard", ["reused_slot", "no_lane", "dead_lanes"])
def test_the_ragged_recurrences_hazards(cfg, params, hazard):
    a, b = _sequences(cfg, lens=(20, 9), seed=7)
    pool = _pool(cfg)
    _, pool = _stream(cfg, params, pool, [(0, a, 0, 8), (2, b, 0, 4)], 8)
    before = [jax.tree.map(np.asarray, pool.slot_state(s)) for s in range(3)]
    if hazard == "reused_slot":
        # Slot 0 takes ANOTHER request from position 0: what its first
        # request left is not seen.
        logits, pool = _stream(cfg, params, pool, [(0, b, 0, 5)], 8)
        want = np.asarray(reference.logits(params, cfg, b))
        np.testing.assert_allclose(logits[:5], want[:5], **TOL)
        _, state, tail = reference._next_token_logprobs(
            params, cfg, b, None, 5)
        got_state, got_tail = pool.slot_state(0)
        np.testing.assert_allclose(got_state, state, **TOL)
        np.testing.assert_allclose(got_tail, tail, **TOL)
        return
    if hazard == "no_lane":  # slots 0 and 1 hold no lane this step
        _, pool = _stream(cfg, params, pool, [(2, b, 4, 2)], 8)
        untouched = (0, 1)
    else:  # every lane dead
        _, pool = _stream(cfg, params, pool, [], 8)
        untouched = (0, 1, 2)
    for s in untouched:  # bit for bit
        got_state, got_tail = pool.slot_state(s)
        np.testing.assert_array_equal(got_state, before[s][0])
        np.testing.assert_array_equal(got_tail, before[s][1])


def test_a_plan_with_state_needs_its_slots_and_its_lane_width(cfg, params):
    with pytest.raises(ValueError, match="n_slots"):
        tfm.init_paged_kv_cache(cfg, 4, 8)
    with pytest.raises(ValueError, match="slot_lanes"):
        tfm.decode_step_ragged_paged(
            params, cfg, jnp.zeros((LANES,), jnp.int32),
            jnp.zeros((LANES,), jnp.int32), _pool(cfg), TABLE,
            jnp.zeros((LANES,), jnp.int32), paged_kernel=False)
    assert tfm.plan_refusal(cfg, serving=True) is None


# --------------------------------------------- the engine: waves over slots


def _engine(cfg, params, slots=4, **kw):
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=slots, kv_page_size=8, **kw)


def _sample(cfg, lens=(21, 9, 17), seed=9):
    seqs = _sequences(cfg, lens=lens, seed=seed)
    return SequenceSample(
        keys={"packed_prompts"}, ids=[str(i) for i in range(len(seqs))],
        seqlens={"packed_prompts": [[len(s)] for s in seqs]},
        data={"packed_prompts": np.concatenate(seqs)},
    )


def test_serving_equals_the_static_program_token_for_token(cfg, params):
    """Greedy, 3 prompts x 2 = 6 requests over 4 slots (admission in two
    waves, reused slots, prompts in slices of W = 4) against the static
    program over 8 slots; and what the call counted."""
    g = GenerationHyperparameters(n=2, max_new_tokens=12, greedy=True)
    sample = _sample(cfg)
    static = _engine(cfg, params, slots=8).generate(
        sample, MicroBatchSpec(), g, inflight=False)
    eng = _engine(cfg, params, slots=4, prefill_chunk_tokens=4)
    served = eng.generate(sample, MicroBatchSpec(), g)  # 6 > 4: serving
    np.testing.assert_array_equal(
        served.data["packed_input_ids"], static.data["packed_input_ids"])
    np.testing.assert_allclose(
        served.data["packed_logprobs"], static.data["packed_logprobs"], **TOL)
    pool = eng.last_pool_stats
    assert eng.lanes_dispatched > 0 and eng.dead_live_lanes == 0
    assert eng.prefill_dispatches == 0 and eng.cache_copy_bytes == 0
    assert eng.decode_compiles == 1
    # No prefix pages are shared for a plan with state: followers prefill
    # their whole prompt, and the counter says how many would have shared.
    assert pool["shared_mappings"] == 0 and pool["prefix_hits"] == 0
    assert pool["cow_copies"] == 0 and pool["admit_passed_over"] == 0
    assert pool["ssm_prefix_would_share"] == 3  # every prompt is over a page
    assert pool["ssm_slots_zeroed"] == 6 and pool["admitted"] == 6
    assert pool["ssm_lanes_prefill"] == 2 * (21 + 9 + 17)
    assert pool["ssm_lanes_decode"] == 6 * 12
    assert pool["ssm_state_bytes"] == 4 * 4 * 4 * 16 * 16 * 4
    assert pool["ssm_conv_bytes"] == 4 * 4 * 3 * cfg.ssm_conv_dim * 4
    assert pool["ssm_live_slot_chunks"] >= 6


def test_what_the_serving_plane_leaves_in_its_slots_is_the_references(
        cfg, params):
    """`check_generator`: 6 requests over 4 slots, a slot that served one
    request and a slot that served two, against the reference's S and conv
    inputs; and a state kept in bfloat16 is refused."""
    seq = _sequences(cfg, lens=(40,), seed=11)[0]
    readings, problems = reference.check_generator(
        params, cfg, seq, n_slots=4, n_requests=6)
    assert not problems, (readings, problems)
    assert readings["most_requests_in_a_slot"] >= 2
    assert readings["slots_compared"] == 2
    assert readings["logprob_max_abs"] < 1e-3
    # The control: the recurrence's new state rounded to bfloat16.
    slab = mamba.ssd_slab

    def rounded(*args):
        y, new = slab(*args)
        return y, jax.lax.reduce_precision(new, 8, 7)

    mamba.ssd_slab = rounded
    try:
        readings, problems = reference.check_generator(
            params, cfg, seq, n_slots=4, n_requests=6)
    finally:
        mamba.ssd_slab = slab
    assert readings["state_bf16_residual_min"] == 0.0
    assert any("bfloat16" in p for p in problems), problems


def test_the_chunk_on_the_state_kernel_equals_the_chunk_on_the_jnp_form(
        monkeypatch):
    """Six requests over four slots (two waves: two slots serve a second
    request from position 0), prompts in slices of W = 4, greedy: the
    serving chunk with the state's part of the recurrence on the Pallas
    kernel `ssm_slab_step` (interpreted; a state of 128 columns and eight
    heads of 16 channels, so that a head's tile is whole and a block of x
    a whole lane tile of the conv's row) against the chunk on `ssd_slab` — same tokens,
    log-probs and what every slot is left with; the kernel is what ran;
    and the counter of (slot, inner step) pairs with a lane equals the
    host's count of the same run."""
    from areal_tpu.ops.pallas import ssm_slab

    cfg = _cfg(ssm_state_dim=128, ssm_n_heads=8)
    assert ssm_slab.fits(
        cfg.ssm_n_heads, cfg.ssm_n_groups, cfg.ssm_head_dim, cfg.ssm_state_dim)
    params = _params(cfg, seed=8)
    lens, new, width = (21, 9, 17, 12, 5, 14), 10, 4
    prompts = _sequences(cfg, lens=lens, seed=13)
    g = GenerationHyperparameters(n=1, max_new_tokens=new, greedy=True)
    ragged, step = mamba.ssm_ragged, ssm_slab.ssm_slab_step
    traced = []
    monkeypatch.setattr(
        ssm_slab, "ssm_slab_step",
        lambda *a, **kw: traced.append(1) or step(*a, **kw))

    def roll(kernel):
        monkeypatch.setattr(
            mamba, "ssm_ragged",
            lambda *a, kernel_=kernel, **kw: ragged(*a, kernel=kernel_))
        eng = _engine(cfg, params, slots=4, prefill_chunk_tokens=width)
        out, pool, served = eng.serving_rollout(
            prompts, g, jax.random.PRNGKey(0))
        return out, pool, served, eng.last_pool_stats

    want, want_pool, want_served, _ = roll(False)
    assert not traced
    got, got_pool, got_served, stats = roll(True)
    # Once a layer a trace of the chunk's loop body.
    assert traced and len(traced) % cfg.n_ssm_layers == 0
    assert got_served == want_served
    assert max(len(v) for v in got_served.values()) == 2  # a reused slot
    for i in range(len(prompts)):
        np.testing.assert_array_equal(got[i][0], want[i][0])
        np.testing.assert_allclose(got[i][1], want[i][1], **TOL)
    for a, b in zip(got_pool.state + got_pool.conv,
                    want_pool.state + want_pool.conv):
        np.testing.assert_allclose(a, b, **TOL)
    # Every slot gets the lanes it wants here (4 slots x W = the stream), so
    # a request holds a lane for ceil(prompt / W) prefill steps and one
    # step a new token.
    assert stats["ssm_lanes_decode"] == len(lens) * new
    assert stats["ssm_lanes_prefill"] == sum(lens)
    assert stats["ssm_slot_steps_live"] == sum(
        -(-n // width) + new for n in lens)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "jnp"])
def test_the_chunk_counts_the_slab_lanes_its_terms_were_made_for(
        kernel, monkeypatch):
    """`ssm_lanes_made` of a toy chunk (six requests over four slots, W =
    4, the stream 16 lanes), a layer's inner step: the kernel makes the
    chunk's terms for the W lanes of each slot that holds a lane, the
    `jnp` form for every lane of the [4, W] slab whoever holds one."""
    cfg = _cfg(ssm_state_dim=128, ssm_n_heads=8)
    params = _params(cfg, seed=8)
    lens, new, width, slots = (21, 9, 17, 12, 5, 14), 10, 4, 4
    prompts = _sequences(cfg, lens=lens, seed=13)
    g = GenerationHyperparameters(n=1, max_new_tokens=new, greedy=True)
    monkeypatch.setattr(
        mamba, "slab_kernel_form", lambda cfg_, kernel_=None: kernel)
    eng = _engine(cfg, params, slots=slots, prefill_chunk_tokens=width)
    eng.serving_rollout(prompts, g, jax.random.PRNGKey(0))
    stats = eng.last_pool_stats
    live = sum(-(-n // width) + new for n in lens)
    assert stats["ssm_slot_steps_live"] == live
    lanes = stats["ssm_lanes_decode"] + stats["ssm_lanes_prefill"]
    assert lanes == len(lens) * new + sum(lens)
    if kernel:
        assert stats["ssm_lanes_made"] == width * live
    else:
        inner = eng.lanes_dispatched // (slots * width)
        assert eng.lanes_dispatched == inner * slots * width
        assert stats["ssm_lanes_made"] == inner * slots * width
    assert stats["ssm_lanes_made"] >= lanes


# ----------------------------------------------------------------- refusals


def test_speculation_episodes_and_the_replay_are_refused_by_name(cfg, params):
    eng = _engine(cfg, params)
    g = GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True)
    with pytest.raises(tfm.HybridLayoutError, match="speculative decoding"):
        eng.generate(
            _sample(cfg), MicroBatchSpec(),
            dataclasses.replace(g, spec_decode_k=2))
    with pytest.raises(tfm.HybridLayoutError, match="agent episodes"):
        eng.episode_start("e0", np.arange(5, dtype=np.int32), g)
    with pytest.raises(tfm.HybridLayoutError, match="resume replay"):
        eng._replay_tails(None, [0])
    # An interrupt does not park such a call: it drains, and says so.
    eng.interrupt()
    out = eng.generate(_sample(cfg), MicroBatchSpec(), g, inflight=True)
    eng.clear_interrupt()
    assert out is not None and not eng.interrupted
    assert eng.last_pool_stats["ssm_interrupts_drained"] >= 1
    assert "snapshot" in gen_mod._NO_STATE_SPEC


@pytest.mark.parametrize("mode", ["m2", "p2", "s2"])
def test_layouts_the_plan_cannot_run_are_refused_by_name(cfg, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.HybridLayoutError, match="data and fsdp"):
        sharding.attn_dispatch(mesh, cfg)
    sharding.attn_dispatch(mesh, tiny_config())  # every other model: fine


@pytest.mark.parametrize("mode", ["d2", "f2"])
def test_a_sharded_forward_equals_the_single_device_one(cfg, params, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    sharding.attn_dispatch(mesh, cfg)  # accepted
    assert sharding.check_divisibility(params, mesh) is None
    placed = sharding.shard_params(params, mesh)
    t = jnp.asarray(np.stack(_sequences(cfg, lens=(40,) * 4, seed=12)))
    got = _forward(
        placed, cfg,
        jax.device_put(t, sharding.named(mesh, sharding.batch_pspec())),
        jnp.ones_like(t))
    want = _forward(params, cfg, t, jnp.ones_like(t))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_the_other_plans_with_state_keep_their_refusals():
    """A one-branch pattern (`nemotron_h`) is refused with its message."""
    pattern = ModelConfig(
        n_layers=2, hidden_dim=32, n_q_heads=2, n_kv_heads=2, head_dim=16,
        intermediate_dim=64, vocab_size=64, layer_pattern="M*",
        ssm_n_heads=2, ssm_head_dim=16, ssm_state_dim=8, pos_emb="none")
    refusal = tfm.plan_refusal(pattern, serving=True)
    assert isinstance(refusal, tfm.HybridLayoutError)
    assert "one-branch layers" in str(refusal)


# --------------------------------------------------------- counts, programs


def test_flops_count_every_mixer_and_an_mlp_a_layer(cfg):
    h, di = cfg.hidden_dim, cfg.ssm_inner_dim
    ssm = h * cfg.ssm_in_dim + di * h + 2 * di * cfg.ssm_state_dim
    attn = h * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * h
    mlp = 3 * h * cfg.intermediate_dim
    assert monitor.matmul_params(cfg) == (
        4 * ssm + attn + 5 * mlp + h * cfg.vocab_size)


# sha256 of the StableHLO text of `decode_step_ragged_paged` over the dense
# and the MoE `tiny_config` at the parent commit (PR 52): the chunk walks
# `cfg.plan` now, and every plan without state lowers to the text it had.
_PARENT_SERVING_STEPS = {
    0: "091db48f5ea4806e",
    4: "d791f3aaaf0453f0",
}


@pytest.mark.parametrize("n_experts", sorted(_PARENT_SERVING_STEPS))
def test_the_dense_serving_step_lowers_to_the_parents_program(n_experts):
    c = tiny_config(n_experts=n_experts)
    shapes = jax.eval_shape(lambda: tfm.init_params(c, jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: tfm.init_paged_kv_cache(c, 16, 8))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = jax.jit(
        lambda p, tk, ps, pool, pt, ro: tfm.decode_step_ragged_paged(
            p, c, tk, ps, pool, pt, ro, paged_kernel=False)
    ).lower(shapes, i32(12), i32(12), pool, i32(4, 4), i32(12)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        _PARENT_SERVING_STEPS[n_experts])


# ------------------------- the gradient program's scan on the Pallas sweep

# The Mamba widths the sweep can cut (heads of 64 channels, a state of 128
# columns, chunks of 128, ONE group as published), the rest of the toy as
# it is.
_WIDE = dict(ssm_n_heads=8, ssm_head_dim=64, ssm_state_dim=128,
             ssm_n_groups=1, ssm_chunk=128)
# `_program_sha(_cfg(**_WIDE, ssm_n_groups=2), "gen")` as printed at the
# parent of PR 58 (6ead7dd): prefill + a decode step at those widths.
_PARENT_WIDE_GEN = (
    "39710ee420d2cef9222cc22aeec317b780e4dfa2609cac35b6085fbbc54cc0a4")


def test_the_train_step_on_the_forced_sweep_is_the_jnp_forms(monkeypatch):
    """The toy model's loss and the gradient of every leaf with the chunked
    scan on `ssd_chunk` against `ssd_chunked`, inside this file's fp32
    bounds (the case's body: `tests/test_ssd_chunk_kernel.py`)."""
    from tests.test_ssd_chunk_kernel import (
        train_step_on_the_sweep_is_the_jnp_forms,
    )

    cfg = _cfg(**_WIDE)
    train_step_on_the_sweep_is_the_jnp_forms(
        cfg, _params(cfg), monkeypatch, jit=True)


def test_prefill_keeps_the_parents_program_at_the_sweeps_widths():
    """Prefill reads the final state (`with_state`): at widths the sweep
    takes in the gradient program, prefill + a decode step lower to the
    text they lowered to at the parent of PR 58."""
    from tests.test_glm4_moe_lite import _program_sha

    assert _program_sha(
        _cfg(**dict(_WIDE, ssm_n_groups=2)), "gen") == _PARENT_WIDE_GEN


# ---------------------------------------------- the cell's window, rehearsed

# `granite4hm-serving-waves` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)

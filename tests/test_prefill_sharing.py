"""The static program's prefill in waves computes a group's prompt once
(`GeneratorEngine._prefill_distinct`): `generate()` tells `static_rollout`
which rows repeat, the distinct rows alone go through the waves and each
lands at every row of its group.  Held here, for a toy of every plan family,
to the program that prefills every row — and every batch that cannot share
(no repeat, a single prefill, a direct caller) to the parent's program."""

import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base import tracer
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import generator as generator_mod
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.models.config import ModelConfig, tiny_config

# sp = 256 (the longest passes the sala toy's dense_len); four prompts, so
# that their one wave is as wide as a wave of the sixteen rows: four.
LENS = (200, 70, 40, 9)
NEW = 6


def _family(name) -> ModelConfig:
    """The toy each family's own test file runs."""
    if name == "dense":
        return tiny_config()
    module = {
        "window_rings": "test_mellum",  # + an MoE with `expert_share`
        "short_conv_tails": "test_lfm2_moe",  # + an MoE with `expert_share`
        "mamba2_state": "test_nemotron_h",
        "mamba2_state_two_branch": "test_granite_hybrid",
        "gdn_state_96x192": "test_olmo_hybrid",
        "gdn_state_whole_tiles": "test_qwen3_next",
        "latent_rows": "test_glm4_moe_lite",
        "sparse_ck_lightning_state": "test_minicpm_sala",
    }[name]
    return importlib.import_module(f"tests.{module}")._cfg()


FAMILIES = (
    "dense", "window_rings", "short_conv_tails", "mamba2_state",
    "mamba2_state_two_branch", "gdn_state_96x192", "gdn_state_whole_tiles",
    "latent_rows", "sparse_ck_lightning_state",
)


def _params(cfg):
    """Random weights, every leaf moved off its initial zeros and ones."""
    from tests.test_layer_plan import _params

    return _params(cfg)


def _sample(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    toks = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]
    return toks, SequenceSample.from_default(
        ids=[str(i) for i in range(len(lens))], seqlens=list(lens),
        data={"packed_prompts": np.concatenate(toks)})


def _engine(cfg, slots=64, layout="d1"):
    pc = ParallelConfig.from_str(layout)
    return GeneratorEngine(
        cfg, _params(cfg), make_mesh(pc, jax.devices()[: pc.world_size]),
        eos_token_id=cfg.vocab_size, max_decode_batch=slots,
        donation_safe_swap=False)


def _generate(cfg, lens, n, share=True, **engine):
    """`generate()` over prompts of `lens`, `n` a group -> (the engine, the
    rollout, the cache each chunk's program left, each chunk's `src`).
    `share` False: every chunk's `src` withheld, the program that prefills
    every row."""
    eng = _engine(cfg, **engine)
    rollout, caches, srcs = eng.static_rollout, [], []

    def with_cache(prompts, g, key, src=None):
        srcs.append(src)
        *out, cache = rollout(
            prompts, g, key, with_cache=True, src=src if share else None)
        caches.append(cache)
        return out

    eng.static_rollout = with_cache
    _, sample = _sample(cfg, lens)
    out = eng.generate(
        sample, MicroBatchSpec(),
        GenerationHyperparameters(n=n, max_new_tokens=NEW), seed=3,
        inflight=False)
    return eng, out, caches, srcs


def _assert_same_rollout(shared, own, atol=1e-6):
    """`atol`: 1e-6 where both programs' waves are equally wide; the CPU's
    matmuls round a row by the rows beside it, so 1e-5 where they are
    not."""
    (_, a, caches_a, _), (_, b, caches_b, _) = shared, own
    np.testing.assert_array_equal(
        a.data["packed_input_ids"], b.data["packed_input_ids"])
    np.testing.assert_allclose(
        a.data["packed_logprobs"], b.data["packed_logprobs"], atol=atol)
    assert len(caches_a) == len(caches_b)
    for x, y in zip(jax.tree.leaves(caches_a), jax.tree.leaves(caches_b)):
        np.testing.assert_allclose(x, y, atol=atol)


def _responses(out, n):
    """[prompt][response] -> the sampled tokens."""
    ids, mask = out.data["packed_input_ids"], out.data["prompt_mask"]
    bounds = out.cu_seqlens("packed_input_ids")
    seqs = [ids[a:b][~mask[a:b]] for a, b in zip(bounds[:-1], bounds[1:])]
    return [seqs[i: i + n] for i in range(0, len(seqs), n)]


def _stats(eng):
    st = eng.last_pool_stats
    return st["prefill_rows"], st["prefill_rows_requested"]


def _src_of(eng):
    """The `src` entry of each compiled static program's key."""
    return [key[-1] for key in eng._gen_fns]


@pytest.fixture
def budget(monkeypatch):
    """Four rows of 256 slots a wave: sixteen rows go in four waves, their
    four distinct prompts in one."""
    monkeypatch.setattr(generator_mod, "PREFILL_WAVE_TOKENS", 1024)


# ------------------------------------------------------- every cache kind


@pytest.mark.parametrize("name", FAMILIES)
def test_a_group_is_prefilled_once_and_lands_at_every_row(name, budget):
    cfg = _family(name)
    shared = _generate(cfg, LENS, n=4)
    eng, out, caches, srcs = shared
    assert srcs == [[0] * 4 + [4] * 4 + [8] * 4 + [12] * 4]
    assert _src_of(eng) == [tuple(srcs[0])]
    assert _stats(eng) == (4, 16)
    populations = {
        f for f in ("k", "v", "state", "conv", "latent", "wk", "wv", "ck")
        if getattr(caches[0], f) is not None}
    assert populations >= {
        "dense": {"k", "v"},
        "window_rings": {"k", "v", "wk", "wv"},
        "short_conv_tails": {"k", "v", "conv"},
        "mamba2_state": {"k", "v", "state", "conv"},
        "mamba2_state_two_branch": {"k", "v", "state", "conv"},
        "gdn_state_96x192": {"k", "v", "state", "conv"},
        "gdn_state_whole_tiles": {"k", "v", "state", "conv"},
        "latent_rows": {"latent"},
        "sparse_ck_lightning_state": {"k", "v", "ck", "state"},
    }[name]
    own = _generate(cfg, LENS, n=4, share=False)
    assert _src_of(own[0]) == [None] and _stats(own[0]) == (16, 16)
    _assert_same_rollout(shared, own)
    # One prefill a group, and still four continuations.
    for group in _responses(out, 4):
        assert len({tuple(r.tolist()) for r in group}) > 1


# ------------------------------------------------------------- edge cases


def test_a_group_split_by_a_chunk_boundary_is_shared_within_each_chunk(
        budget):
    """Six slots, groups of four: chunks of 4 + 2, 2 + 4 rows."""
    cfg, lens = tiny_config(), (200, 180, 150)  # both chunks past the budget
    shared = _generate(cfg, lens, n=4, slots=6)
    eng, _, _, srcs = shared
    assert srcs == [[0, 0, 0, 0, 4, 4], [0, 0, 2, 2, 2, 2]]
    assert _src_of(eng) == [tuple(s) for s in srcs]
    assert _stats(eng) == (4, 12)
    own = _generate(cfg, lens, n=4, slots=6, share=False)
    _assert_same_rollout(shared, own, atol=1e-5)  # waves of 2 against 3


def test_more_distinct_rows_than_a_wave_holds_go_in_waves(monkeypatch):
    """Two rows a wave: four distinct prompts go in two waves."""
    monkeypatch.setattr(generator_mod, "PREFILL_WAVE_TOKENS", 512)
    cfg = tiny_config()
    shared = _generate(cfg, LENS, n=4)
    assert shared[0]._prefill_wave_rows(4, 256) == 2
    assert _stats(shared[0]) == (4, 16)
    _assert_same_rollout(shared, _generate(cfg, LENS, n=4, share=False))


def test_rows_added_to_reach_the_batch_sharding_are_their_own_source(budget):
    cfg = tiny_config()
    eng = _engine(cfg)
    assert eng._shared_rows(8, 256, [0, 0, 0, 3, 3, 3]) == (
        0, 0, 0, 3, 3, 3, 6, 7)
    # A mesh shards the batch axis a wave would slice: one prefill of all
    # eight rows (two of them pads), whatever repeats.
    eng, _, _, srcs = _generate(cfg, (9, 9), n=3, layout="d4")
    assert srcs == [[0, 0, 0, 3, 3, 3]] and eng.batch_shard == 4
    assert _src_of(eng) == [None] and _stats(eng) == (6, 6)


@pytest.mark.parametrize("src", [[0, 0, 0, 0], [1, 1, 2, 3]])
def test_a_source_row_carries_the_same_prompt_and_comes_first(src):
    cfg = tiny_config()
    toks, _ = _sample(cfg, (9, 9))
    with pytest.raises(ValueError, match="same prompt"):
        _engine(cfg).static_rollout(
            [toks[0], toks[0], toks[1], toks[1]],
            GenerationHyperparameters(n=1, max_new_tokens=NEW),
            jax.random.PRNGKey(0), src=src)


# As printed at the parent of PR 61 (23a2611) by this file's `_program_sha`
# for the same three calls: sha256 of the lowered `gen`, the results' names
# left out.
_PARENT_WAVES = "159158c36ec074cfd617a33f9c4232e52ed04638d083b30ff3a4103a29de9d45"
_PARENT_SINGLE = "2e4adf5b01e12ecc2920abe1eac0b47b967a10fd650a49a0daaff7c39e83f536"
_PARENT_KEY = (8, 128, 256, NEW, 0, False, 1.0, 0, 1.0, False, False)


def _program_sha(eng):
    (key, fn), = eng._gen_fns.items()
    b, sp = key[:2]
    text = fn.lower(
        eng.params, jax.ShapeDtypeStruct((b, sp), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32), jax.random.PRNGKey(0)).as_text()
    text = re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)
    return key, hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("call,budget_tokens,parent", [
    ("n=1", 512, _PARENT_WAVES),
    ("direct", 512, _PARENT_WAVES),
    ("under the budget", 48 * 1024, _PARENT_SINGLE),
])
def test_a_batch_that_cannot_share_traces_the_parents_program(
        monkeypatch, call, budget_tokens, parent):
    monkeypatch.setattr(generator_mod, "PREFILL_WAVE_TOKENS", budget_tokens)
    cfg = tiny_config()
    eng = _engine(cfg, slots=8)
    g = GenerationHyperparameters(n=1, max_new_tokens=NEW)
    if call == "direct":  # as `benchmark/references/minicpm_sala.py` calls
        toks, _ = _sample(cfg, (70,))
        eng.static_rollout(toks * 8, g, jax.random.PRNGKey(1))
    elif call == "n=1":  # eight distinct prompts, in waves of four
        _, sample = _sample(cfg, (70, 9, 40, 33, 21, 60, 5, 17))
        eng.generate(sample, MicroBatchSpec(), g, seed=3)
    else:  # two groups of four in ONE prefill
        _, sample = _sample(cfg, (70, 9))
        eng.generate(
            sample, MicroBatchSpec(),
            GenerationHyperparameters(n=4, max_new_tokens=NEW), seed=3)
    assert eng._prefill_wave_rows(8, 128) == (8 if parent == _PARENT_SINGLE else 4)
    assert _stats(eng) == (8, 8)
    key, sha = _program_sha(eng)
    assert key == (*_PARENT_KEY, None)
    assert sha == parent


# ------------------------------------------------------------ the counter


@pytest.mark.parametrize("n,rows", [(4, 2), (1, 8)])
def test_the_counter_says_how_many_rows_were_prefilled(
        tmp_path, budget, n, rows):
    tracer._reset_for_tests()
    tracer.configure(
        role="test", rank=0, dir=str(tmp_path), enabled=True, force=True)
    try:
        cfg = tiny_config()
        eng = _engine(cfg)
        before = (eng._m_prefill_rows.get(),
                  eng._m_prefill_rows_requested.get())
        _, sample = _sample(cfg, LENS[:2] if n == 4 else LENS + LENS)
        eng.generate(
            sample, MicroBatchSpec(),
            GenerationHyperparameters(n=n, max_new_tokens=NEW), seed=3)
        assert _stats(eng) == (rows, 8)
        assert (eng._m_prefill_rows.get() - before[0],
                eng._m_prefill_rows_requested.get() - before[1]) == (rows, 8)
        _, events = tracer.read_shard(tracer.flush())
        (chunk,) = [e for e in events if e["name"] == "gen_chunk"]
        assert (chunk["args"]["b"], chunk["args"]["sp"]) == (8, 256)
        assert (chunk["args"]["prefill_rows"],
                chunk["args"]["prefill_rows_requested"]) == (rows, 8)
    finally:
        tracer._reset_for_tests()

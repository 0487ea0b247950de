"""The static program's prefill computes a group's prompt once
(`GeneratorEngine._prefill_distinct`) where the prefill is what the call
spends — it goes in waves, or it fits one and the prompt bucket is longer
than the decode budget (`_shared_rows`): `generate()` tells `static_rollout`
which rows repeat, the distinct rows alone are prefilled and each lands at
every row of its group.  Held here, for a toy of every plan family, to the
program that prefills every row — and every batch that cannot share (no
repeat, a decode budget as long as the prompt bucket, a mesh, a direct
caller) to the parent's program."""

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
GROUPS = [r - r % 4 for r in range(16)]  # each of LENS four times: `src`


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


def _generate(cfg, lens, n, share=True, new=NEW, **engine):
    """`generate()` over prompts of `lens`, `n` a group, `new` tokens a row
    -> (the engine, the rollout, the cache each chunk's program left, each
    chunk's `src`).  `share` False: every chunk's `src` withheld, the
    program that prefills every row."""
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
        GenerationHyperparameters(n=n, max_new_tokens=new), seed=3,
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
    assert srcs == [GROUPS] and _src_of(eng) == [tuple(GROUPS)]
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


# ------------------------------- under the budget: the batch's own shape


@pytest.mark.parametrize("name", ["dense", "mamba2_state"])
def test_one_prefill_longer_than_the_decode_budget_is_shared(name):
    """Sixteen rows of 256 slots fit the budget as it stands (48 k): ONE
    prefill, of the four distinct rows, because 256 slots of prompt stand
    against 6 new tokens."""
    cfg = _family(name)
    shared = _generate(cfg, LENS, n=4)
    eng = shared[0]
    assert eng._prefill_wave_rows(16, 256) == 16
    assert _src_of(eng) == [tuple(GROUPS)] and _stats(eng) == (4, 16)
    own = _generate(cfg, LENS, n=4, share=False)
    assert _src_of(own[0]) == [None] and _stats(own[0]) == (16, 16)
    _assert_same_rollout(shared, own, atol=1e-5)  # a prefill of 4 against 16
    for group in _responses(shared[1], 4):
        assert len({tuple(r.tolist()) for r in group}) > 1


def test_one_prefill_no_longer_than_the_decode_budget_is_not_shared():
    """The same batch with 256 new tokens a row: the decode loop is the
    call, and the program is the one it had."""
    eng, _, _, srcs = _generate(tiny_config(), LENS, n=4, new=256)
    assert srcs == [GROUPS]
    assert _src_of(eng) == [None] and _stats(eng) == (16, 16)
    assert eng._shared_rows(16, 256, 255, GROUPS) == tuple(GROUPS)
    assert eng._shared_rows(16, 256, 256, GROUPS) is None


# ----------------------------------------------- the benchmark's own cells

# Every cell of BENCHMARK.json on the static program, and why its generate
# call shares its prefill (None: it does not, and keeps the parent's key).
# The four cells in waves always did (PR 61); `q1p5b-train-longprompt` fits
# one prefill of [16, 2560] against 64 new tokens; the other seven decode at
# least as long as their prompt bucket, `q7b-realloc-4chip` on a mesh.
_CELL_SHARES = {
    "q1p5b-decode-static": None,
    "q1p5b-train-longprompt": "prompt past the decode budget",
    "q7b-realloc-4chip": None,
    "olmoe-decode-tail": None,
    "q3next-rollout64-512": None,
    "glm47f-rollout64-1k": None,
    "nemo3n-rollout64-512": None,
    "mellum2-coderl32-4k": "waves",
    "lfm2-ctxrl32-4k": "waves",
    "sala-docrl8-longctx": "waves",
    "olmoh-rollout64-512": None,
    "dots3n-docrl8-longctx": "waves",
    # The block loop (`engines/block_diffusion.py`) never asks the rule: it
    # prefills every row; by its shape the rule would not share either.
    "sdar-rollout64-512": None,
}


def _cell_shape(name):
    """(rows, prompt bucket, decode budget, devices of the generator's
    mesh, the group) of a cell, from the benchmark's own files."""
    from benchmark import files
    from benchmark.traffic.math_prompts import quantile_lengths

    _, config, traffic = files.load_cell(name)
    layout = config["benchmark"]["layout"]
    mesh = ParallelConfig.from_str(
        layout["gen_parallel"] or layout["actor_parallel"])
    longest = max(quantile_lengths(traffic["prompt_len"], traffic["n_prompts"]))
    return (
        traffic["n_prompts"] * traffic["group"],
        generator_mod.bucket_len(longest), traffic["max_new_tokens"],
        mesh.world_size, traffic["group"])


def test_the_table_names_every_static_cell():
    from benchmark import files

    static = {
        w["name"] for w in files.benchmark_json()["workloads"]
        if files.load_cell(w["name"])[0]["route"] == "static"}
    assert static == set(_CELL_SHARES)


@pytest.mark.parametrize("name", sorted(_CELL_SHARES))
def test_which_cells_share_their_prefill(name):
    b, sp, new, devices, group = _cell_shape(name)
    why = _CELL_SHARES[name]

    class Mesh:
        size = devices

    eng = object.__new__(GeneratorEngine)  # the rule reads its mesh alone
    eng.mesh = Mesh()
    src = [r - r % group for r in range(b)]
    # A caller that names no repeats keeps the parent's program, first of
    # all: the references' own generator calls are long prompts, few tokens.
    assert eng._shared_rows(b, sp, new, None) is None
    assert eng._shared_rows(b, sp, new, list(range(b))) is None
    assert eng._shared_rows(b, sp, new, src) == (tuple(src) if why else None)
    in_waves = eng._prefill_wave_rows(b, sp) < b
    assert in_waves == (why == "waves")
    assert in_waves or bool(why) == (sp > new and devices == 1)
    if name == "q1p5b-train-longprompt":
        assert (b, sp, new, len(set(src))) == (16, 2560, 64, 4)


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
    assert eng._shared_rows(8, 256, NEW, [0, 0, 0, 3, 3, 3]) == (
        0, 0, 0, 3, 3, 3, 6, 7)
    # A mesh shards the batch axis a wave, and the landing, would slice: one
    # prefill of all eight rows (two of them pads), whatever repeats and
    # however long the prompt bucket stands against the decode budget.
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
# for the same calls: sha256 of the lowered `gen`, the results' names left
# out.  `_PARENT_SINGLE_128`: two groups of four in one prefill with 128 new
# tokens, as printed at the parent of PR 67 (4cfbc9f), where the batch with
# 6 new tokens still traced `_PARENT_SINGLE` and now shares its prefill.
_PARENT_WAVES = "159158c36ec074cfd617a33f9c4232e52ed04638d083b30ff3a4103a29de9d45"
_PARENT_SINGLE = "2e4adf5b01e12ecc2920abe1eac0b47b967a10fd650a49a0daaff7c39e83f536"
_PARENT_SINGLE_128 = "81b066ddf376de66ce935bdf88b35029f4e92c76f8415939971b0192716f1641"
_PARENT_KEY = (8, 128, 256, NEW, 0, False, 1.0, 0, 1.0, False, False)


def _program_sha(eng):
    (key, fn), = eng._gen_fns.items()
    b, sp = key[:2]
    text = fn.lower(
        eng.params, jax.ShapeDtypeStruct((b, sp), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32), jax.random.PRNGKey(0)).as_text()
    text = re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)
    return key, hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("call,budget_tokens,new,parent", [
    ("n=1", 512, NEW, _PARENT_WAVES),
    ("direct", 512, NEW, _PARENT_WAVES),
    # Under the budget.  Two groups of four in ONE prefill keep the parent's
    # program where the decode budget is as long as the prompt bucket (with
    # 6 new tokens, PR 61's case, the batch shares since PR 67: above) ...
    ("groups", 48 * 1024, 128, _PARENT_SINGLE_128),
    # ... and a prompt bucket past the decode budget keeps it where no row
    # repeats or the caller names none (the references' generator checks).
    ("n=1", 48 * 1024, NEW, _PARENT_SINGLE),
    ("direct", 48 * 1024, NEW, _PARENT_SINGLE),
])
def test_a_batch_that_cannot_share_traces_the_parents_program(
        monkeypatch, call, budget_tokens, new, parent):
    monkeypatch.setattr(generator_mod, "PREFILL_WAVE_TOKENS", budget_tokens)
    cfg = tiny_config()
    eng = _engine(cfg, slots=8)
    g = GenerationHyperparameters(n=1, max_new_tokens=new)
    if call == "direct":  # as `benchmark/references/minicpm_sala.py` calls
        toks, _ = _sample(cfg, (70,))
        eng.static_rollout(toks * 8, g, jax.random.PRNGKey(1))
    elif call == "n=1":  # eight distinct prompts (in waves of four)
        _, sample = _sample(cfg, (70, 9, 40, 33, 21, 60, 5, 17))
        eng.generate(sample, MicroBatchSpec(), g, seed=3)
    else:  # two groups of four in ONE prefill
        _, sample = _sample(cfg, (70, 9))
        eng.generate(
            sample, MicroBatchSpec(),
            GenerationHyperparameters(n=4, max_new_tokens=new), seed=3)
    assert eng._prefill_wave_rows(8, 128) == (4 if parent == _PARENT_WAVES else 8)
    assert _stats(eng) == (8, 8)
    key, sha = _program_sha(eng)
    assert key == (*_PARENT_KEY[:3], new, *_PARENT_KEY[4:], None)
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

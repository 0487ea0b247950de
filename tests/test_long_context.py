"""Long-context operation at reference scale (≥16k tokens).

The reference's flagship config decodes up to 27,648 new tokens with
max_tokens_per_mb=30720 (examples/configs/7B-distill/
ppo-7B-distill-gpus-128.yaml:58-70).  These tests drive the same
machinery — serving-plane page mapping under an 8k prompt, token-budget
micro-batching at 16k tokens per microbatch, ring attention over long
sharded rows — on the CPU cluster; nothing here measures the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    FinetuneSpec,
    GenerationHyperparameters,
    OptimizerConfig,
)
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.engines.train import TrainEngine
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import functional as F

EOS = 7


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return tfm.init_params(cfg, jax.random.PRNGKey(3))


def test_generate_from_8k_prompt(cfg, params, rng):
    """Long-context generation through the serving plane: an 8k-token
    prompt is consumed in W-token slices into 64 pool pages, then decode
    maps a further page; the response must extend the full prompt with
    aligned logprobs.  (The single-core CI budget caps this at 8k.)"""
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=EOS, max_decode_batch=1
    )
    plen = 8150
    toks = rng.integers(8, cfg.vocab_size, size=plen).astype(np.int32)
    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=["long0"],
        seqlens={"packed_prompts": [[plen]]},
        data={"packed_prompts": toks},
    )
    g = GenerationHyperparameters(
        n=1, max_new_tokens=24, min_new_tokens=24, greedy=True
    )
    out = eng.generate(sample, MicroBatchSpec(), g, inflight=True)
    L = out.seqlens["packed_input_ids"][0][0]
    assert L == plen + 24
    got = np.asarray(out.data["packed_input_ids"])
    np.testing.assert_array_equal(got[:plen], toks)
    # Behavior logprobs cover exactly the generated span.
    lp = np.asarray(out.data["packed_logprobs"])
    assert len(lp) == L - 1
    assert np.all(lp[plen - 1 : plen - 1 + 24] <= 0.0)


def _packed(rng, cfg, lens):
    toks = rng.integers(0, cfg.vocab_size, size=sum(lens)).astype(np.int32)
    return SequenceSample(
        keys={"packed_input_ids"},
        ids=[f"r{i}" for i in range(len(lens))],
        seqlens={"packed_input_ids": [[l] for l in lens]},
        data={"packed_input_ids": toks},
    )


def test_microbatch_split_at_reference_budgets(cfg, rng):
    """Token-budget micro-batching at the reference's long-context
    budgets (max_tokens_per_mb=30720, 27,648-token responses): the FFD
    splitter must pack 16k of mixed rows into one mb, admit one 27,648-
    token row under the 30,720 budget, and never exceed the cap."""
    # 8x2048 under 16384 -> exactly one microbatch.
    groups = _packed(rng, cfg, [2048] * 8).split_groups(
        MicroBatchSpec(max_tokens_per_mb=16384)
    )
    assert len(groups) == 1 and sorted(groups[0]) == list(range(8))
    # One reference-flagship row fits the flagship budget.
    groups = _packed(rng, cfg, [27648, 27648]).split_groups(
        MicroBatchSpec(max_tokens_per_mb=30720)
    )
    assert len(groups) == 2  # 2x27648 > 30720: one row per mb
    # Mixed long rows: every mb respects the cap, nothing is dropped.
    lens = [27648, 16384, 8192, 8192, 4096, 2048, 1024, 512]
    sample = _packed(rng, cfg, lens)
    groups = sample.split_groups(MicroBatchSpec(max_tokens_per_mb=30720))
    seen = sorted(i for g in groups for i in g)
    assert seen == list(range(len(lens)))
    for g in groups:
        assert sum(lens[i] for i in g) <= 30720


@pytest.mark.slow
def test_train_long_rows_one_microbatch(cfg, params, rng):
    """Device-side packing: 4x1024-token rows under a 4096-token budget
    run as ONE jitted microbatch (the 16k/30720 equivalents differ only
    in the splitter input, pinned above — a 16k CPU step blows the
    single-core CI budget)."""
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    engine = TrainEngine(
        cfg, params, mesh,
        optimizer_config=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 8, 8),
    )
    lens = [1024] * 4
    toks = rng.integers(0, cfg.vocab_size, size=sum(lens)).astype(np.int32)
    pmask = np.zeros(sum(lens), bool)
    off = 0
    for l in lens:
        pmask[off : off + 4] = True
        off += l
    sample = SequenceSample(
        keys={"packed_input_ids", "prompt_mask"},
        ids=[f"r{i}" for i in range(len(lens))],
        seqlens={
            "packed_input_ids": [[l] for l in lens],
            "prompt_mask": [[l] for l in lens],
        },
        data={"packed_input_ids": toks, "prompt_mask": pmask},
    )
    stats = engine.train_batch(
        sample,
        MicroBatchSpec(max_tokens_per_mb=4096),
        loss_fn=F.sft_loss,
        loss_weight_fn=F.sft_label_count,
        token_key="packed_input_ids",
        extra_keys=("prompt_mask",),
    )
    assert stats["n_micro_batches"] == 1.0
    assert np.isfinite(stats["loss"])


def test_ring_attention_8k_row(rng):
    """Ring attention (context parallelism) on one 8192-token segment
    spanning both seq shards — the mechanism that lets a single sequence
    span chips at 27k+ tokens — must match dense attention at length."""
    from areal_tpu.ops.attention import packed_attention_reference
    from areal_tpu.ops.ring_attention import ring_packed_attention

    pc = ParallelConfig.from_str("d1s2")
    mesh = make_mesh(pc, jax.devices()[:2])
    b, s, h, d = 1, 8192, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    seg = jnp.ones((b, s), jnp.int32)
    want = packed_attention_reference(q, k, v, seg, causal=True)
    got = jax.jit(
        lambda q, k, v, seg: ring_packed_attention(q, k, v, seg, mesh)
    )(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4
    )

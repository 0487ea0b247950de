"""Sharded-execution parity: the same forward pass, sharded over an 8-device
mesh (dp×fsdp×tp), must match single-device numerics.

Models the reference's distributed parity tests (tests/model/
test_distributed_load_hf.py, tests/comm/*) on the JAX fake cluster.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.parallel import sharding


@pytest.fixture(scope="module")
def tiny():
    return tiny_config()


@pytest.fixture(scope="module")
def tiny_params(tiny):
    return tfm.init_params(tiny, jax.random.PRNGKey(0))


def _batch(rng, cfg, b=8, s=32):
    tokens = rng.integers(0, cfg.vocab_size, size=(b, s)).astype(np.int32)
    seg = np.ones((b, s), dtype=np.int32)
    seg[:, s - 4 :] = 0  # little padding tail
    return jnp.asarray(tokens), jnp.asarray(seg)


@pytest.mark.parametrize("mode", ["d8", "d2f2m2", "d1f4m2", "d2f1m2s2"])
def test_sharded_forward_matches_single_device(mode, tiny, tiny_params, rng):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    tokens, seg = _batch(rng, tiny)

    expect = tfm.forward(tiny_params, tiny, tokens, seg)

    assert sharding.check_divisibility(tiny_params, mesh) is None
    p_sharded = sharding.shard_params(tiny_params, mesh)
    tok_sh = jax.device_put(
        tokens, sharding.named(mesh, sharding.batch_pspec())
    )
    seg_sh = jax.device_put(seg, sharding.named(mesh, sharding.batch_pspec()))

    @jax.jit
    def fwd(p, t, s):
        return tfm.forward(p, tiny, t, s)

    got = fwd(p_sharded, tok_sh, seg_sh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )


def test_param_pspecs_cover_all_leaves(tiny, tiny_params):
    specs = sharding.param_pspecs(tiny_params)
    flat_p = jax.tree_util.tree_leaves(tiny_params)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim


def test_moe_param_rules():
    cfg = tiny_config(n_experts=4)
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    specs = sharding.param_pspecs(params)
    assert specs["blocks"]["wg"] == P("pipe", "fsdp", None, "model")
    assert specs["blocks"]["router"] == P("pipe", "fsdp", None)


def test_critic_sharded(rng):
    cfg = tiny_config(is_critic=True)
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    pc = ParallelConfig.from_str("d2f2m2")
    mesh = make_mesh(pc)
    tokens, seg = _batch(rng, cfg)
    expect = tfm.forward(params, cfg, tokens, seg)
    p_sh = sharding.shard_params(params, mesh)
    got = jax.jit(lambda p, t, s: tfm.forward(p, cfg, t, s))(
        p_sh,
        jax.device_put(tokens, sharding.named(mesh, sharding.batch_pspec())),
        jax.device_put(seg, sharding.named(mesh, sharding.batch_pspec())),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-4, atol=2e-4
    )


# ------------------ the log-prob head is vocabulary-parallel (PR 33) --------
#
# Under a mesh whose parameter-sharding axes (model x fsdp) divide V,
# `fused_next_token_logprobs` keeps a chunk's logits [chunk, V / (m f)] a
# chip.  Left to the stored layout (D over fsdp) the partitioner all-reduces
# the fp32 [chunk, V] logits themselves, every chunk, forward and recomputed.

_HEAD = dict(d=32, v=2048, b=4, s=96, chunk=48)  # 8 chunks; no two sizes meet
_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)(?:-start)?\("
)


def _logit_block_collectives(hlo_text, chunk=_HEAD["chunk"], v=_HEAD["v"]):
    """Collectives of a compiled program whose result has a chunk-sized
    dimension TOGETHER with a vocabulary-sized one (V or a shard of it) —
    a [chunk, V...] block of logits crossing the chips.  The head's own
    [D, V] re-layout has no chunk-sized dimension and is allowed."""
    vocab_sized = {v // k for k in (1, 2, 4, 8)}
    found = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE.search(line)
        if not m:
            continue
        for dims in re.findall(r"\[([\d,]+)\]", m.group(1)):
            dims = {int(n) for n in dims.split(",")}
            if chunk in dims and dims & vocab_sized:
                found.append(line.strip()[:120])
    return found


def _head_case(mode, tied):
    """x, the head's stored weight and the labels, placed as the engines
    place them under `mode`; `loss(x, w, mesh)` -> (sum, per-token lp)."""
    from areal_tpu.ops.functional import fused_next_token_logprobs

    h = _HEAD
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(h["b"], h["s"], h["d"])), jnp.float32)
    # Stored layouts: lm_head [D, V], or the tied embedding [V, D].
    shape = (h["v"], h["d"]) if tied else (h["d"], h["v"])
    w = jnp.asarray(0.2 * rng.normal(size=shape), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, h["v"], (h["b"], h["s"])), jnp.int32)
    seg = np.ones((h["b"], h["s"]), np.int32)
    seg[:, h["s"] - 5:] = 0
    seg = jnp.asarray(seg)

    def loss(x, w, mesh):
        lp = fused_next_token_logprobs(
            x, w.T if tied else w, tokens, seg, h["chunk"], mesh
        )
        return lp.sum(), lp

    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    spec = sharding._TOP_RULES["embed"] if tied else sharding.HEAD_STORED
    placed = (
        jax.device_put(x, sharding.named(mesh, sharding.act_pspec())),
        jax.device_put(w, sharding.named(mesh, spec)),
    )
    return loss, (x, w), placed, mesh


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("mode", ["f4", "f2m2", "d2f2", "s2f2"])
def test_vocab_parallel_head_matches_one_device(mode, tied):
    """Value and gradients (dx, dhead) of the sharded head equal the
    single-device function's, and no [chunk, V...] block is a collective's
    operand in the compiled gradient program."""
    loss, plain, placed, mesh = _head_case(mode, tied)

    def grad(m):
        return jax.jit(jax.value_and_grad(
            lambda x, w: loss(x, w, m), (0, 1), has_aux=True
        ))

    (_, lp0), (dx0, dw0) = grad(None)(*plain)
    sharded = grad(mesh)
    (_, lp), (dx, dw) = sharded(*placed)
    for got, want in ((lp, lp0), (dx, dx0), (dw, dw0)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
    text = sharded.lower(*placed).compile().as_text()
    assert _logit_block_collectives(text) == []
    ways = mesh.shape["model"] * mesh.shape["fsdp"]
    assert sharding.head_vocab_shards(mesh, _HEAD["v"]) == ways


def test_the_stored_layout_all_reduces_the_logit_block():
    """The control: the same inputs under f4 with no mesh handed down (the
    parent's program) all-reduce f32[chunk, V], and the detector above
    sees it."""
    loss, _, placed, _ = _head_case("f4", tied=False)
    f = jax.jit(jax.grad(lambda x, w: loss(x, w, None)[0], (0, 1)))
    found = _logit_block_collectives(f.lower(*placed).compile().as_text())
    assert found and all("all-reduce" in line for line in found), found


# StableHLO of the train gradient program on a ONE-device mesh, sha256 of
# `lower(...).as_text()` at the parent of PR 33 (commit ee14960; jax 0.9.0).
# A product of model x fsdp of 1 adds no constraint, so every one-chip
# program is the parent's, text for text.  To regenerate after a change that
# is MEANT to alter these programs: print `_grad_program_sha(...)` below.
_PARENT_GRAD_PROGRAM = {
    "dense": "3e3e8efb30d0be9baa2452ede64ea14e399842e069b47d159d7d37d439fc3c0b",
    "olmoe": "be2f3b33bd802599a5f0b79cb67f5a6665a033a291e48c4b344ed24fde7bb7c1",
}


def _toy(name):
    if name == "dense":
        return tiny_config()
    import dataclasses

    from areal_tpu.models.hf import registry
    from tests.test_olmoe import HF_TOY

    cfg = registry.HF_FAMILIES["olmoe"].config_from_hf(HF_TOY)
    return dataclasses.replace(cfg, param_dtype="float32")


def _train_engine(cfg, mode):
    from areal_tpu.api.model_api import FinetuneSpec
    from areal_tpu.engines.train import TrainEngine

    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 8, 8))


def _grad_program_sha(cfg):
    import hashlib

    from areal_tpu.ops import functional as F

    engine = _train_engine(cfg, "d1")
    grad_fn, _ = engine._get_grad_fn(F.sft_loss)
    ints = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    batch = {
        "tokens": ints, "segment_ids": ints, "positions": ints,
        "prompt_mask": jax.ShapeDtypeStruct((2, 128), jnp.bool_),
    }
    text = grad_fn.lower(
        engine.params, batch, jax.ShapeDtypeStruct((), jnp.float32)
    ).as_text()
    assert "stablehlo.dot_general" in text
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", ["dense", "olmoe"])
def test_one_device_grad_program_is_the_parents(name):
    assert _grad_program_sha(_toy(name)) == _PARENT_GRAD_PROGRAM[name]


@pytest.mark.parametrize(
    "mode,vocab,shards", [("d1", 512, 1), ("f4", 512, 4), ("f4", 510, 1)],
    ids=["one-device", "f4", "f4-indivisible"],
)
def test_head_counter_says_how_the_vocabulary_was_split(
        mode, vocab, shards, rng):
    """The train stats carry vocab_shards: 1 on one device, 4 under f4,
    and 1 — the stored layout, no error — where V does not divide by
    model x fsdp.  (The tracer's `head` counter track, which nobody
    read, went in PR 36.)"""
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.ops import functional as F
    from tests import fixtures

    engine = _train_engine(tiny_config(vocab_size=vocab), mode)
    assert engine.head_vocab_shards == shards
    sample = fixtures.random_sample(
        rng, ids=list("abcdefgh"), keys=("packed_input_ids", "prompt_mask")
    )
    sample.seqlens["prompt_mask"] = sample.seqlens["packed_input_ids"]
    sample.data["prompt_mask"] = np.zeros(
        len(sample.data["packed_input_ids"]), bool
    )
    stats = engine.train_batch(
        sample, MicroBatchSpec(), loss_fn=F.sft_loss,
        loss_weight_fn=F.sft_label_count, extra_keys=("prompt_mask",),
    )
    assert stats["head/vocab_shards"] == shards
    assert np.isfinite(stats["loss"]) and stats["grad_norm"] > 0

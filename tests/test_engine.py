"""Engine + packing tests, and the SFT end-to-end minimum slice.

Models the reference's tests/experiments/test_sft.py: a full train loop on
the CPU fake cluster, loss must decrease; plus packing invariants.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import FinetuneSpec, Model, OptimizerConfig, make_interface
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines import packing
from areal_tpu.engines.train import TrainEngine, make_lr_schedule
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.ops import functional as F
from tests import fixtures

import areal_tpu.interfaces.sft  # noqa: F401  (registers "sft")


class TestPacking:
    def test_roundtrip(self, rng):
        sample = fixtures.random_sample(rng, ids=[f"s{i}" for i in range(7)])
        pk = packing.pack_sample(sample, "packed_input_ids", n_rows_multiple=4)
        assert pk.n_rows % 4 == 0
        # Unpack the packed tokens; must equal original 1D data.
        got = pk.unpack(pk.arrays["tokens"])
        np.testing.assert_array_equal(got, sample.data["packed_input_ids"])

    def test_segment_ids_and_positions(self, rng):
        sample = fixtures.random_sample(rng, ids=["a", "b", "c"])
        pk = packing.pack_sample(sample, "packed_input_ids")
        seg, pos = pk.arrays["segment_ids"], pk.arrays["positions"]
        for (r, s, l) in pk.seq_map:
            assert (seg[r, s : s + l] == seg[r, s]).all()
            np.testing.assert_array_equal(pos[r, s : s + l], np.arange(l))
        # Padding has segment 0.
        total = sum(l for (_, _, l) in pk.seq_map)
        assert (seg > 0).sum() == total

    def test_bucket_len(self):
        assert packing.bucket_len(1) == 128
        assert packing.bucket_len(128) == 128
        assert packing.bucket_len(129) == 256
        assert packing.bucket_len(1000) == 1024
        # Training rows keep coarse (1024) buckets: every new shape costs
        # a full fwd+bwd compile.
        assert packing.bucket_len(1025) == 2048
        assert packing.bucket_len(30000) == 30720
        # Decode cache windows bucket finer (256 above 1024): every decode
        # step streams the whole window.
        assert packing.decode_bucket_len(1025) == 1280
        assert packing.decode_bucket_len(1153) == 1280
        assert packing.decode_bucket_len(512) == 512

    def test_misaligned_extra_key_rejected(self, rng):
        sample = fixtures.random_sample(rng, ids=["a", "b"])
        other = fixtures.random_sample(rng, ids=["a", "b"], keys=("m",))
        sample.update_(other)
        with pytest.raises(ValueError):
            packing.pack_sample(sample, "packed_input_ids", extra_keys=("m",))


class TestSchedules:
    def test_warmup_cosine(self):
        cfg = OptimizerConfig(
            lr=1e-3, lr_scheduler_type="cosine", warmup_steps_proportion=0.1,
            min_lr_ratio=0.1,
        )
        sched = make_lr_schedule(cfg, 100)
        assert float(sched(0)) == 0.0
        assert abs(float(sched(10)) - 1e-3) < 1e-9
        assert float(sched(100)) < 1.2e-4


def _make_sft_model(mesh, ftspec, lr=1e-3):
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    engine = TrainEngine(
        cfg,
        params,
        mesh,
        optimizer_config=OptimizerConfig(lr=lr, warmup_steps_proportion=0.0),
        ftspec=ftspec,
    )
    return Model(
        name="default", engine=engine, tokenizer=fixtures.make_tokenizer(),
        config=cfg,
    )


@pytest.mark.parametrize("mode", ["d1", "d2f2m2"])
def test_sft_e2e_loss_decreases(mode, tmp_path):
    """The minimum end-to-end slice: dataset -> dataloader -> interface ->
    engine -> loss decreases -> save HF checkpoint."""
    from areal_tpu.data.datasets import PackedDataLoader, PromptAnswerDataset

    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    tok = fixtures.make_tokenizer()
    ds = PromptAnswerDataset(
        seed=1, dp_rank=0, world_size=1, tokenizer=tok, max_length=128,
        dataset_builder=lambda: fixtures.build_sft_rows(16, seed=5),
    )
    dl = PackedDataLoader(ds, batch_size=8)
    ftspec = FinetuneSpec(
        total_train_epochs=4, dataset_size=len(ds), train_batch_size=8
    )
    model = _make_sft_model(mesh, ftspec)
    interface = make_interface("sft")

    losses = []
    mb_spec = MicroBatchSpec(n_mbs=2)
    for _ in range(4):
        for batch in dl:
            stats = interface.train_step(model, batch, mb_spec)
            losses.append(stats["nll"])
    assert losses[-1] < losses[0] * 0.9, losses

    # ZeRO-1: Adam's two moments are sharded like their params on every
    # device, after real train steps — never a full replica per chip.
    def dev_bytes(tree, dev):
        return sum(
            s.data.nbytes
            for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards
            if s.device == dev
        )

    eng = model.engine
    for dev in mesh.devices.flat:
        p, o = dev_bytes(eng.params, dev), dev_bytes(eng.opt_state, dev)
        assert 2 * p <= o <= 2 * p + 64, (mode, dev, p, o)

    # Evaluate + save.
    ev = interface.evaluate(model, [next(iter(dl))])
    assert "eval_nll" in ev
    interface.save(model, str(tmp_path / "ckpt"))
    from areal_tpu.models.hf import registry as hf

    cfg2, params2 = hf.load_hf_checkpoint(str(tmp_path / "ckpt"), dtype=jnp.float32)
    assert cfg2.n_layers == model.config.n_layers


def test_train_context_parallel_matches_single_device():
    """Ring-attention CP (mesh seq axis) must give the same training step as
    the unsharded engine — the long-context path is numerics-identical."""
    rng = np.random.default_rng(3)
    cfg = tiny_config()
    sample = fixtures.random_sample(
        rng, ids=[f"s{i}" for i in range(8)], keys=("packed_input_ids",),
        max_len=48,
    )
    masks = []
    for sl in sample.seqlens["packed_input_ids"]:
        m = np.zeros(sl[0], dtype=bool)
        m[:2] = True
        masks.append(m)
    sample.update_(
        SequenceSample(
            keys={"prompt_mask"},
            ids=sample.ids,
            seqlens={"prompt_mask": [list(s) for s in sample.seqlens["packed_input_ids"]]},
            data={"prompt_mask": np.concatenate(masks)},
        )
    )

    def run(mode, n_dev):
        """One grad evaluation on the given mesh -> (loss, grad leaves)."""
        from areal_tpu.engines import packing
        from areal_tpu.ops import functional as F_

        pc = ParallelConfig.from_str(mode)
        mesh = make_mesh(pc, jax.devices()[:n_dev])
        params = tfm.init_params(cfg, jax.random.PRNGKey(7))
        eng = TrainEngine(
            cfg, params, mesh,
            optimizer_config=OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0),
            ftspec=FinetuneSpec(1, 8, 8),
        )
        mb = sample.split(MicroBatchSpec(n_mbs=1))[0]
        pk = packing.pack_sample(
            mb, "packed_input_ids", extra_keys=("prompt_mask",),
            n_rows_multiple=eng.batch_shard,
        )
        batch = eng._device_batch(pk.arrays)
        grads, loss, _ = eng._get_grad_fn(F_.sft_loss)[0](
            eng.params, batch, jnp.float32(1.0)
        )
        return float(loss), jax.tree.map(np.asarray, jax.tree.leaves(grads))

    loss0, base = run("d1", 1)
    loss1, cp = run("d1s4", 4)
    loss2, cp_tp = run("d1s2m2", 4)
    assert abs(loss1 - loss0) < 1e-2 * max(1.0, abs(loss0))
    assert abs(loss2 - loss0) < 1e-2 * max(1.0, abs(loss0))
    for a, b in zip(base, cp):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-4)
    for a, b in zip(base, cp_tp):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-4)


def test_train_batch_mb_invariance():
    """Gradient must not depend on micro-batch split: 1 mb vs 4 mbs give the
    same updated params (token-weighted normalization)."""
    rng = np.random.default_rng(0)
    pc = ParallelConfig.from_str("d1")
    mesh = make_mesh(pc, jax.devices()[:1])
    cfg = tiny_config()

    def make_engine():
        params = tfm.init_params(cfg, jax.random.PRNGKey(7))
        return TrainEngine(
            cfg, params, mesh,
            optimizer_config=OptimizerConfig(
                lr=1e-2, warmup_steps_proportion=0.0, gradient_clipping=0.0,
                weight_decay=0.0,
            ),
            ftspec=FinetuneSpec(1, 8, 8),
        )

    sample = fixtures.random_sample(
        rng, ids=[f"s{i}" for i in range(8)], keys=("packed_input_ids",),
        max_len=24,
    )
    # prompt_mask: first 2 tokens of each seq are prompt.
    masks = []
    for sl in sample.seqlens["packed_input_ids"]:
        m = np.zeros(sl[0], dtype=bool)
        m[:2] = True
        masks.append(m)
    sample.update_(
        SequenceSample(
            keys={"prompt_mask"},
            ids=sample.ids,
            seqlens={"prompt_mask": [list(s) for s in sample.seqlens["packed_input_ids"]]},
            data={"prompt_mask": np.concatenate(masks)},
        )
    )

    e1, e4 = make_engine(), make_engine()
    kw = dict(
        loss_fn=F.sft_loss, loss_weight_fn=F.sft_label_count,
        token_key="packed_input_ids", extra_keys=("prompt_mask",),
    )
    e1.train_batch(sample, MicroBatchSpec(n_mbs=1), **kw)
    e4.train_batch(sample, MicroBatchSpec(n_mbs=4), **kw)
    p1 = jax.tree.leaves(e1.get_params())
    p4 = jax.tree.leaves(e4.get_params())
    for a, b in zip(p1, p4):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_fused_next_token_logprobs_matches_dense(rng):
    """Chunked head+logsumexp == dense log_softmax path, values and grads."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(5))
    b, s = 2, 20
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(b, s)).astype(np.int32)
    )
    seg = jnp.asarray(
        np.where(np.arange(s)[None, :] < [[15], [20]], 1, 0).astype(np.int32)
    )

    def dense(p):
        logits = tfm.forward(p, cfg, tokens, seg)
        lp = F.next_token_logprobs(logits, tokens, seg)
        return lp.sum(), lp

    def fused(p):
        x, _ = tfm.hidden_states(p, cfg, tokens, seg)
        lp = F.fused_next_token_logprobs(
            x, tfm.head_weights(p, cfg), tokens, seg, chunk_size=8
        )
        return lp.sum(), lp

    (s1, lp1), g1 = jax.value_and_grad(dense, has_aux=True)(params)
    (s2, lp2), g2 = jax.value_and_grad(fused, has_aux=True)(params)
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp2), rtol=1e-5, atol=1e-5)
    for a, b_ in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5
        )


def test_forward_returns_aligned_logprobs(rng):
    pc = ParallelConfig.from_str("d1")
    mesh = make_mesh(pc, jax.devices()[:1])
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    engine = TrainEngine(cfg, params, mesh, ftspec=FinetuneSpec(1, 4, 4))
    sample = fixtures.random_sample(rng, ids=["a", "b", "c"], max_len=30)

    def post(logp, batch):
        return logp  # engines emit fused next-token logprobs directly

    out = engine.forward(
        sample, MicroBatchSpec(), post_fn=post, output_key="logprobs"
    )
    assert out.ids == sample.ids
    assert out.seqlens["logprobs"] == sample.seqlens["packed_input_ids"]
    lp = out.data["logprobs"]
    assert lp.shape[0] == sample.total_len("packed_input_ids")
    assert (lp <= 0).all()


_REMAT_REF = {}


@pytest.mark.parametrize("policy", ["full", "dots", "none"])
def test_remat_policy_grad_parity(policy):
    """Rematerialization changes memory/FLOPs, never math: every policy
    yields the same loss and gradients."""
    import jax

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import FinetuneSpec, OptimizerConfig
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.ops import functional as F

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    rng = np.random.default_rng(0)
    lens = [12, 20, 9]
    toks = rng.integers(0, cfg.vocab_size, size=sum(lens)).astype(np.int32)
    pmask = np.zeros(sum(lens), bool)
    off = 0
    for l in lens:
        pmask[off : off + 3] = True
        off += l
    sample = SequenceSample(
        keys={"packed_input_ids", "prompt_mask"},
        ids=[f"s{i}" for i in range(3)],
        seqlens={
            "packed_input_ids": [[l] for l in lens],
            "prompt_mask": [[l] for l in lens],
        },
        data={"packed_input_ids": toks, "prompt_mask": pmask},
    )

    def run(pol):
        eng = TrainEngine(
            cfg,
            tfm.init_params(cfg, jax.random.PRNGKey(3)),
            mesh,
            optimizer_config=OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0
            ),
            ftspec=FinetuneSpec(1, 16, 16),
            remat_policy=pol,
        )
        return eng.train_batch(
            sample,
            MicroBatchSpec(),
            loss_fn=F.sft_loss,
            loss_weight_fn=F.sft_label_count,
            token_key="packed_input_ids",
            extra_keys=("prompt_mask",),
        )

    # Reference computed once per module run, by whichever case goes
    # first — every case (under any selection/ordering) still asserts.
    if not _REMAT_REF:
        _REMAT_REF.update(run("full"))
    got = run(policy)
    ref = _REMAT_REF
    assert np.isclose(got["loss"], ref["loss"], rtol=1e-6), (got, ref)
    assert np.isclose(got["grad_norm"], ref["grad_norm"], rtol=1e-5)


def test_hotswap_never_aliases_donated_train_buffers():
    """Donation-safety regression (async rollout crash): a same-dtype
    hot-swap must COPY, not alias, the train engine's buffers — the next
    optimizer step donates them, and an aliasing generator would then
    decode from deleted buffers."""
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines.generator import GeneratorEngine

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    train = TrainEngine(
        cfg,
        tfm.init_params(cfg, jax.random.PRNGKey(0)),
        mesh,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 8, 8),
        # CPU engines compute in fp32 == master dtype -> the aliasing case.
    )
    gen = GeneratorEngine(cfg, train.get_params(), mesh, eos_token_id=7)
    gen.set_params(train.get_params())

    rng = np.random.default_rng(0)
    lens = [10, 14]
    sample = SequenceSample(
        keys={"packed_input_ids", "prompt_mask"},
        ids=["a", "b"],
        seqlens={
            "packed_input_ids": [[l] for l in lens],
            "prompt_mask": [[l] for l in lens],
        },
        data={
            "packed_input_ids": rng.integers(
                0, cfg.vocab_size, size=sum(lens)
            ).astype(np.int32),
            "prompt_mask": np.concatenate(
                [np.r_[np.ones(3, bool), np.zeros(l - 3, bool)] for l in lens]
            ),
        },
    )
    # The optimizer step donates the train params the generator was synced
    # from; generation afterwards must still work.
    train.train_batch(
        sample, MicroBatchSpec(), loss_fn=F.sft_loss,
        loss_weight_fn=F.sft_label_count,
        token_key="packed_input_ids", extra_keys=("prompt_mask",),
    )
    prompts = SequenceSample(
        keys={"packed_prompts"},
        ids=["p0"],
        seqlens={"packed_prompts": [[6]]},
        data={"packed_prompts": rng.integers(8, cfg.vocab_size, size=6).astype(np.int32)},
    )
    out = gen.generate(
        prompts, MicroBatchSpec(),
        GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True),
    )
    assert len(np.asarray(out.data["packed_input_ids"])) >= 7


@pytest.fixture
def compile_events():
    """Names of the tracing / compile / cache-load events jax.monitoring
    reports while the test runs."""
    from jax import monitoring

    seen = []

    def on_event(event, duration, **kw):
        seen.append(event)

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on_event)


@pytest.mark.parametrize(
    "train_layout,gen_layout", [("d1f2", "d1m2"), ("d1f4", "d1m4")]
)
def test_colocated_handback_relayouts_on_device(
    train_layout, gen_layout, compile_events
):
    """Train under fsdp, generate under tensor parallelism on the SAME
    devices: the hand-back is the held compiled re-layout, never
    jax.device_put (which carries such a pair through the host)."""
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.parallel import sharding

    cfg = tiny_config()
    pc = ParallelConfig.from_str(train_layout)
    devices = jax.devices()[: pc.world_size]
    train = TrainEngine(
        cfg,
        tfm.init_params(cfg, jax.random.PRNGKey(0)),
        make_mesh(pc, devices),
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 8, 8),
    )
    host = jax.tree.map(np.asarray, train.get_params())
    n = len(jax.tree.leaves(host))
    gen = GeneratorEngine(
        cfg, host, make_mesh(ParallelConfig.from_str(gen_layout), devices),
        eos_token_id=7,
    )
    # The engine's first weights came from the host: device_put.
    assert gen.last_sync_stats["leaves_put"] == n

    def hand_back():
        gen.set_params(train.get_params())
        stats = gen.last_sync_stats
        assert stats["leaves_put"] == 0 and stats["bytes_put"] == 0
        assert stats["leaves_resharded"] > 0
        assert stats["leaves_aliased"] + stats["leaves_resharded"] == n
        assert 0 < stats["bytes_resharded"] <= stats["bytes"]
        want = sharding.tree_named(gen.mesh, sharding.param_pspecs(host))
        for got, w, sh in zip(
            jax.tree.leaves(gen.get_params()),
            jax.tree.leaves(train.get_params()),
            jax.tree.leaves(want),
        ):
            assert got.sharding == sh
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))

    hand_back()
    # New values under the same layouts, as after an optimizer step: the
    # second hand-back traces, compiles and loads nothing.
    train.params = jax.block_until_ready(
        jax.tree.map(lambda x: x * 2, train.params)
    )
    del compile_events[:]
    hand_back()
    assert not compile_events, compile_events


@pytest.mark.parametrize("engine", ["generator", "inference"])
def test_donation_safe_swap_on_an_identical_layout_shares_no_buffer(engine):
    """Same mesh, same dtype: reshard leaves every leaf in place, so the
    engines' alias copy is what keeps them off the trainer's buffers."""
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.engines.inference import InferenceEngine
    from areal_tpu.engines.offload import buffers_alias

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1f2"), jax.devices()[:2])
    train = TrainEngine(
        cfg,
        tfm.init_params(cfg, jax.random.PRNGKey(1)),
        mesh,
        optimizer_config=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 8, 8),
    )
    src = train.get_params()
    n = len(jax.tree.leaves(src))
    if engine == "generator":
        eng = GeneratorEngine(
            cfg, src, mesh, eos_token_id=7, donation_safe_swap=True
        )
    else:
        eng = InferenceEngine(cfg, src, mesh)
    eng.set_params(src)
    assert eng.last_sync_stats["leaves_aliased"] == n
    for got, orig in zip(
        jax.tree.leaves(eng.get_params()), jax.tree.leaves(src)
    ):
        assert not buffers_alias(got, orig)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(orig))
    if engine == "generator":
        # The synchronous opt-out keeps the alias (no second copy in HBM).
        eng.donation_safe_swap = False
        eng.set_params(src)
        assert all(
            got is orig for got, orig in zip(
                jax.tree.leaves(eng.get_params()), jax.tree.leaves(src)
            )
        )

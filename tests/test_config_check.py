"""Experiment-config validation (the reference's experiments/common/
check.py role): misconfigurations fail at build time with named knobs."""

import dataclasses

import pytest

from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
from areal_tpu.api.model_api import GenerationHyperparameters, OptimizerConfig
from areal_tpu.base.topology import ParallelConfig
from areal_tpu.experiments.common import (
    PPOMathConfig,
    SFTConfig,
    build_ppo_math,
    build_sft,
)
from areal_tpu.models.config import tiny_config
from tests import fixtures


def _ppo_cfg(**kw):
    base = dict(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        ref=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: fixtures.build_math_rows(4),
             "max_length": 64},
        ),
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        batch_size=4,
        fileroot="/tmp/x",
    )
    base.update(kw)
    return PPOMathConfig(**base)


def _expect(msg_part, **kw):
    with pytest.raises(ValueError, match=msg_part):
        build_ppo_math(_ppo_cfg(**kw), fixtures.make_tokenizer())


class TestPPOChecks:
    def test_valid_config_builds(self):
        plan = build_ppo_math(_ppo_cfg(), fixtures.make_tokenizer())
        assert plan.dfg.nodes

    def test_adaptive_kl_needs_nonzero_init(self):
        _expect("kl_adaptive", ppo_kwargs={"kl_adaptive": True})

    def test_kl_needs_ref(self):
        _expect("needs a ref", ref=None, ppo_kwargs={"kl_ctl": 0.1})

    def test_generation_size_below_group(self):
        _expect("generation_size", ppo_kwargs={"generation_size": 1})

    def test_missing_hf_path(self):
        _expect(
            "does not exist",
            actor=ModelAbstraction("hf", {"path": "/nonexistent/ckpt"}),
        )

    def test_batch_cannot_fill_parallel_grid(self):
        _expect(
            "cannot fill",
            actor_parallel=ParallelConfig.from_str("d8"),
            batch_size=2,
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        )

    def test_bad_temperature(self):
        _expect(
            "temperature",
            gconfig=GenerationHyperparameters(
                n=2, max_new_tokens=8, temperature=0.0
            ),
        )

    def test_bad_filter_band(self):
        _expect(
            "accuracy band",
            dataset_filter={"min_accuracy": 0.9, "max_accuracy": 0.2},
        )

    def test_bad_placement(self):
        _expect("placement", placement={"actor_gen": -1})

    def test_bad_warmup(self):
        _expect(
            "warmup",
            optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=1.5),
        )

    def test_fuse_needs_ref(self):
        _expect("fuse_rew_ref", ref=None, fuse_rew_ref=True)

    def test_nonpositive_early_stop_rejected(self):
        _expect("early_stop_kl", ppo_kwargs={"early_stop_kl": 0.0})
        _expect(
            "early_stop_imp_ratio",
            ppo_kwargs={"early_stop_imp_ratio": -1.0},
        )


class TestSFTChecks:
    def test_sft_batch_grid(self):
        cfg = SFTConfig(
            model=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "prompt_answer",
                {"dataset_builder": lambda: fixtures.build_sft_rows(4),
                 "max_length": 64},
            ),
            parallel=ParallelConfig.from_str("d8"),
            batch_size=2,
            mb_spec=MicroBatchSpec(n_mbs=2),
            fileroot="/tmp/x",
        )
        with pytest.raises(ValueError, match="cannot fill"):
            build_sft(cfg, fixtures.make_tokenizer())


class TestAliasSwapChecks:
    """Colocated copy-free hot-swap wiring (round 5, VERDICT #3)."""

    def test_sync_default_aliases_generator(self):
        plan = build_ppo_math(_ppo_cfg(), fixtures.make_tokenizer())
        gen = [
            s
            for w in plan.worker_configs
            for s in w.shards
            if s.backend.type_ == "generator"
        ]
        assert gen and all(
            s.backend.args.get("donation_safe_swap") is False for s in gen
        )

    def test_async_keeps_defensive_copy(self):
        plan = build_ppo_math(
            _ppo_cfg(rollout_ahead=1), fixtures.make_tokenizer()
        )
        gen = [
            s
            for w in plan.worker_configs
            for s in w.shards
            if s.backend.type_ == "generator"
        ]
        assert gen and all(
            s.backend.args.get("donation_safe_swap") is True for s in gen
        )

    def test_async_refuses_forced_alias(self):
        _expect(
            "donation_safe_swap",
            rollout_ahead=1,
            gen_backend_args={"donation_safe_swap": False},
        )

    def test_gen_backend_args_refused_with_remote_server(self):
        _expect(
            "gen_backend_args",
            gen_server_url="http://h:1",
            gen_backend_args={"kv_cache_dtype": "int8"},
        )


class TestRemovedGenerationPaths:
    """The options that selected the dense and two-program inflight paths
    are refused at build time, with the knob named."""

    @pytest.mark.parametrize(
        "kw, msg",
        [
            ({"prefill_chunk_tokens": 0}, "two-program admit path"),
            (
                {"gen_backend_args": {"prefill_chunk_tokens": 0}},
                "two-program admit path",
            ),
            ({"gen_backend_args": {"kv_paged": False}}, "kv_paged"),
        ],
    )
    def test_rejected_by_check(self, kw, msg):
        _expect(msg, **kw)

    def test_kv_paged_is_not_a_config_field(self):
        with pytest.raises(TypeError, match="kv_paged"):
            _ppo_cfg(kv_paged=False)

"""FLOPs accounting, MFU, timing marks, stats sinks (reference:
system/flops_counter.py + base/monitor.py surfaces)."""

import json
import os

import numpy as np
import pytest

from areal_tpu.base import monitor
from areal_tpu.models.config import tiny_config


class TestFlops:
    def test_matmul_params_matches_param_count(self):
        """Analytic matmul-param count must match the real param tree
        (embedding excluded; dense tiny config)."""
        import jax

        from areal_tpu.models import transformer as tfm

        cfg = tiny_config()
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        total = sum(
            np.prod(x.shape) for x in jax.tree.leaves(params)
        )
        embed = cfg.vocab_size * cfg.hidden_dim
        # Non-matmul params: embedding + norms (+ biases); analytic count
        # must agree within the small norm/bias budget.
        analytic = monitor.matmul_params(cfg)
        non_matmul = total - analytic
        assert embed <= non_matmul <= embed + cfg.hidden_dim * (
            3 * cfg.n_layers + 10
        ) + 3 * cfg.n_layers * (
            cfg.n_q_heads + 2 * cfg.n_kv_heads
        ) * cfg.head_dim

    def test_forward_train_ratio(self):
        cfg = tiny_config()
        f = monitor.flops_forward(cfg, 1024, sum_sq_seqlens=8 * 128**2)
        t = monitor.flops_train(cfg, 1024, sum_sq_seqlens=8 * 128**2)
        assert t == pytest.approx(3 * f)

    def test_generate_flops_between_bounds(self):
        cfg = tiny_config()
        # decode of G tokens costs at least G * 2N matmul flops and less
        # than a full forward over (P+G) squared.
        p, g = [100, 50], [20, 30]
        fl = monitor.flops_generate(cfg, p, g)
        lower = 2.0 * monitor.matmul_params(cfg) * sum(g)
        upper = monitor.flops_forward(
            cfg, sum(p) + sum(g), sum((a + b) ** 2 for a, b in zip(p, g))
        )
        assert lower < fl < upper

    def test_mfu_against_the_device_peak(self, monkeypatch):
        import jax

        class _Dev:
            platform = "tpu"
            device_kind = "TPU v5 lite"

        assert monitor.mfu(1e12, 0.1, 1) is None  # CPU: no peak, no MFU
        monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
        # 1.97e13 flops in 1s on 1 device of 197 TFLOP/s peak -> 10% MFU
        assert monitor.mfu(1.97e13, 1.0, 1) == pytest.approx(0.1)
        assert monitor.mfu(1.97e13, 1.0, 2) == pytest.approx(0.05)

    def test_unknown_accelerator_kind_raises(self, monkeypatch):
        import jax

        class _Dev:
            platform = "tpu"
            device_kind = "TPU v99"

        monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
        with pytest.raises(ValueError, match="TPU v99"):
            monitor.peak_tflops_per_device()

    def test_matmul_params_moe_counts_active_experts(self):
        """MoE counts only routed (active) experts at the MoE intermediate
        width — not the full expert pool, not the dense width — plus the
        router's `hidden x n_experts` matmul every token runs."""
        dense = tiny_config()
        moe = tiny_config(n_experts=8)
        n_mats = 3  # gated mlp
        dense_mlp = n_mats * dense.hidden_dim * dense.intermediate_dim
        moe_mlp = (
            n_mats * moe.hidden_dim * moe.moe_intermediate_dim
            * moe.n_experts_per_tok
        )
        router = moe.hidden_dim * moe.n_experts
        got_diff = monitor.matmul_params(moe) - monitor.matmul_params(dense)
        assert got_diff == moe.n_layers * (moe_mlp + router - dense_mlp)
        # Pool size enters the per-token count through the router ALONE.
        moe_big_pool = tiny_config(n_experts=64)
        assert monitor.matmul_params(moe_big_pool) - monitor.matmul_params(
            moe
        ) == moe.n_layers * moe.hidden_dim * (64 - 8)

    def test_matmul_params_agrees_with_the_benchmark_at_olmoe_sizes(self):
        """The program's count and the benchmark's (`benchmark/peaks.py`,
        a copy with causal attention halved) give one number for OLMoE-1B-
        7B: 8 of 64 experts of width 1,024 and the router, 67.2 M a layer."""
        from areal_tpu.models.hf.registry import HF_FAMILIES
        from benchmark import files, peaks

        cfg = HF_FAMILIES["olmoe"].config_from_hf(
            files.load_json("configs", "olmoe-1b-7b-0125-l3.json")
        )
        assert peaks.mlp_params(cfg) == 8 * 3 * 2048 * 1024 + 2048 * 64
        assert monitor.matmul_params(cfg) == peaks.matmul_params(cfg) == (
            3 * 67_239_936 + 2048 * 50304
        )

    def test_matmul_params_critic_drops_lm_head(self):
        lm = tiny_config()
        critic = tiny_config(is_critic=True)
        assert monitor.matmul_params(lm) - monitor.matmul_params(
            critic
        ) == lm.hidden_dim * lm.vocab_size

    def test_matmul_params_ungated_mlp(self):
        import dataclasses

        cfg = tiny_config()
        ungated = dataclasses.replace(cfg, mlp_gated=False)
        assert monitor.matmul_params(cfg) - monitor.matmul_params(
            ungated
        ) == cfg.n_layers * cfg.hidden_dim * cfg.intermediate_dim

    def test_flops_forward_packed_sum_sq(self):
        """Packed-batch attention must be charged per sequence (sum of
        squared seqlens), not over the packed total squared."""
        cfg = tiny_config()
        n = 4 * 128
        packed = monitor.flops_forward(cfg, n, sum_sq_seqlens=4 * 128**2)
        mm = 2.0 * monitor.matmul_params(cfg) * n
        attn = (
            4.0 * cfg.n_q_heads * cfg.head_dim * (4 * 128**2) * cfg.n_layers
        )
        assert packed == pytest.approx(mm + attn)
        # Default (one contiguous sequence) charges n^2 — strictly more
        # than the same tokens packed as 4 separate sequences.
        assert monitor.flops_forward(cfg, n) > packed


class TestMergeStats:
    def test_denominator_weighted_mean(self):
        from areal_tpu.base.stats import merge_stats

        out = merge_stats([
            {"loss": 1.0, "loss_denominator": 100.0},
            {"loss": 3.0, "loss_denominator": 300.0},
        ])
        # Token-weighted: (1*100 + 3*300) / 400, and denominators SUM.
        assert out["loss"] == pytest.approx(2.5)
        assert out["loss_denominator"] == 400.0

    def test_plain_keys_unweighted(self):
        from areal_tpu.base.stats import merge_stats

        out = merge_stats([{"kl": 1.0}, {"kl": 3.0}])
        assert out["kl"] == pytest.approx(2.0)

    def test_partial_denominator_drops_key(self, caplog):
        """A denominator present in some-but-not-all shards breaks the
        positional value/weight pairing: the key must be dropped (with a
        one-time warning), never averaged unweighted."""
        import logging

        from areal_tpu.base.stats import merge_stats

        shards = [
            {"pd_loss": 1.0, "pd_loss_denominator": 100.0},
            {"pd_loss": 3.0},
        ]
        # The repo's logging module sets propagate=False on the
        # "areal_tpu" parent, so capture at the stats logger itself.
        slog = logging.getLogger("areal_tpu.stats")
        slog.addHandler(caplog.handler)
        try:
            with caplog.at_level(logging.WARNING, logger="areal_tpu.stats"):
                out = merge_stats(shards)
                assert "pd_loss" not in out
                assert out["pd_loss_denominator"] == 100.0
                warned = [
                    r for r in caplog.records
                    if "pd_loss" in r.getMessage()
                ]
                assert len(warned) == 1
                # Log-once: the second merge stays quiet.
                caplog.clear()
                merge_stats(shards)
                assert not [
                    r for r in caplog.records
                    if "pd_loss" in r.getMessage()
                ]
        finally:
            slog.removeHandler(caplog.handler)

    def test_zero_denominator_falls_back_to_mean(self):
        from areal_tpu.base.stats import merge_stats

        out = merge_stats([
            {"acc": 1.0, "acc_denominator": 0.0},
            {"acc": 3.0, "acc_denominator": 0.0},
        ])
        assert out["acc"] == pytest.approx(2.0)


def test_stats_logger_jsonl(tmp_path):
    sl = monitor.StatsLogger(str(tmp_path), "e", "t", use_tensorboard=False)
    sl.log(1, {"loss": 0.5})
    sl.log(2, {"loss": 0.25, "perf/mfu": 0.4})
    sl.close()
    rows = monitor.read_stats(str(tmp_path), "e", "t")
    assert [r["global_step"] for r in rows] == [1, 2]
    assert rows[1]["perf/mfu"] == 0.4


def test_master_emits_perf_stats(tmp_path):
    """End-to-end: a trial's stats carry per-MFC time + tflops and land in
    the jsonl sink."""
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
    from areal_tpu.api.model_api import OptimizerConfig
    from areal_tpu.experiments.common import SFTConfig, build_sft, run_experiment
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    cfg = SFTConfig(
        model=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "prompt_answer",
            {
                "dataset_builder": lambda: fixtures.build_sft_rows(8, seed=2),
                "max_length": 128,
            },
        ),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        batch_size=8,
        total_train_epochs=1,
        mb_spec=MicroBatchSpec(n_mbs=2),
        ctrl=ExperimentSaveEvalControl(benchmark_steps=1),
        fileroot=str(tmp_path),
        experiment_name="perftest",
    )
    tok = fixtures.make_tokenizer()
    _, stats = run_experiment(build_sft(cfg, tok), tokenizer=tok)
    s = stats[-1]
    assert s["perf/time_s"] > 0
    assert s["perf/tflops"] > 0
    assert s["time/step_s"] > 0
    rows = monitor.read_stats(str(tmp_path), "perftest", "trial")
    assert len(rows) == 1 and rows[0]["perf/tflops"] == s["perf/tflops"]


def test_mfc_trace_dump(tmp_path, monkeypatch):
    """AREAL_DUMP_TRACE exports an xprof trace per MFC (reference:
    REAL_DUMP_TRACE, model_worker.py:84-99)."""
    import os

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.experiments.common import SFTConfig, build_sft, run_experiment
    from areal_tpu.api.model_api import OptimizerConfig
    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.base.topology import ParallelConfig
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    monkeypatch.setenv("AREAL_DUMP_TRACE", str(tmp_path / "traces"))
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_sft_rows(8, seed=3)
    cfg = SFTConfig(
        model=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "prompt_answer", {"dataset_builder": lambda: rows, "max_length": 64}
        ),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        batch_size=8,
        mb_spec=MicroBatchSpec(n_mbs=2),
        ctrl=ExperimentSaveEvalControl(benchmark_steps=1),
        fileroot=str(tmp_path / "trial"),
    )
    _, stats = run_experiment(build_sft(cfg, tok), tokenizer=tok)
    assert len(stats) == 1
    trace_dir = tmp_path / "traces" / "default@0_train_step"
    # jax.profiler.trace writes plugins/profile/<ts>/*.xplane.pb
    found = list(trace_dir.rglob("*.xplane.pb"))
    assert found, list(trace_dir.rglob("*"))


@pytest.mark.slow
def test_mfc_trace_dump_concurrent_mfcs(tmp_path, monkeypatch):
    """Tracing must survive MFCs that overlap in one process (JAX allows a
    single active trace; contenders run untraced instead of crashing)."""
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.experiments.common import (
        PPOMathConfig,
        build_ppo_math,
        run_experiment,
    )
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    monkeypatch.setenv("AREAL_DUMP_TRACE", str(tmp_path / "traces"))
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(8, seed=4)
    cfg = PPOMathConfig(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        ref=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows, "max_length": 64},
        ),
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        batch_size=4,
        ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
        fileroot=str(tmp_path / "trial"),
    )
    # rew_inf and ref_inf share no edge -> the in-process runner overlaps
    # them; without the trace lock the second trace raises.
    _, stats = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
    assert len(stats) == 2
    assert list((tmp_path / "traces").rglob("*.xplane.pb"))


def test_hbm_kill_threshold(monkeypatch):
    """AREAL_HBM_KILL_FRAC fails the MFC when device memory crosses the
    watermark (reference: model_worker.py:1434-1537 mem kill)."""
    from areal_tpu.system.worker import _check_hbm_kill

    monkeypatch.setenv("AREAL_HBM_KILL_FRAC", "0.9")
    _check_hbm_kill({"perf/hbm_frac": 0.85})  # under: fine
    _check_hbm_kill({})  # no stats (CPU): fine
    with pytest.raises(MemoryError, match="0.9"):
        _check_hbm_kill({"perf/hbm_frac": 0.95})
    monkeypatch.delenv("AREAL_HBM_KILL_FRAC")
    _check_hbm_kill({"perf/hbm_frac": 0.99})  # disabled: fine

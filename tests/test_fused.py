"""Fused rew+ref interface (reference: fused_interface.py
FusedThreadingForwardInterface, ppo_math_exp.py:132-136): one MFC produces
both rewards and ref logprobs, and the fused trial computes the same math
as the unfused one."""

import numpy as np
import pytest

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


def _cfg(tmp_path, rows, fuse: bool):
    return PPOMathConfig(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        ref=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows, "max_length": 64},
        ),
        reward_interface_args={
            "id2info": {r["query_id"]: r for r in rows}
        },
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        fuse_rew_ref=fuse,
        batch_size=4,
        total_train_epochs=1,
        ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
        fileroot=str(tmp_path / ("fused" if fuse else "plain")),
    )


def test_fused_graph_shape(tmp_path):
    rows = fixtures.build_math_rows(8, seed=4)
    plan = build_ppo_math(_cfg(tmp_path, rows, fuse=True))
    names = {n.name for n in plan.dfg.nodes}
    assert "fused_rew_ref" in names
    assert "rew_inf" not in names and "ref_inf" not in names
    fused = next(n for n in plan.dfg.nodes if n.name == "fused_rew_ref")
    assert set(fused.output_keys) == {"rewards", "packed_ref_logprobs"}
    # The reward pseudo-model disappears: its work rides the ref worker.
    roles = {s.name.role for wc in plan.worker_configs for s in wc.shards}
    assert "reward" not in roles and "ref" in roles


def test_fused_matches_unfused(tmp_path):
    """Same seeds -> the fused trial's stats equal the two-MFC trial's."""
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(8, seed=4)
    _, stats_plain = run_experiment(
        build_ppo_math(_cfg(tmp_path, rows, fuse=False), tok), tokenizer=tok
    )
    _, stats_fused = run_experiment(
        build_ppo_math(_cfg(tmp_path, rows, fuse=True), tok), tokenizer=tok
    )
    assert len(stats_fused) == len(stats_plain) == 2
    for sp, sf in zip(stats_plain, stats_fused):
        for k, v in sp.items():
            if k.startswith("actor_train/") and not k.startswith(
                # measured on the host's clock, not computed from the batch
                ("actor_train/perf", "actor_train/time/", "actor_train/host/")
            ):
                assert np.isclose(sf[k], v, rtol=1e-4, atol=1e-6), (k, v, sf[k])
    assert abs(stats_fused[0]["actor_train/importance_weight"] - 1.0) < 5e-2

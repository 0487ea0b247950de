"""Multi-process runtime: ZMQ stream + local scheduler + worker bootstrap.

Mirrors the reference's end-to-end experiment tests (tests/experiments/
utils.py: master in the main process, model workers in spawned processes),
with the file name-resolve backend for discovery.
"""

import os
import pickle
import sys
import tempfile

import numpy as np
import pytest

from areal_tpu.api.config import ModelAbstraction, ModelInterfaceAbstraction
from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
from areal_tpu.api.model_api import OptimizerConfig
from areal_tpu.base import name_resolve
from areal_tpu.base.topology import ParallelConfig
from areal_tpu.models.config import tiny_config
from areal_tpu.scheduler import JobException, JobState, make_scheduler
from areal_tpu.system.master import ExperimentSaveEvalControl

from tests import fixtures


def test_local_scheduler_lifecycle(tmp_path):
    sched = make_scheduler("local", "t", "s", log_root=str(tmp_path))
    sched.submit("ok", [sys.executable, "-c", "print('done')"])
    sched.wait(timeout=30)
    info = sched.find("ok")
    assert info.state == JobState.COMPLETED

    sched2 = make_scheduler("local", "t", "s2", log_root=str(tmp_path))
    sched2.submit("bad", [sys.executable, "-c", "import sys; sys.exit(3)"])
    with pytest.raises(JobException):
        sched2.wait(timeout=30)

    sched3 = make_scheduler("local", "t", "s3", log_root=str(tmp_path))
    sched3.submit(
        "hang", [sys.executable, "-c", "import time; time.sleep(600)"]
    )
    sched3.stop_all()
    assert sched3.find("hang").state == JobState.CANCELLED


def test_sft_multiprocess_e2e(tmp_path):
    """Full trial over ZMQ: 1 worker subprocess, master here, 2 steps."""
    from areal_tpu.experiments.common import SFTConfig, build_sft
    from areal_tpu.apps import main as runner

    # A tiny jsonl dataset on disk; the worker subprocess bootstraps the
    # hermetic char tokenizer via the "char:<vocab>" path scheme.
    rows = fixtures.build_sft_rows(16, seed=5)
    data_path = tmp_path / "data.jsonl"
    import json

    with open(data_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    cfg = SFTConfig(
        model=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "prompt_answer",
            {"dataset_path": str(data_path), "max_length": 128},
        ),
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        batch_size=8,
        total_train_epochs=1,
        mb_spec=MicroBatchSpec(n_mbs=2),
        ctrl=ExperimentSaveEvalControl(
            total_train_epochs=1, benchmark_steps=2
        ),
        experiment_name="zmqtest",
        trial_name="t0",
        fileroot=str(tmp_path / "trial"),
    )
    plan = build_sft(cfg)
    for wc in plan.worker_configs:
        wc.tokenizer_path = "char:512"

    stats = runner.run_experiment(
        plan,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        },
    )
    assert len(stats) == 2
    assert np.isfinite(stats[-1]["nll"])


def test_sft_multihost_spmd(tmp_path):
    """One model, one GLOBAL d4 mesh laid across TWO worker processes (2
    local devices each) via jax.distributed — the multi-controller
    equivalent of the reference's multi-node NCCL world
    (impl/model/comm/global_comm.py).  Both processes execute the train
    MFC SPMD-symmetrically; gradients cross process boundaries through
    XLA collectives (gloo on the CPU fake cluster)."""
    import json

    from areal_tpu.experiments.common import (
        SFTConfig,
        build_sft,
        run_experiment as run_inproc,
    )
    from areal_tpu.apps import main as runner

    rows = fixtures.build_sft_rows(16, seed=5)
    data_path = tmp_path / "data.jsonl"
    with open(data_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    def make_cfg(n_hosts, parallel, root):
        return SFTConfig(
            model=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "prompt_answer",
                {"dataset_path": str(data_path), "max_length": 128},
            ),
            n_hosts=n_hosts,
            parallel=ParallelConfig.from_str(parallel),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            batch_size=8,
            total_train_epochs=1,
            mb_spec=MicroBatchSpec(n_mbs=2),
            ctrl=ExperimentSaveEvalControl(
                total_train_epochs=1, benchmark_steps=2
            ),
            experiment_name="zmqdist",
            trial_name="t0",
            fileroot=str(root),
        )

    plan = build_sft(make_cfg(2, "d4", tmp_path / "dist"))
    for wc in plan.worker_configs:
        wc.tokenizer_path = "char:512"
    assert plan.model_groups == {"default@0": [0, 1]}
    stats = runner.run_experiment(
        plan,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    assert len(stats) == 2
    assert np.isfinite(stats[-1]["nll"])

    # The distributed run must compute the same math as a single-process
    # run of the identical trial (d4 over 4 in-process devices).
    plan1 = build_sft(make_cfg(1, "d4", tmp_path / "solo"))
    for wc in plan1.worker_configs:
        wc.tokenizer_path = "char:512"
    _, stats1 = run_inproc(plan1, tokenizer=None)
    for s_dist, s_solo in zip(stats, stats1):
        assert np.isclose(s_dist["nll"], s_solo["nll"], rtol=1e-3), (
            s_dist, s_solo,
        )


@pytest.mark.slow
def test_ppo_disjoint_workers_multiprocess(tmp_path):
    """VERDICT r1 'done' criterion: gen and train in DIFFERENT worker
    processes with their own meshes; a PPO step completes — prompts, rollouts,
    rewards and fresh weights all cross process boundaries over the ZMQ
    transfer plane."""
    import json

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.apps import main as runner
    from areal_tpu.experiments.common import PPOMathConfig, build_ppo_math
    from areal_tpu.models.config import tiny_config

    rows = fixtures.build_math_rows(8, seed=4)
    data_path = tmp_path / "math.jsonl"
    with open(data_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")

    cfg = PPOMathConfig(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_path": str(data_path), "max_length": 64},
        ),
        reward_interface_args={
            "id2info": {r["query_id"]: r for r in rows}
        },
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 2},
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        actor_parallel=ParallelConfig.from_str("d2"),
        gen_parallel=ParallelConfig.from_str("d2"),
        placement={"actor_gen": 1, "reward": 1},
        batch_size=4,
        total_train_epochs=1,
        ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
        experiment_name="zmqppo",
        trial_name="t0",
        fileroot=str(tmp_path / "trial"),
    )
    plan = build_ppo_math(cfg)
    for wc in plan.worker_configs:
        wc.tokenizer_path = "char:512"

    stats = runner.run_experiment(
        plan,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    assert len(stats) == 2
    assert np.isfinite(stats[-1]["actor_train/actor_loss"])
    assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2

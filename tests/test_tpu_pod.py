"""TPU-pod launcher tests over a mocked ssh transport.

Models the role of the reference's Ray-controller tests (worker placement
+ lifecycle, realhf/system/controller.py:448) without a pod: the transport
records every gcloud argv and serves canned probe replies.
"""

import pytest

from areal_tpu.scheduler.client import (
    JobException,
    JobState,
    make_scheduler,
)
from areal_tpu.scheduler.tpu_pod import TPUPodSchedulerClient


class FakeTransport:
    def __init__(self):
        self.calls = []  # list of argv
        self.replies = {}  # substring of remote cmd -> (rc, stdout)
        # (rc, token): emulate the probe protocol — single probes answer
        # the bare token, batched per-host probes answer one
        # '<worker_type> <token>' line per job (mirrors the remote shell).
        self.probe = None
        self.default = (0, "")

    def __call__(self, argv):
        import re

        self.calls.append(list(argv))
        remote = argv[argv.index("--command") + 1]
        if "if [ -f" in remote and self.probe is not None:
            rc, token = self.probe
            wts = re.findall(r"printf '%s ' '?([^';]+)'?;", remote)
            if wts:
                return rc, "".join(f"{w} {token}\n" for w in wts)
            return rc, token + "\n"
        for key, reply in self.replies.items():
            if key in remote:
                return reply
        return self.default


def _client(**kw):
    t = FakeTransport()
    c = TPUPodSchedulerClient(
        "exp", "t0", tpu_name="pod1", zone="us-east5-a",
        project="proj", num_hosts=4, log_root="/gcs/logs",
        env={"AREAL_NAME_RESOLVE": "file", "X": "a b"},
        poll_interval=0.01, transport=t, **kw,
    )
    return c, t


class TestSubmit:
    def test_argv_and_placement(self):
        c, t = _client()
        c.submit("model_worker/6", ["python", "-m", "w", "--index", "6"])
        argv = t.calls[0]
        assert argv[:6] == [
            "gcloud", "compute", "tpus", "tpu-vm", "ssh", "pod1"
        ]
        assert "--worker=2" in argv  # 6 % 4 hosts
        assert ["--zone", "us-east5-a"] == argv[-4:-2]
        assert ["--project", "proj"] == argv[-2:]
        remote = argv[argv.index("--command") + 1]
        # Detached launch with env, log, pid, and exit-code capture.
        assert "nohup sh -c" in remote
        assert "AREAL_NAME_RESOLVE=file" in remote
        assert "X=" in remote and "a b" in remote  # value survives quoting
        assert "/gcs/logs/exp_t0/model_worker_6.log" in remote
        assert ".exit" in remote and ".pid" in remote

    def test_submit_failure_raises(self):
        c, t = _client()
        t.default = (255, "ssh unreachable")
        with pytest.raises(JobException):
            c.submit("model_worker/0", ["python"])

    def test_submit_array_spreads_hosts(self):
        c, t = _client()
        c.submit_array(
            "model_worker", lambda i: ["python", str(i)], count=4
        )
        workers = [
            next(a for a in argv if a.startswith("--worker="))
            for argv in t.calls
        ]
        assert workers == [f"--worker={i}" for i in range(4)]


class TestStates:
    @pytest.mark.parametrize(
        "reply,state,code",
        [
            ("RUNNING", JobState.RUNNING, None),
            ("EXIT:0", JobState.COMPLETED, 0),
            ("EXIT:9", JobState.FAILED, 9),
            ("LOST", JobState.FAILED, None),
        ],
    )
    def test_probe_mapping(self, reply, state, code):
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        t.probe = (0, reply)
        info = c.find("model_worker/0")
        assert info.state == state
        assert info.exit_code == code
        assert info.host == "pod1:0"
        assert info.log_path.endswith("model_worker_0.log")

    def test_probe_ignores_ssh_noise(self):
        """gcloud/ssh interleave stderr warnings with stdout; the state
        token must be found anywhere in the output, not on the last
        line."""
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        t.replies["if [ -f"] = (
            0,
            "EXIT:3\nWarning: Permanently added 'tpu' to known hosts.\n",
        )
        info = c.find("model_worker/0")
        assert info.state == JobState.FAILED and info.exit_code == 3

    def test_find_all_batches_one_ssh_per_host(self):
        """A poll sweep costs one ssh per HOST, not per worker."""
        c, t = _client()
        for i in range(8):  # 8 workers over 4 hosts
            c.submit(f"model_worker/{i}", ["python"])
        t.probe = (0, "RUNNING")
        n0 = len(t.calls)
        infos = c.find_all()
        assert len(infos) == 8
        assert all(i.state == JobState.RUNNING for i in infos)
        assert len(t.calls) - n0 == 4

    def test_transient_ssh_failure_is_pending(self):
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        t.probe = (255, "")
        assert c.find("model_worker/0").state == JobState.PENDING

    def test_unknown_worker_not_found(self):
        c, _ = _client()
        assert c.find("nope").state == JobState.NOT_FOUND


class TestWaitStop:
    def test_wait_drains_completed(self):
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        c.submit("model_worker/1", ["python"])
        t.probe = (0, "EXIT:0")
        c.wait(timeout=5.0)
        assert not c._jobs

    def test_wait_raises_on_failure_with_host(self):
        c, t = _client()
        c.submit("model_worker/1", ["python"])
        t.probe = (0, "EXIT:137")
        with pytest.raises(JobException) as ei:
            c.wait(timeout=5.0)
        assert ei.value.reason == JobState.FAILED
        assert "host" not in ei.value.host  # real host name, pod1:1
        assert ei.value.host == "pod1:1"

    def test_wait_times_out_while_running(self):
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        t.probe = (0, "RUNNING")
        with pytest.raises(TimeoutError):
            c.wait(timeout=0.05)

    def test_stop_all_kills_and_forgets(self):
        c, t = _client()
        c.submit("model_worker/0", ["python"])
        c.submit("model_worker/1", ["python"])
        n_submit = len(t.calls)
        c.stop_all()
        assert not c._jobs
        kills = t.calls[n_submit:]
        assert len(kills) == 2
        for argv in kills:
            remote = argv[argv.index("--command") + 1]
            assert "kill -TERM" in remote and "pkill" in remote


def test_make_scheduler_mode():
    c = make_scheduler(
        "tpu-pod", "e", "t", tpu_name="pod1", transport=lambda a: (0, "")
    )
    assert isinstance(c, TPUPodSchedulerClient)


def _local_shell_transport(argv):
    """Execute the would-be-remote command in a local shell: the full pod
    protocol (nohup detach, pid files, exit files, probes, kills) runs for
    real — only gcloud ssh is swapped out."""
    import subprocess

    remote = argv[argv.index("--command") + 1]
    p = subprocess.run(
        ["sh", "-c", remote], capture_output=True, text=True, timeout=120
    )
    return p.returncode, p.stdout + p.stderr


def test_pod_launcher_runs_a_real_trial(tmp_path):
    """End-to-end through the tpu-pod code path: run_experiment launches
    real worker processes via the pod launcher's detach/probe/teardown
    protocol (local-shell transport standing in for gcloud ssh) and a PPO
    trial completes over the ZMQ planes."""
    import json

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.apps import main as runner
    from areal_tpu.experiments.common import PPOMathConfig, build_ppo_math
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

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
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 2},
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        batch_size=4,
        total_train_epochs=1,
        ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
        experiment_name="podppo",
        trial_name="t0",
        fileroot=str(tmp_path / "trial"),
    )
    plan = build_ppo_math(cfg)
    for wc in plan.worker_configs:
        wc.tokenizer_path = "char:512"
    import numpy as np

    stats = runner.run_experiment(
        plan,
        scheduler_mode="tpu-pod",
        scheduler_kwargs={
            "tpu_name": "fakepod",
            "num_hosts": 1,
            "transport": _local_shell_transport,
            "log_root": str(tmp_path / "logs"),
            "poll_interval": 0.5,
        },
        # Pod workers own their chips; this fake pod is this CPU host.
        worker_env={"JAX_PLATFORMS": "cpu"},
    )
    assert len(stats) == 2
    assert np.isfinite(stats[-1]["actor_train/actor_loss"])
    # The worker ran detached with pid/exit-file bookkeeping.
    logs = list((tmp_path / "logs" / "podppo_t0").glob("*.log"))
    assert logs, "pod worker log missing"
    assert (tmp_path / "logs" / "podppo_t0" / "model_worker_0.log.exit").exists()

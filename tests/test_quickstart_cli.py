"""Quickstart CLI end-to-end (reference: apps/quickstart.py hydra entry):
both subcommands run a real tiny trial from argv, including the
decoupled/fusion/EMA flags."""

import json

import jax
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.models.hf import registry as hf

from tests import fixtures


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("ckpt")
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    hf.save_hf_checkpoint(str(path), cfg, params, model_type="qwen2")
    return str(path)


def test_quickstart_sft_cli(tmp_path, ckpt_dir, capsys):
    from areal_tpu.apps import quickstart

    rows = fixtures.build_sft_rows(16, seed=5)
    data = tmp_path / "data.jsonl"
    with open(data, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    quickstart.main([
        "sft",
        "--model.path", ckpt_dir,
        "--dataset.path", str(data),
        "--tokenizer-path", "char:512",
        "--batch-size", "8",
        "--benchmark-steps", "2",
        "--lr", "1e-3",
        "--fileroot", str(tmp_path / "trial"),
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert np.isfinite(out["nll"])


def test_quickstart_ppo_cli_full_flags(tmp_path, ckpt_dir, capsys):
    """ppo-math via argv with ref + KL + fusion + EMA + offload."""
    from areal_tpu.apps import quickstart

    rows = fixtures.build_math_rows(8, seed=4)
    data = tmp_path / "math.jsonl"
    with open(data, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    quickstart.main([
        "ppo-math",
        "--model.path", ckpt_dir,
        "--dataset.path", str(data),
        "--tokenizer-path", "char:512",
        "--ref-path", ckpt_dir,
        "--kl-ctl", "0.1",
        "--fuse-rew-ref",
        "--ref-ema-eta", "0.5",
        "--offload-ref",
        "--batch-size", "4",
        "--group-size", "2",
        "--max-new-tokens", "8",
        "--benchmark-steps", "2",
        "--fileroot", str(tmp_path / "trial"),
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    actor_keys = [k for k in out if k.startswith("actor_train/")]
    assert actor_keys and np.isfinite(out["actor_train/actor_loss"])


@pytest.mark.parametrize(
    "cfg",
    [
        "examples/configs/sft-1.5b-v5e-8.yaml",
        "examples/configs/ppo-1.5b-v5e-8.yaml",
        "examples/configs/ppo-7b-v5p-32.yaml",
        "examples/configs/ppo-7b-zero-v5p-32.yaml",
        "examples/configs/sft-32b-v5p-64.yaml",
    ],
)
def test_example_configs_keys_resolve(cfg):
    """Every key in the gallery YAMLs must map to a real CLI flag —
    _apply_yaml_config SystemExits with 'unknown option' otherwise.  The
    run itself then fails on the placeholder /ckpts path, which is fine."""
    import os

    from areal_tpu.apps import quickstart

    cmd = "sft" if "/sft-" in cfg else "ppo-math"
    path = os.path.join(os.path.dirname(__file__), "..", cfg)
    with pytest.raises(BaseException) as ei:
        quickstart.main([cmd, "--config", path])
    assert "unknown option" not in str(ei.value)


def test_multiprocess_launcher_stays_off_the_jax_backend():
    """A chip belongs to one process.  The `--multiprocess` launcher
    (imports, plan build, metrics server) must never initialize a JAX
    backend, or on a TPU host it would take the chips from its workers."""
    import subprocess
    import sys

    code = """
from jax._src import xla_bridge
import areal_tpu.apps.quickstart, areal_tpu.apps.main
from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.data_api import DatasetAbstraction
from areal_tpu.base import metrics
from areal_tpu.experiments.common import PPOMathConfig, build_ppo_math
from areal_tpu.models.config import tiny_config
build_ppo_math(PPOMathConfig(
    actor=ModelAbstraction("random", {"config": tiny_config()}),
    dataset=DatasetAbstraction("math_code_prompt", {"dataset_path": "x"}),
))
metrics.MetricsServer(announce=("e", "t", "master")).close()
assert not xla_bridge.backends_are_initialized()
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, timeout=120
    )
    assert out.returncode == 0, out.stderr.decode(errors="replace")[-2000:]

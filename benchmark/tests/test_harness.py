"""The harness end to end at toy size on the CPU: every cell's rehearsal,
and a cell, a config, a traffic mix and a metric it has never heard of,
added as files only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import files

CELLS = [w["name"] for w in files.benchmark_json()["workloads"]]


def rehearse(cwd, cell, trace=0, seconds=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = files.ROOT  # for areal_tpu; cwd's benchmark/ wins
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "3", "--seconds", str(seconds), "--trace", str(trace),
         "--cpu-rehearsal"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def would_print(proc):
    line = [l for l in proc.stderr.splitlines() if "would print: " in l][-1]
    return json.loads(line.split("would print: ", 1)[1])


@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal(cell):
    proc = rehearse(files.ROOT, cell)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""  # platform=cpu: no result line
    out = would_print(proc)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in files.metrics_for(cell, traced=False)}
    # No device on the CPU: nothing is read from memory_stats.
    assert set(out["metrics"]) == want - {"peak_hbm_gb"}


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=files.ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=files.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_new_cell_config_traffic_and_metric_from_files_alone(tmp_path):
    shutil.copytree(
        files.HERE, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    b = tmp_path / "benchmark"
    spec = files.benchmark_json()
    config = files.load_json("configs", f"{spec['configs'][0]['name']}.json")
    config["num_hidden_layers"] = 3
    (b / "configs" / "dummy-model.json").write_text(json.dumps(config))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "generator": "math_prompts", "n_prompts": 3, "group": 2,
        "max_new_tokens": 256, "dataset_max_length": 512,
        "prompt_len": {"dist": "uniform", "lo": 400, "hi": 480, "jitter": 16},
        # What no cell of BENCHMARK.json does: new batches in the window,
        # in the order drawn, with an EOS the model can sample.
        "batches": 3, "eos_reachable": True,
    }))
    (b / "workloads" / "dummy-cell.json").write_text(json.dumps({
        "config": "dummy-model", "traffic": "dummy-mix", "chips": 1,
        "route": "static",
    }))
    (b / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return 42.0 + len(run.steps) * 0\n"
    )
    spec["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "master",
        "moves": "samples_per_s", "workloads": ["dummy-cell"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    proc = rehearse(tmp_path, "dummy-cell", trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = would_print(proc)
    # New batches compile inside the window: reported, not a failure.
    assert out["correct"] is True
    assert out["metrics"]["compiles_in_window"]["value"] >= 0
    assert out["metrics"]["dummy_metric"] == {"value": 42.0, "unit": "count"}
    assert "step_s" in out["metrics"]

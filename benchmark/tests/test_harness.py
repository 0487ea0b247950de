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


def copy_of_the_benchmark(tmp_path):
    shutil.copytree(
        files.HERE, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    return tmp_path / "benchmark", files.benchmark_json()


def test_new_cell_config_traffic_and_metric_from_files_alone(tmp_path):
    b, spec = copy_of_the_benchmark(tmp_path)
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


def other_family_reference():
    """qwen2's reference as text, with what another family changes: the
    attention biases optional, and the MLP the mixture over a token's
    top-k experts with every expert computed densely (the k weights
    renormalised, as HF `modeling_mixtral.py` does)."""
    with open(os.path.join(files.HERE, "references", "qwen2.py")) as f:
        text = f.read()
    edits = [(f'+ blk["b{x}"])', f'+ blk.get("b{x}", 0.0))') for x in "qkv"]
    edits.append((
        'return x + (jax.nn.silu(h @ blk["wg"]) * (h @ blk["wu"])) @ blk["wd"]',
        "return x + _mlp(h, blk, cfg)",
    ))
    for old, new in edits:
        assert text.count(old) == 1, old
        text = text.replace(old, new)
    return text + '''

def _mlp(h, blk, cfg):
    if not cfg.n_experts:
        return (jax.nn.silu(h @ blk["wg"]) * (h @ blk["wu"])) @ blk["wd"]
    probs = jax.nn.softmax(h @ blk["router"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, blk["wg"]))
    out = jnp.einsum(
        "tef,efd->ted", act * jnp.einsum("td,edf->tef", h, blk["wu"]),
        blk["wd"])
    return jnp.einsum("te,ted->td", gates, out)
'''


@pytest.mark.parametrize("family,extra,toy,leaves,mlp_params", [
    # MoE, no attention bias: leaves the harness has never met (`router`,
    # four-dimensional experts) and none of the one it used to read (`bq`).
    ("mixtral", {"num_local_experts": 8, "num_experts_per_tok": 2},
     {"num_local_experts": 4, "intermediate_size": 32}, 13,
     2 * 3 * 64 * 32 + 64 * 4),  # the 2 active of 4 experts + the router
    # The dense block without the bias: what every catalog candidate has.
    ("llama", {}, {}, 12, 3 * 64 * 128),
])
def test_a_config_of_another_family_from_files_alone(
        tmp_path, family, extra, toy, leaves, mlp_params):
    b, spec = copy_of_the_benchmark(tmp_path)
    config = files.load_json("configs", f"{spec['configs'][0]['name']}.json")
    config.update(extra, model_type=family, tie_word_embeddings=False)
    config["benchmark"] = dict(
        config["benchmark"], reference="other_family", toy=toy)
    (b / "configs" / "other-family.json").write_text(json.dumps(config))
    (b / "references" / "other_family.py").write_text(other_family_reference())
    cell = files.load_json("workloads", f"{CELLS[0]}.json")
    (b / "workloads" / "other-cell.json").write_text(
        json.dumps(dict(cell, config="other-family")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and CELLS[0] in m["workloads"]:
            m["workloads"].append("other-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    proc = rehearse(tmp_path, "other-cell")
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = would_print(proc)
    assert out["correct"] is True and out["failed"] == 0
    # Every leaf the model has was compared, not three by name.
    check = [l for l in proc.stderr.splitlines() if "weight check: " in l][-1]
    assert "'ok': True" in check and f"'leaves': {leaves}" in check, check
    # What `mfu_train` divides by on a chip (no peak on the CPU, so the
    # rehearsal leaves the metric out): the experts a token is routed to.
    from benchmark import peaks, run

    cfg = run.model_config(run.toy(config, files.load_json(
        "traffic", f"{cell['traffic']}.json"))[0])
    assert peaks.mlp_params(cfg) == mlp_params
    assert "mfu_train" not in out["metrics"]

"""The yardstick's arithmetic and the contract's shape."""

import json
import os
import types

import pytest

from benchmark import files, peaks

CFG = types.SimpleNamespace(
    hidden_dim=1536, head_dim=128, n_q_heads=12, n_kv_heads=2,
    intermediate_dim=8960, n_layers=28, vocab_size=151936,
)


def test_matmul_params_of_the_1p5b():
    # 28 x (attention 5.5 M + MLP 41.3 M) + a 233 M head.
    assert peaks.matmul_params(CFG) == 28 * (
        1536 * (12 + 4) * 128 + 12 * 128 * 1536 + 3 * 1536 * 8960
    ) + 1536 * 151936
    assert peaks.weight_bytes(CFG) == 2 * peaks.matmul_params(CFG)


def test_flops_train_is_three_forwards_and_attention_is_causal():
    one = peaks.flops_forward(CFG, [1000])
    assert peaks.flops_train(CFG, [1000]) == 3 * one
    attn = one - 2.0 * peaks.matmul_params(CFG) * 1000
    assert attn == pytest.approx(2.0 * 12 * 128 * 1000**2 * 28)
    # Generating g tokens after a prompt of p costs what a forward over
    # p + g tokens costs, up to the causal half-square's discretisation.
    gen = peaks.flops_generate(CFG, [500], [500])
    assert gen == pytest.approx(one, rel=1e-3)


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9000")


def test_every_name_in_benchmark_json_has_its_files():
    spec = files.benchmark_json()
    assert spec["paths"] == ["benchmark"]
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(files.ROOT, c["file"]))
        cfg = files.load_json("configs", f"{c['name']}.json")
        assert sorted(cfg["benchmark"]["reduced"]) == sorted(c["reduced"])
        assert cfg["benchmark"]["source"] == c["source"]
        files.load_module("references", cfg["benchmark"]["reference"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(files.load_module("metrics", m["name"]).read)
        assert m.get("moves", "samples_per_s") in e2e
    for w in spec["workloads"]:
        cell, config, traffic = files.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"]
        )
        assert cell["chips"] == config["benchmark"]["layout"]["chips"]
        # Every cell reports setup_s, another end-to-end metric and a
        # per-layer metric; a per-layer metric only where its `moves` is.
        names = {m["name"] for m in files.metrics_for(w["name"], False)}
        assert "setup_s" in names and len(names) >= 2
        for m in files.metrics_for(w["name"], True):
            assert m["moves"] in names, (w["name"], m["name"])
    assert len(json.dumps(spec)) < 64 * 1024

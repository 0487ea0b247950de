"""PR 68's configuration, cell and readers in the harness's own cases:
`peaks_bd.py` on fixed work at the published widths and, to the digit,
against the model's own counts; each reader on a recorded run (the
program's counters and the scopes' seconds as a traced chip run of
`sdar-rollout64-512` reduced them: `data/sdar_recorded_run.json`); that
every one says nothing for a program without the scopes or the counters
(the parent of PR 68) and for another model; and the entries in
BENCHMARK.json.  `per_layer` stands at the contract's 128 entries, so the
eleven readers' entries are NOT there (PERF.md section 7): the files wait
for a benchmark PR to list them."""
import json
import os

import jax
import pytest

from areal_tpu.models import transformer as tfm
from benchmark import files, peaks_bd
from benchmark import run as run_mod
from benchmark.metrics import (
    _bd, bd_cache_copy_share, bd_commit_share, bd_denoise_ms,
    bd_stream_overhead, bd_tokens_per_forward, decode_hbm_share_bd,
    mfu_gen_bd, mfu_train_bd, moe_bd_mlp_ms, moe_bd_mlp_roofline,
    moe_bd_route_share,
)

CELL = "sdar-rollout64-512"
CONFIG = "sdar-30b-a3b-chat-l8-e16"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = (
    bd_tokens_per_forward, bd_denoise_ms, bd_commit_share,
    bd_stream_overhead, decode_hbm_share_bd, mfu_gen_bd, mfu_train_bd,
    moe_bd_mlp_ms, moe_bd_mlp_roofline, moe_bd_route_share,
    bd_cache_copy_share,
)
SHARES = (decode_hbm_share_bd, mfu_gen_bd, mfu_train_bd, moe_bd_mlp_roofline)
# The readers of the device trace (the others read the program's counters).
TRACED = (
    bd_denoise_ms, bd_commit_share, decode_hbm_share_bd, moe_bd_mlp_ms,
    moe_bd_mlp_roofline, moe_bd_route_share, bd_cache_copy_share,
)
# The lists the cell's name was appended to: the readers whose count of its
# work is right.  NOT `decode_ms_per_step`, `decode_loop_ms`,
# `sample_draw_ms`, `moe_decode_mlp_ms`, `moe_route_share`: they divide by
# new tokens a row or read `gen/decode_step`, and this program steps blocks.
APPENDED_TO = (
    "gen_tokens_per_s", "flash_fwd_share", "flash_bwd_share",
    "moe_experts_touched", "moe_local_rows_share",
    "moe_train_rows_gathered_share",
)


def _cfg(config=CONFIG):
    return run_mod.model_config(files.load_json("configs", f"{config}.json"))


def _recorded(cfg=None, peaks=PEAKS):
    with open(os.path.join(
            os.path.dirname(__file__), "data", "sdar_recorded_run.json")) as f:
        rec = json.load(f)
    run = run_mod.Run(
        cell_name=CELL, cell={"route": "static"}, config={}, traffic={},
        model_cfg=cfg or _cfg(), chips=1, device_kind="TPU v5 lite",
        peaks=peaks, seed=1, traced=True)
    run.steps = rec["steps"]
    run.trace = {k: rec[k] for k in (
        "traced_steps", "busy_s", "window_s", "scope_seconds",
        "op_seconds_scoped")}
    return run


def test_peaks_bd_counts_the_models_matmuls_to_the_digit():
    cfg = _cfg()
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 768
    assert peaks_bd.attn_params(cfg) == attn == 18_874_368
    assert peaks_bd.mlp_params(cfg) == 2048 * 128 + expert * 8 * 16 / 128
    assert peaks_bd.head_params(cfg) == 2048 * 18992
    own = sum(
        tfm.BRANCHES[b].matmul_params(cfg)
        for kind in cfg.plan.unit for b in kind) * 8 + 2048 * 18992
    assert own == peaks_bd.matmul_params(cfg)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 834_899_968


def test_peaks_bd_on_fixed_work():
    cfg = _cfg()
    # A 130-token prompt, 512 new: tail 2, 129 blocks, 258 + 129 forwards.
    assert peaks_bd.forwards_of(cfg, 130, 512) == (129, 258, 129)
    assert peaks_bd.forwards_of(cfg, 128, 512) == (128, 256, 128)
    assert peaks_bd.stream_slots(cfg, 642, 130) == (642, 516)
    assert peaks_bd.stream_slots(cfg, 640, 128) == (640, 512)
    # Two blocks, prompt of one: clean 4 + 8 pairs a token, masked 4 + 4.
    small = peaks_bd.stream_pairs(cfg, 8, 4)
    assert small == 4 * 4 + 4 * 8 + 4 * (4 + 4)
    layers = 2.0 * 8 * peaks_bd.layer_params(cfg)
    head = 2.0 * peaks_bd.head_params(cfg)
    pair = 4.0 * 32 * 128 * 8
    assert peaks_bd.flops_train(cfg, [8], [4]) == pytest.approx(
        3 * (layers * 12 + pair * small + head * 4))
    # Generation of one block behind a one-block prompt: prefill 4 tokens
    # (4 x 4 pairs), the first-block forward and T + 1 = 3 forwards of 4.
    got = peaks_bd.flops_generate(cfg, [4], [4])
    want = (layers * 4 + pair * 16 + 4 * (layers * 4 + head * 3)
            + pair * 4 * (8 * 3 + 8))
    assert got == pytest.approx(want)
    # Bytes of a denoising forward: 8 layers of attention weights, K and V
    # of the context and the block twice, the experts' parts, the head.
    rows, ctx = 64, 400
    per_slot = 2 * 4 * 128 * 2
    mlp = sum(by for _, by in peaks_bd.moe_layer_parts(cfg, rows * 4).values())
    assert peaks_bd.forward_bytes(cfg, [ctx] * rows) == pytest.approx(
        8 * (18_874_368 * 2 + (rows * ctx + 8 * rows) * per_slot + mlp)
        + 2048 * 18992 * 2 + rows * 4 * 18992 * 4)
    assert peaks_bd.forward_bytes(cfg, [ctx] * rows, head=False) < (
        peaks_bd.forward_bytes(cfg, [ctx] * rows))


def test_each_reader_on_the_recorded_run():
    run = _recorded()
    pool, pack = run.steps[-1]["pool"], run.steps[-1]["pack"]
    assert bd_tokens_per_forward.read(run) == pytest.approx(
        64 * 512 / (388 * 64))
    assert pool["bd/revealed_by_step"][:2] == pytest.approx(
        [2.0, 2.0], abs=0.02)
    assert pool["bd/denoise_forwards"] == 258 and pool["bd/blocks"] == 129
    assert bd_stream_overhead.read(run) == pytest.approx(
        (pack["bd/clean_slots"] + pack["bd/masked_slots"])
        / pack["bd/clean_slots"])
    assert 1.7 < bd_stream_overhead.read(run) < 1.9
    denoise = sum(run.trace["scope_seconds"]["gen/bd_denoise"].values())
    whole_denoise = sum(
        sum(v.values()) for k, v in run.trace["scope_seconds"].items()
        if f"/{k}/".startswith("/gen/bd_denoise/"))
    assert whole_denoise > denoise  # the layers' scopes lie under it
    assert bd_denoise_ms.read(run) == pytest.approx(
        1e3 * whole_denoise / run.trace["traced_steps"] / 258)
    assert 1.0 < bd_denoise_ms.read(run) < 20.0
    assert 15.0 < bd_commit_share.read(run) < 100.0 / 3
    for reader in SHARES:  # a share of a peak reads under 100
        assert 0.0 < reader.read(run) < 100.0, reader.__name__


def test_the_mlp_and_cache_copy_readers_on_the_recorded_run():
    """The routed MLP inside the loop's forwards (scoped parts + the
    ragged-dot kernels at the loop's rows, over every forward) and the
    slices of a layer's K and V out of the stacked cache."""
    run = _recorded()
    scopes = run.trace["scope_seconds"]
    steps, forwards = run.trace["traced_steps"], 388
    assert _bd.all_forwards(run) == forwards

    def under(*needles):
        return sum(
            sum(v.values()) for k, v in scopes.items()
            if k.split("/")[1] in ("bd_denoise", "bd_commit",
                                   "bd_first_block_logp")
            and any(f"/{n}/" in f"/{k}/" for n in needles))

    ragged = sum(
        v for k, v in run.trace["op_seconds_scoped"].items()
        if k.startswith("ragged-dot-none") and " bf16[2048," in k)
    assert ragged > 0
    whole = under("layer/mlp") + ragged
    assert moe_bd_mlp_ms.read(run) == pytest.approx(
        1e3 * whole / steps / forwards)
    assert 0.5 < moe_bd_mlp_ms.read(run) < bd_denoise_ms.read(run)
    route = under("layer/mlp/router", "layer/mlp/dispatch",
                  "layer/mlp/combine")
    assert moe_bd_route_share.read(run) == pytest.approx(
        100.0 * route / whole)
    assert 10.0 < moe_bd_route_share.read(run) < 90.0
    copies = sum(
        v for k, v in run.trace["op_seconds_scoped"].items()
        if " bf16[1,64,896,4,128] @gen/bd_" in k
        and k.rpartition("@")[2].count("/") == 1)
    assert copies > 0
    assert bd_cache_copy_share.read(run) == pytest.approx(
        100.0 * copies / steps / _bd.loop_seconds(run))
    assert 0.0 < bd_cache_copy_share.read(run) < 50.0
    # Another program that steps as many rows: the kernels cannot be told
    # apart, and the scoped parts are read alone.
    run.trace["op_seconds_scoped"][
        "fusion.1 fusion bf16[2048,768] @gen/decode_step/layer/mlp/experts:fwd"
    ] = 1.0
    assert moe_bd_mlp_ms.read(run) == pytest.approx(
        1e3 * under("layer/mlp") / steps / forwards)


def test_the_readers_say_nothing_without_the_scopes_or_counters():
    """The parent of PR 68 (no `gen/bd_*` scope, no `bd/` counter), an
    untraced run, and another model: None, never a raise."""
    from areal_tpu.models.config import tiny_config

    bare = _recorded()
    bare.trace = dict(bare.trace, scope_seconds={
        "gen/decode_step/layer/attn": {"fwd": 1.0, "recompute": 0, "bwd": 0}})
    for s in bare.steps:
        s["pool"] = {k: v for k, v in s["pool"].items()
                     if not k.startswith("bd/")}
        s["pack"] = {k: v for k, v in s["pack"].items()
                     if not k.startswith("bd/")}
    bare.trace["op_seconds_scoped"] = {
        "fusion.1 fusion bf16[64,2048] @gen/decode_step/layer/attn:fwd": 1.0}
    for reader in set(READERS) - {mfu_gen_bd, mfu_train_bd}:
        assert reader.read(bare) is None, reader.__name__
    untraced = _recorded()
    untraced.trace = None
    for reader in TRACED:
        assert reader.read(untraced) is None, reader.__name__
    other = _recorded(cfg=tiny_config())
    for reader in READERS:
        assert reader.read(other) is None, reader.__name__
    no_peaks = _recorded(peaks=None)
    for reader in SHARES:
        assert reader.read(no_peaks) is None, reader.__name__


def test_the_sdar_cell_lists_what_it_reports():
    spec = files.benchmark_json()
    assert spec["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "rollout64-512",
        "chips": 1, "why": spec["workloads"][-1]["why"],
    }
    assert len(spec["workloads"][-1]["why"]) <= 200
    assert spec["configs"][-1]["name"] == CONFIG
    assert spec["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "mask_token_id"]
    assert len(spec["configs"][-1]["why"]) <= 200
    assert len(spec["per_layer"]) == 128  # the contract's cap: none added
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in APPENDED_TO), m[
                "name"]
            if CELL in m["workloads"]:
                assert m["workloads"][-1] == CELL
    names = {m["name"] for m in spec["per_layer"]}
    for reader in READERS:
        assert reader.__name__.split(".")[-1] not in names
    cell, config, traffic = files.load_cell(CELL)
    assert (cell["route"], cell["timed_steps"], cell["traffic_seed"]) == (
        "static", 4, 68)
    assert config["benchmark"]["weights_seed"] == 68
    assert traffic == files.load_cell("q3next-rollout64-512")[2]
    from benchmark.traffic.math_prompts import quantile_lengths

    lens = quantile_lengths(traffic["prompt_len"], traffic["n_prompts"])
    assert min(lens) == 98 and max(lens) == 158
    assert {n % 4 for n in lens} == {2}  # every row's first block: a tail of 2

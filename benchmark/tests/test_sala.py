"""PR 55's configuration, cell and readers in the harness's own cases: the
new files load, `peaks_sala.py` on fixed work at the published widths
against hand arithmetic, each new reader on a recorded run (the program's
counters and the scopes' seconds as a traced run reduces them), that every
one says nothing for a program without the scopes or the counters (the
parent of PR 55) and for another cell's model, and the entries in
BENCHMARK.json where the issue put them."""
import pytest

from benchmark import files, peaks_sala
from benchmark import run as run_mod
from benchmark.metrics import (
    decode_hbm_share_sala, lightning_train_share, mfu_gen_sala,
    mfu_train_sala, sparse_decode_ms, sparse_read_share, sparse_train_share,
)
from benchmark.metrics._labels import GEN, TRAIN
from benchmark.tests.test_ledger_readers import QUIET, recorded

SALA_CELL = "sala-docrl8-longctx"
SALA_CONFIG = "minicpm-sala-l4-v8"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SALA_ENTRIES = [
    ("sparse_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("sparse_read_share", "%", "lower", "program_counter", "cache",
     "gen_tokens_per_s"),
    ("sparse_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("lightning_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("mfu_train_sala", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("mfu_gen_sala", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("decode_hbm_share_sala", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
]
SALA_READERS = (
    sparse_decode_ms, sparse_read_share, sparse_train_share,
    lightning_train_share, mfu_train_sala, mfu_gen_sala,
    decode_hbm_share_sala,
)
LENS = [10752] * 4 + [13312] * 4
PROMPTS = [10496] * 4 + [13056] * 4


def _sala_cfg():
    return run_mod.model_config(
        files.load_json("configs", f"{SALA_CONFIG}.json"))


def test_the_new_files_load():
    cell, config, traffic = files.load_cell(SALA_CELL)
    assert cell["route"] == "static" and cell["timed_steps"] == 3
    assert cell["traffic_seed"] == 55 and cell["chips"] == 1
    assert config["benchmark"]["weights_seed"] == 55
    assert config["benchmark"]["reference"] == "minicpm_sala"
    assert traffic["n_prompts"] * traffic["group"] == 8
    generator = files.load_module("traffic", traffic["generator"])
    lens = sorted(generator.quantile_lengths(traffic["prompt_len"], 2))
    assert lens == [10496, 13056]  # both past dense_len
    assert min(lens) >= config["sparse_config"]["dense_len"] == 8192
    assert 4 * sum(lens) + 8 * traffic["max_new_tokens"] == 96256
    rows = generator.generate(traffic, cell["traffic_seed"])
    assert sorted(len(r["prompt"]) for r in rows) == lens
    cfg = _sala_cfg()
    assert (cfg.n_sparse_layers, cfg.n_lightning_layers) == (1, 3)
    assert cfg.vocab_size == 9181 and cfg.pos_emb == "none"
    for name, *_ in SALA_ENTRIES:
        assert callable(files.load_module("metrics", name).read), name
    assert callable(
        files.load_module("references", "minicpm_sala").next_token_logprobs)


def test_peaks_sala_counts_the_selected_keys_by_hand():
    cfg = _sala_cfg()
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
    light = 5 * 4096 * 4096
    mlp = 3 * 4096 * 16384
    head = 4096 * 9181
    assert peaks_sala.attn_params(cfg) == attn == 52_428_800
    assert peaks_sala.lightning_params(cfg) == light == 83_886_080
    assert peaks_sala.mlp_params(cfg) == mlp == 201_326_592
    assert peaks_sala.matmul_params(cfg) == (
        attn + 3 * light + 4 * mlp + head) == 1_146_998_784
    assert peaks_sala.lightning_flops_per_token(cfg) == 4 * 32 * 128 * 128
    # A query past 4,096 keys of a long sequence: 64 blocks of 64, and a
    # compressed key every 16 tokens once 32 are there.
    assert peaks_sala.selected_keys(cfg, 13311, 13312) == 4096
    assert peaks_sala.selected_keys(cfg, 1000, 13312) == 1001
    assert peaks_sala.selected_keys(cfg, 8000, 8001) == 8001  # dense
    assert peaks_sala.visible_kernels(cfg, 13311, 13312) == 831
    assert peaks_sala.visible_kernels(cfg, 30, 13312) == 0
    assert peaks_sala.visible_kernels(cfg, 8000, 8001) == 0
    n = 13312
    chosen = 4096 * 4097 // 2 + (n - 4096) * 4096
    kernels = sum(max((t - 31) // 16 + 1, 0) for t in range(n))
    assert peaks_sala.sparse_flops(cfg, 0, n, n) == (
        4.0 * 4096 * chosen + 2.0 * 4096 * kernels)
    assert peaks_sala.flops_forward(cfg, [n]) == (
        2.0 * 1_146_998_784 * n + 4.0 * 4096 * chosen + 2.0 * 4096 * kernels
        + 3 * 4 * 32 * 128 * 128 * n)
    assert peaks_sala.flops_train(cfg, [n]) == 3 * peaks_sala.flops_forward(
        cfg, [n])
    # The selected keys are half of the causal half at this length.
    assert chosen / (n * (n + 1) / 2) == pytest.approx(0.52, abs=0.01)
    # One token through a cache of 13,312: the weights dominate, the state
    # of three layers and the chosen blocks ride beside them.
    state = 32 * 128 * 128 * 4
    row = 2 * 128 * 2
    assert peaks_sala.lightning_decode_bytes(cfg, 8) == light * 2 + 16 * state
    assert peaks_sala.sparse_decode_bytes(cfg, [13312] * 8) == (
        attn * 2 + 8 * (2 * row * 4096 + row * 831))
    whole = peaks_sala.decode_bytes(cfg, [13312] * 8)
    assert whole == (
        peaks_sala.sparse_decode_bytes(cfg, [13312] * 8)
        + 3 * peaks_sala.lightning_decode_bytes(cfg, 8)
        + (4 * mlp + head) * 2 + 2 * 8 * 9181 * 4)
    assert 2.2e9 < whole < 2.5e9
    # A new token costs what it costs in a whole forward pass.
    assert peaks_sala.flops_generate(cfg, [9000], [1]) == pytest.approx(
        peaks_sala.flops_forward(cfg, [9001]), rel=1e-3)


def _sala_run(scopes=None, pool=None, model=True):
    """Three timed steps of the cell's eight sequences on the static
    program: 256 decode iterations a step."""
    run = recorded(QUIET, walls=(12.0, 12.0, 12.0), pool=pool)
    run.cell_name = SALA_CELL
    run.cell = {"route": "static"}
    run.model_cfg = _sala_cfg() if model else run_mod.model_config(
        files.load_json("configs", "qwen2.5-math-1.5b.json"))
    run.peaks = PEAKS
    for s in run.steps:
        s.update(
            spans={GEN: 3.0, TRAIN: 8.0}, seq_lens=list(LENS),
            prompt_lens=list(PROMPTS),
            gen={"lanes_dispatched": 0, "serving_lane_budget": 0},
        )
    run.trace = None if scopes is None else {
        "scope_seconds": scopes, "traced_steps": 2, "busy_s": 20.0,
        "loop_seconds": {GEN: [1.28, 1.28]},
    }
    return run


SALA_POOL = {
    "sparse_keys_read": 256 * 8 * (4096 + 750.0),
    "sparse_keys_cached": 256 * 8 * 12032.0, "sparse_dense_rows": 0.0,
    "compressed_cache_bytes": 3_407_872, "lightning_state_bytes": 50_331_648,
}
SALA_SCOPES = {
    "gen/decode_step/layer/sparse_attn/select": {"fwd": 2 * 256 * 0.2e-3},
    "gen/decode_step/layer/sparse_attn/attend": {"fwd": 2 * 256 * 0.3e-3},
    "gen/decode_step/layer/lightning/recurrence": {"fwd": 2 * 256 * 0.4e-3},
    "gen/decode_step/layer/mlp": {"fwd": 2 * 256 * 3e-3},
    "train/grad/layer/sparse_attn/attend":
        {"fwd": 0.5, "recompute": 0.5, "bwd": 1.0},
    "train/grad/layer/lightning/recurrence":
        {"fwd": 0.25, "recompute": 0.25, "bwd": 0.5},
    "train/grad/layer/mlp": {"fwd": 1.25, "recompute": 1.25, "bwd": 2.5},
}


def test_each_sala_reader_on_a_recorded_run():
    cfg = _sala_cfg()
    run = _sala_run(SALA_SCOPES, SALA_POOL)
    assert sparse_decode_ms.read(run) == pytest.approx(0.5)
    assert sparse_read_share.read(run) == pytest.approx(
        100 * (4096 + 750) / 12032)
    assert sparse_train_share.read(run) == pytest.approx(25.0)
    assert lightning_train_share.read(run) == pytest.approx(12.5)
    assert mfu_train_sala.read(run) == pytest.approx(
        100 * peaks_sala.flops_train(cfg, LENS) / 8.0 / 197e12)
    gen = [l - p for l, p in zip(LENS, PROMPTS)]
    assert mfu_gen_sala.read(run) == pytest.approx(
        100 * peaks_sala.flops_generate(cfg, PROMPTS, gen) / 3.0 / 197e12)
    ctx = [p + 128 for p in PROMPTS]
    floor = peaks_sala.decode_bytes(cfg, ctx) / 819e9
    # 0.2 + 0.3 + 0.4 + 3 ms of an iteration under `gen/decode_step`; the
    # longest outermost loop (here 5 ms an iteration) is not asked.
    assert decode_hbm_share_sala.read(run) == pytest.approx(
        100 * floor * 1e3 / 3.9)
    for reader in SALA_READERS:  # a share, but for the milliseconds
        if reader is not sparse_decode_ms:
            assert 0 < reader.read(run) <= 100, reader.__name__


def test_the_sala_readers_say_nothing_for_a_program_without_the_names():
    """The parent of PR 55: no `sparse_*` counter, no such scope; another
    cell's model; an untraced run."""
    bare = {k: v for k, v in SALA_SCOPES.items()
            if "sparse_attn" not in k and "lightning" not in k}
    parent = _sala_run(bare, {"kv_cache_bytes": 1})
    for reader in (sparse_decode_ms, sparse_read_share, sparse_train_share,
                   lightning_train_share):
        assert reader.read(parent) is None, reader.__name__
    other = _sala_run(SALA_SCOPES, {"kv_cache_bytes": 1}, model=False)
    for reader in SALA_READERS:
        assert reader.read(other) is None, reader.__name__
    untraced = _sala_run(None, SALA_POOL)
    for reader in (sparse_decode_ms, sparse_train_share,
                   lightning_train_share, decode_hbm_share_sala):
        assert reader.read(untraced) is None, reader.__name__
    assert sparse_read_share.read(untraced) is not None  # a counter


def test_the_sala_entries_are_the_last_and_the_cell_lists_what_it_reports():
    spec = files.benchmark_json()
    assert [
        (m["name"], m["unit"], m["better"], m["source"], m["layer"],
         m["moves"]) for m in spec["per_layer"][-len(SALA_ENTRIES):]
    ] == SALA_ENTRIES
    for m in spec["per_layer"][-len(SALA_ENTRIES):]:
        assert m["workloads"] == [SALA_CELL]
    assert spec["workloads"][-1] == {
        "name": SALA_CELL, "config": SALA_CONFIG,
        "traffic": "rollout8-ctx9k-14k-256", "chips": 1,
        "why": spec["workloads"][-1]["why"],
    }
    assert spec["configs"][-1]["name"] == SALA_CONFIG
    assert spec["configs"][-1]["reduced"] == [
        "num_hidden_layers", "mixer_types", "vocab_size"]
    assert (len(spec["workloads"]), len(spec["configs"])) == (12, 10)
    assert len(spec["per_layer"]) <= 128
    reported = {m["name"] for m in files.metrics_for(SALA_CELL, traced=True)}
    assert {"decode_ms_per_step", "sample_draw_ms"} | {
        name for name, *_ in SALA_ENTRIES} <= reported
    # `decode_loop_ms` reads the generate request's longest outermost loop:
    # in this cell the prefill's scan over waves, so the cell is not listed.
    assert "decode_loop_ms" not in reported
    assert "gen_tokens_per_s" in {
        m["name"] for m in files.metrics_for(SALA_CELL, traced=False)}
    # The two list-less readers that find nothing in a cell without a flash
    # kernel: the list of the eleven cells before this one, and no other.
    before = [w["name"] for w in spec["workloads"][:-1]]
    for m in spec["per_layer"]:
        if m["name"] in ("flash_fwd_share", "flash_bwd_share"):
            assert m["workloads"] == before, m["name"]
    # No other cell reports the new readers.
    for other in spec["workloads"][:-1]:
        names = {m["name"] for m in files.metrics_for(other["name"], True)}
        assert not names & {name for name, *_ in SALA_ENTRIES}, other["name"]

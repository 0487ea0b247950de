"""PR 53's configuration, cell and readers in the harness's own cases:
`peaks_ssmd.py` on fixed work at the published widths, each new reader on
a recorded toy run (the program's counters and the scopes' seconds as a
traced run reduces them), that every one says nothing for a program
without the scopes or the counters (the parent of PR 53), and the entries
in BENCHMARK.json where the issue put them.  The cell's `--cpu-rehearsal`
is a case of `test_harness.test_cpu_rehearsal`, which takes its cells from
BENCHMARK.json."""
import pytest

from benchmark import files, peaks_ssmd
from benchmark import run as run_mod
from benchmark.metrics import (
    decode_hbm_share_ssmd, mfu_gen_ssmd, mfu_train_ssmd, ssm_serving_ms,
    ssm_serving_roofline, ssm_slot_state_share, ssm_train_mfu_ssmd,
    ssm_train_share_ssmd,
)
from benchmark.metrics._labels import GEN, TRAIN
from benchmark.tests.test_ledger_readers import QUIET, recorded

CELL = "granite4hm-serving-waves"
CONFIG = "granite-4.0-h-micro-l10"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
ENTRIES = [
    ("mfu_train_ssmd", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("mfu_gen_ssmd", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("decode_hbm_share_ssmd", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("ssm_serving_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("ssm_serving_roofline", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("ssm_slot_state_share", "%", "lower", "program_counter", "generator",
     "gen_tokens_per_s"),
    ("ssm_train_share_ssmd", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("ssm_train_mfu_ssmd", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
]
READERS = (
    mfu_train_ssmd, mfu_gen_ssmd, decode_hbm_share_ssmd, ssm_serving_ms,
    ssm_serving_roofline, ssm_slot_state_share, ssm_train_share_ssmd,
    ssm_train_mfu_ssmd,
)


def _cfg():
    return run_mod.model_config(files.load_json("configs", f"{CONFIG}.json"))


def test_peaks_ssmd_counts_a_mixer_and_a_dense_mlp_a_layer():
    cfg = _cfg()
    ssm = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    head = 2048 * 100352
    assert peaks_ssmd.ssm_params(cfg) == ssm == 25_821_184
    assert peaks_ssmd.attn_params(cfg) == attn == 10_485_760
    assert peaks_ssmd.mlp_params(cfg) == mlp == 50_331_648
    assert peaks_ssmd.matmul_params(cfg) == (
        9 * ssm + attn + 10 * mlp + head) == 951_713_792
    # The recurrence as DEFINED: 5 d_inner N a token and layer.
    assert peaks_ssmd.ssm_flops_per_token(cfg) == 5 * 4096 * 128
    n = 100
    assert peaks_ssmd.flops_forward(cfg, [n]) == (
        2.0 * 951_713_792 * n + 9 * 5 * 4096 * 128 * n
        + 2.0 * 32 * 64 * n * n)
    assert peaks_ssmd.flops_train(cfg, [n]) == 3 * peaks_ssmd.flops_forward(
        cfg, [n])
    # One token at a time costs what the same tokens cost whole, up to the
    # attention term's discretisation.
    assert peaks_ssmd.flops_generate(cfg, [50], [50]) == pytest.approx(
        peaks_ssmd.flops_forward(cfg, [100]), rel=1e-3)
    # An inner step at 64 live slots: 2 x 1.21 GB of state beside 0.47 GB
    # of the mixers' weights; every weight once is 1.90 GB.
    state = 9 * 64 * 4096 * 128 * 4
    tails = 9 * 64 * 3 * 4352 * 2
    small = 9 * (5 * 4352 + 4096 + 3 * 64) * 2
    assert state == 1_207_959_552
    assert peaks_ssmd.ssm_serving_bytes(cfg, 64) == (
        9 * ssm * 2 + small + 2 * state + 2 * tails)
    assert peaks_ssmd.ssm_serving_bytes(cfg, 0) == 9 * ssm * 2 + small
    whole = peaks_ssmd.serving_step_bytes(cfg, 64, 96, 6400)
    assert whole == (
        peaks_ssmd.ssm_serving_bytes(cfg, 64) + (attn + 10 * mlp + head) * 2
        + 2 * 8 * 64 * 2 * 6400 + 2 * 96 * 100352 * 4)
    assert 2 * state / whole > 0.5  # the state is over half a step's bytes


def _run(scopes=None, pool=None, model=True):
    """Four timed steps of 96 sequences (prompt 64, 128 new) on the
    serving plane: 350 inner steps of 96 lanes a step."""
    run = recorded(QUIET, pool=pool)
    run.cell_name = CELL
    run.model_cfg = _cfg() if model else run_mod.model_config(
        files.load_json("configs", "qwen2.5-math-1.5b.json"))
    run.peaks = PEAKS
    for s in run.steps:
        s.update(
            spans={GEN: 3.5, TRAIN: 1.0},
            seq_lens=[192] * 96, prompt_lens=[64] * 96,
            gen={"lanes_dispatched": 350 * 96, "serving_lane_budget": 96,
                 "lanes_live": 350 * 52},
        )
    run.trace = None if scopes is None else {
        "scope_seconds": scopes, "traced_steps": 2, "busy_s": 9.0}
    return run


POOL = {
    "chunks": 11, "ssm_live_slot_chunks": 11 * 48, "pages_live": 350 * 100,
    "page_size": 128, "ssm_state_bytes": 1_207_959_552,
    "ssm_conv_bytes": 15_040_512, "peak_allocated_bytes": 50_331_648,
}
SCOPES = {
    "gen/serving_chunk/gen/decode_step/layer/ssm/ssm_ragged/ssd_scan":
        {"fwd": 2 * 350 * 3e-3},
    "gen/serving_chunk/gen/decode_step/layer/ssm/in_proj":
        {"fwd": 2 * 350 * 1e-3},
    "gen/serving_chunk/gen/decode_step/layer/mlp": {"fwd": 2 * 350 * 2e-3},
    "gen/serving_chunk/sample_draw": {"fwd": 2 * 350 * 2e-3},
    "train/grad/layer/ssm/ssd_scan": {"fwd": 0.2, "recompute": 0.2, "bwd": 0.4},
    "train/grad/layer/mlp": {"fwd": 0.2, "recompute": 0.2, "bwd": 0.4},
}


def test_each_new_reader_on_a_recorded_toy_run():
    cfg = _cfg()
    run = _run(SCOPES, POOL)
    # 4 + 2 + 2 ms of an inner step under the chunk, 4 of them the mixers'.
    assert ssm_serving_ms.read(run) == pytest.approx(4.0)
    floor = peaks_ssmd.ssm_serving_bytes(cfg, 48) / 819e9
    assert ssm_serving_roofline.read(run) == pytest.approx(
        100 * floor * 1e3 / 4.0)
    whole = peaks_ssmd.serving_step_bytes(cfg, 48, 52, 100 * 128) / 819e9
    assert decode_hbm_share_ssmd.read(run) == pytest.approx(
        100 * whole * 1e3 / 8.0)
    slots = 1_207_959_552 + 15_040_512
    assert ssm_slot_state_share.read(run) == pytest.approx(
        100 * slots / (slots + 50_331_648))
    assert ssm_train_share_ssmd.read(run) == pytest.approx(50.0)
    tokens = 96 * 192
    assert ssm_train_mfu_ssmd.read(run) == pytest.approx(
        100 * peaks_ssmd.ssm_train_flops(cfg, tokens) / 0.4 / 197e12)
    assert mfu_train_ssmd.read(run) == pytest.approx(
        100 * peaks_ssmd.flops_train(cfg, [192] * 96) / 1.0 / 197e12)
    assert mfu_gen_ssmd.read(run) == pytest.approx(
        100 * peaks_ssmd.flops_generate(cfg, [64] * 96, [128] * 96)
        / 3.5 / 197e12)
    for reader in READERS:  # a share of a peak or of a roofline
        if reader is not ssm_serving_ms:
            assert 0 < reader.read(run) <= 100, reader.__name__


def test_the_new_readers_say_nothing_for_a_program_without_the_names():
    """The parent of PR 53: no `ssm_*` counter, no `layer/ssm` under the
    chunk; another model; an untraced run."""
    bare = {k: v for k, v in SCOPES.items() if "layer/ssm" not in k}
    parent = _run(bare, {"chunks": 11, "pages_live": 1, "page_size": 128})
    for reader in READERS:
        if reader not in (mfu_train_ssmd, mfu_gen_ssmd):  # the host's clock
            assert reader.read(parent) is None, reader.__name__
    other = _run(SCOPES, POOL, model=False)
    for reader in READERS:  # but the two that read a name alone
        if reader not in (ssm_serving_ms, ssm_slot_state_share):
            assert reader.read(other) is None, reader.__name__
    untraced = _run(None, POOL)
    for reader in (decode_hbm_share_ssmd, ssm_serving_ms,
                   ssm_serving_roofline, ssm_train_share_ssmd,
                   ssm_train_mfu_ssmd):
        assert reader.read(untraced) is None, reader.__name__


def test_the_entries_are_the_last_and_the_cell_lists_what_it_reports():
    spec = files.benchmark_json()
    assert [
        (m["name"], m["unit"], m["better"], m["source"], m["layer"],
         m["moves"]) for m in spec["per_layer"][-len(ENTRIES):]
    ] == ENTRIES
    for m in spec["per_layer"][-len(ENTRIES):]:
        assert m["workloads"] == [CELL]
    assert spec["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "waves-over-slots",
        "chips": 1, "why": spec["workloads"][-1]["why"],
    }
    assert spec["configs"][-1]["reduced"] == ["num_hidden_layers", "layer_types"]
    cell, _, traffic = files.load_cell(CELL)
    assert cell["route"] == "serving" and cell["timed_steps"] == 6
    assert cell["traffic_seed"] == 53
    # The traffic file is q1p5b-serving-waves', unedited.
    assert files.load_cell("q1p5b-serving-waves")[2] == traffic
    assert traffic["n_prompts"] * traffic["group"] == 96
    reported = {m["name"] for m in files.metrics_for(CELL, traced=True)}
    assert {
        "decode_ms_per_step", "lane_occupancy", "chunk_host_ms",
        "admit_wait_s", "admit_passed_over", "paged_attn_live_page_share",
        "sample_draw_ms",
    } <= reported
    assert "gen_tokens_per_s" in {
        m["name"] for m in files.metrics_for(CELL, traced=False)}

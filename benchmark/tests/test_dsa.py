"""PR 64's configuration, cell and readers in the harness's own cases:
`peaks_dsa.py` on fixed work at the published widths and, to the digit,
against the model's own counts; each reader on a recorded run (the
program's counters and the scopes' seconds as a traced run reduces them);
that every one says nothing for a program without the scopes or the
counters (the parent of PR 64) and for another model; and the entries in
BENCHMARK.json.  `per_layer` stands at the contract's 128 entries, so the
nine readers' entries are NOT there (PERF.md section 7): the files wait
for a benchmark PR to list them."""
import jax
import pytest

from areal_tpu.models import transformer as tfm
from benchmark import files, peaks_dsa
from benchmark import run as run_mod
from benchmark.metrics import (
    decode_hbm_share_dsa, dsa_index_decode_ms, dsa_index_roofline,
    dsa_selected_share, dsa_train_share, index_cache_share,
    latent_window_decode_ms, mfu_gen_dsa, mfu_train_dsa,
)
from benchmark.metrics._labels import GEN, TRAIN
from benchmark.tests.test_ledger_readers import QUIET, recorded

CELL = "dots3n-docrl8-longctx"
CONFIG = "dots3-note-prev-l5-e8-h8"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = (
    dsa_index_decode_ms, dsa_index_roofline, dsa_selected_share,
    index_cache_share, dsa_train_share, latent_window_decode_ms,
    mfu_train_dsa, mfu_gen_dsa, decode_hbm_share_dsa,
)
APPENDED_TO = (
    "gen_tokens_per_s", "sample_draw_ms", "decode_ms_per_step",
    "flash_fwd_share", "flash_bwd_share", "moe_experts_touched",
    "moe_decode_mlp_ms", "moe_route_share", "moe_local_rows_share",
    "moe_train_rows_gathered_share", "flash_window_live_tile_share",
)
SEQ_LENS = [10752] * 4 + [13312] * 4
PROMPT_LENS = [10496] * 4 + [13056] * 4


def _cfg(config=CONFIG):
    return run_mod.model_config(files.load_json("configs", f"{config}.json"))


def test_peaks_dsa_counts_the_models_matmuls_to_the_digit():
    """Of the configuration's 1,390,831,104 parameters those in a token's
    matmuls: every leaf but the embedding (a lookup), the norms and the
    router's bias — with the held experts at their share of a token's
    choices (8 x 8 / 256 of an expert, not 8)."""
    cfg = _cfg()
    full = (5120 * 1024 + 1024 * 16 * 192 + 5120 * 576
            + 512 * 16 * 256 + 16 * 128 * 5120 + 5120 * 16)
    index = 1024 * 64 * 128 + 5120 * (128 + 64)
    sliding = (5120 * 1024 + 1024 * 8 * 256 + 5120 * 1088
               + 1024 * 8 * 320 + 8 * 128 * 5120 + 5120 * 8)
    assert peaks_dsa.index_params(cfg) == index == 9_371_648
    assert peaks_dsa.mixer_params(cfg, "F") == full + index == 33_374_208
    assert peaks_dsa.mixer_params(cfg, "S") == sliding == 20_815_872
    expert = 3 * 5120 * 1536
    assert peaks_dsa.moe_params(cfg) == (
        5120 * 256 + expert + expert * 8 * 8 / 256)
    dense, head = 3 * 5120 * 13824, 5120 * 19008
    assert peaks_dsa.matmul_params(cfg) == (
        2 * (full + index) + 3 * sliding + dense
        + 4 * peaks_dsa.moe_params(cfg) + head)
    # The model's own: the records' counts a layer.
    own = sum(
        tfm.BRANCHES[b].matmul_params(cfg)
        for kind in cfg.plan.prefix + cfg.plan.unit for b in kind) + head
    assert own == peaks_dsa.matmul_params(cfg)
    # ... and the leaves `init_params` allocates: all eight held experts.
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    matrices = sum(
        x.size for n, x in shapes["blocks"].items()
        if x.ndim >= 3 or n.removeprefix("dense_") in (
            "idx_q", "idx_k", "idx_w")) + shapes["lm_head"].size
    assert matrices == peaks_dsa.matmul_params(cfg) + 4 * (
        8 - 8 * 8 / 256) * expert
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_390_831_104


def test_peaks_dsa_on_fixed_work():
    cfg = _cfg()
    n = 3000  # past index_topk 2,048 and the window of 513
    vis = n * (n + 1) / 2
    sel = 2048 * 2049 / 2 + (n - 2048) * 2048
    win = 513 * 514 / 2 + (n - 513) * 513
    assert peaks_dsa.visible_keys(0, n) == vis
    assert peaks_dsa.selected_keys(cfg, 0, n) == sel
    assert peaks_dsa.window_keys(cfg, 0, n) == win
    mixing = (2 * (2.0 * 64 * 128 * vis + 2.0 * 16 * (192 + 128) * sel)
              + 3 * 2.0 * 8 * (256 + 128) * win)
    assert peaks_dsa.mixing_flops(cfg, 0, n) == mixing
    params = peaks_dsa.matmul_params(cfg)
    assert peaks_dsa.flops_forward(cfg, [n]) == 2.0 * params * n + mixing
    once = 2 * (2.0 * 9_371_648 * n + 2.0 * 64 * 128 * vis)
    assert peaks_dsa.flops_train(cfg, [n]) == pytest.approx(
        3 * (peaks_dsa.flops_forward(cfg, [n]) - once) + once)
    assert peaks_dsa.flops_generate(cfg, [2900], [100]) == pytest.approx(
        peaks_dsa.flops_forward(cfg, [n]), rel=1e-9)
    # A decode iteration at the cell's 8 rows: the index keys of every
    # visible slot outweigh the selected latent rows.
    ctx = [10624] * 4 + [13184] * 4
    keys = sum(ctx) * 128 * 2
    rows = 8 * 2048 * 576 * 2
    ring = 8 * 513 * 1088 * 2
    assert peaks_dsa.index_decode_bytes(cfg, ctx) == 9_371_648 * 2 + keys
    assert peaks_dsa.selected_decode_bytes(cfg, ctx) == rows
    assert peaks_dsa.ring_decode_bytes(cfg, ctx) == ring
    assert keys > rows  # 24.4 MB against 18.9 MB a full layer
    assert peaks_dsa.cache_bytes(cfg, 8, 13568) == (
        2 * 8 * 13568 * 576 * 2, 2 * 8 * 13568 * 128 * 2,
        3 * 8 * 513 * 1088 * 2)
    weights = peaks_dsa.decode_bytes(cfg, ctx) - (
        2 * (keys + rows) + 3 * ring + 2 * 8 * 19008 * 4)
    assert 1.0e9 < weights < 1.5e9  # bf16 weights a step reads


def _run(scopes=None, pool=None, stats=None, config=CONFIG):
    """Three timed steps of the cell's 8 sequences on the static decode
    program."""
    run = recorded(
        dict(QUIET, **(stats or {})), walls=(9.0, 9.0, 9.0), pool=pool)
    run.cell_name = CELL
    run.cell = {"route": "static"}
    run.model_cfg = _cfg(config)
    run.peaks = PEAKS
    for s in run.steps:
        s.update(
            spans={GEN: 4.0, TRAIN: 5.0},
            seq_lens=list(SEQ_LENS), prompt_lens=list(PROMPT_LENS),
            gen={"lanes_dispatched": 0, "serving_lane_budget": 0},
        )
    run.trace = None if scopes is None else {
        "scope_seconds": scopes, "traced_steps": 2, "busy_s": 17.0,
        "loop_seconds": {GEN: [2.0, 2.0]}}
    return run


POOL = {
    "latent_cache_bytes": 250_085_376, "index_cache_bytes": 55_574_528,
    "latent_ring_bytes": 26_787_840, "latent_ring_rows": 513,
    "select_on_kernel": 1,
    "latent_rows_read": 2 * 8 * 256 * 2048.0,
    "latent_rows_visible": 2 * 256 * (4 * 10624.5 + 4 * 13184.5),
    "index_keys_scored": 2 * 256 * (4 * 10624.5 + 4 * 13184.5),
}
_DEC = "gen/decode_step/layer/"
SCOPES = {  # seconds over the two traced steps
    _DEC + "latent_attn/indexer/proj": {"fwd": 0.02},
    _DEC + "latent_attn/indexer/score": {"fwd": 0.08},
    _DEC + "latent_attn/indexer/topk": {"fwd": 0.06},
    _DEC + "latent_attn/attend": {"fwd": 0.05},
    _DEC + "latent_window/attend": {"fwd": 0.03},
    _DEC + "mlp": {"fwd": 0.8},
    "train/grad/layer/latent_attn/indexer/score":
        {"fwd": 0.5, "recompute": 0.5},
    "train/grad/layer/latent_attn/attend":
        {"fwd": 0.3, "recompute": 0.3, "bwd": 0.9},
    "train/grad/layer/mlp": {"fwd": 1.0, "recompute": 1.0, "bwd": 2.0},
}


def test_each_new_reader_on_a_recorded_run():
    cfg = _cfg()
    run = _run(SCOPES, POOL)
    steps = 256  # decode iterations a step; the scopes' seconds are two steps'
    assert dsa_index_decode_ms.read(run) == pytest.approx(
        1e3 * 0.16 / 2 / steps)
    assert latent_window_decode_ms.read(run) == pytest.approx(
        1e3 * 0.03 / 2 / steps)
    assert dsa_selected_share.read(run) == pytest.approx(
        100 * 8 * 2048 / (4 * 10624.5 + 4 * 13184.5))
    assert 15 < dsa_selected_share.read(run) < 20
    assert index_cache_share.read(run) == pytest.approx(
        100 * 55_574_528 / (250_085_376 + 55_574_528 + 26_787_840))
    assert dsa_train_share.read(run) == pytest.approx(100 * 2.5 / 6.5)
    ctx = [10624] * 4 + [13184] * 4
    floor = 2 * peaks_dsa.index_decode_bytes(cfg, ctx) / 819e9
    assert dsa_index_roofline.read(run) == pytest.approx(
        100 * floor * 1e3 / (1e3 * 0.16 / 2 / steps))
    assert mfu_train_dsa.read(run) == pytest.approx(
        100 * peaks_dsa.flops_train(cfg, SEQ_LENS) / 5.0 / 197e12)
    assert mfu_gen_dsa.read(run) == pytest.approx(
        100 * peaks_dsa.flops_generate(cfg, PROMPT_LENS, [256] * 8)
        / 4.0 / 197e12)
    floor = peaks_dsa.decode_bytes(cfg, ctx) / 819e9
    assert decode_hbm_share_dsa.read(run) == pytest.approx(
        100 * floor * 1e3 / (1e3 * 1.04 / 2 / steps))
    for reader in (dsa_index_roofline, dsa_selected_share, index_cache_share,
                   dsa_train_share, mfu_train_dsa, mfu_gen_dsa,
                   decode_hbm_share_dsa):  # a share of a peak or of a whole
        assert 0 < reader.read(run) <= 100, reader.__name__


def test_the_new_readers_say_nothing_for_a_program_without_the_names():
    """The parent of PR 64 cannot build the configuration; a program that
    could but kept no such scope or counter, another model (the latent
    twin without an indexer), an untraced run."""
    bare = {k: v for k, v in SCOPES.items() if "latent_" not in k}
    parent = _run(bare, {"latent_cache_bytes": 1})
    for reader in (dsa_index_decode_ms, dsa_index_roofline,
                   dsa_selected_share, index_cache_share, dsa_train_share,
                   latent_window_decode_ms):
        assert reader.read(parent) is None, reader.__name__
    twin = _run(SCOPES, POOL, config="glm-4.7-flash-l7-e8")
    for reader in (dsa_index_decode_ms, dsa_index_roofline, dsa_train_share,
                   latent_window_decode_ms, mfu_train_dsa, mfu_gen_dsa,
                   decode_hbm_share_dsa):
        assert reader.read(twin) is None, reader.__name__
    untraced = _run(None, POOL)
    for reader in (dsa_index_decode_ms, dsa_index_roofline, dsa_train_share,
                   latent_window_decode_ms, decode_hbm_share_dsa):
        assert reader.read(untraced) is None, reader.__name__


def test_the_dots3_cell_lists_what_it_reports():
    spec = files.benchmark_json()
    assert spec["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "rollout8-ctx9k-14k-256",
        "chips": 1, "why": spec["workloads"][-1]["why"],
    }
    assert spec["configs"][-1]["name"] == CONFIG
    assert spec["configs"][-1]["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads",
        "swa_num_attention_heads", "swa_num_key_value_heads", "vocab_size"]
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
        "static", 3, 64)
    assert config["benchmark"]["weights_seed"] == 64
    assert traffic == files.load_cell("sala-docrl8-longctx")[2]

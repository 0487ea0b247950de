"""PR 59's configuration, cell and readers in the harness's own cases:
`peaks_gdnd.py` on fixed work at the published widths and, to the digit,
against the model's own counts; each reader on a recorded toy run (the
program's counters and the scopes' seconds as a traced run reduces them);
that every one says nothing for a program without the scopes or the
counters (the parent of PR 59) and for another model; and the entries in
BENCHMARK.json.  `per_layer` stood at the contract's 128 entries before
this PR, so the six readers' entries are NOT there (PERF.md section 7):
the files wait for a benchmark PR to list them.  The cell's
`--cpu-rehearsal` is a case of `test_harness.test_cpu_rehearsal`, which
takes its cells from BENCHMARK.json."""
import jax
import pytest

from areal_tpu.models import transformer as tfm
from benchmark import files, peaks_gdnd, peaks_hybrid
from benchmark import run as run_mod
from benchmark.metrics import (
    decode_hbm_share_gdnd, gdn_kernel_forms, gdn_state_share, mfu_gen_gdnd,
    mfu_train_gdnd, post_norm_share,
)
from benchmark.metrics._labels import GEN, TRAIN
from benchmark.tests.test_ledger_readers import QUIET, recorded

CELL = "olmoh-rollout64-512"
CONFIG = "olmo-hybrid-7b-l4-v8"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = (
    mfu_train_gdnd, mfu_gen_gdnd, decode_hbm_share_gdnd, post_norm_share,
    gdn_state_share, gdn_kernel_forms,
)
APPENDED_TO = (
    "gen_tokens_per_s", "decode_ms_per_step", "decode_loop_ms",
    "sample_draw_ms", "flash_fwd_share", "flash_bwd_share", "gdn_decode_ms",
    "gdn_decode_roofline", "gdn_train_share", "gdn_train_mfu",
    "gdn_delta_rule_share",
)


def _cfg(config=CONFIG):
    return run_mod.model_config(files.load_json("configs", f"{config}.json"))


def test_peaks_gdnd_counts_the_models_matmuls_to_the_digit():
    """The yardstick: of the configuration's 928,862,196 parameters those
    in a token's matmuls — every leaf but the embedding (a lookup), the
    norms, the conv's taps, A_log and dt_bias."""
    cfg = _cfg()
    linear = 3840 * (2 * 2880 + 5760 + 5760 + 60) + 5760 * 3840
    full = 4 * 3840 * 3840
    mlp = 3 * 3840 * 11008
    head = 3840 * 12544
    assert peaks_gdnd.linear_attn_params(cfg) == linear == 88_704_000
    assert peaks_gdnd.full_attn_params(cfg) == full == 58_982_400
    assert peaks_gdnd.mlp_params(cfg) == mlp == 126_812_160
    assert peaks_gdnd.matmul_params(cfg) == (
        3 * linear + full + 4 * mlp + head) == 880_512_000
    # The model's own: the records' counts a layer (a recurrence there as
    # its 3 d_k d_v multiply-adds a head) ...
    rule = 3 * 30 * 96 * 192
    own = sum(
        tfm.BRANCHES[b].matmul_params(cfg)
        for kind in cfg.plan.unit for b in kind) - 3 * rule + head
    assert own == peaks_gdnd.matmul_params(cfg)
    # ... and the leaves `init_params` allocates.
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    blocks = {n: x for n, x in shapes["blocks"].items()
              if x.ndim >= 3 and n != "la_conv"}
    assert sum(x.size for x in blocks.values()) + shapes[
        "lm_head"].size == peaks_gdnd.matmul_params(cfg)
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == 928_862_196
    assert total - peaks_gdnd.matmul_params(cfg) == (
        head + 3 * (4 * 11520 + 60 + 192) + 2 * 3840 + 8 * 3840 + 3840)


def test_peaks_gdnd_on_fixed_work():
    cfg = _cfg()
    n = 100
    rule = 7 * 30 * 96 * 192
    assert peaks_gdnd.delta_rule_flops_per_token(cfg) == rule
    assert peaks_gdnd.flops_forward(cfg, [n]) == (
        2.0 * 880_512_000 * n + 3 * rule * n + 2.0 * 30 * 128 * n * n)
    assert peaks_gdnd.flops_train(cfg, [n]) == 3 * peaks_gdnd.flops_forward(
        cfg, [n])
    assert peaks_gdnd.flops_generate(cfg, [50], [50]) == pytest.approx(
        peaks_gdnd.flops_forward(cfg, [100]), rel=1e-3)
    # A decode step at 64 rows: every weight once (1.76 GB without the
    # embedding), 2 x 0.42 GB of fp32 state, the tails, and the one full
    # layer's K/V at 30 heads: 15,360 B a token.
    state = 3 * 64 * 30 * 96 * 192 * 4
    tails = 3 * 64 * 3 * 11520 * 2
    weights = (880_512_000 + 3 * 4 * 11520) * 2
    assert state == 424_673_280
    assert peaks_gdnd.gdn_decode_bytes(cfg, 64) == (
        3 * (88_704_000 + 4 * 11520) * 2 + 2 * state + 2 * tails)
    ctx = [400.0] * 64
    assert peaks_gdnd.decode_bytes(cfg, ctx) == (
        weights + 2 * state + 2 * tails + 15360 * 400 * 64)
    # The mixers' counts are peaks_hybrid's, imported and not copied.
    assert peaks_gdnd.gdn_decode_bytes is peaks_hybrid.gdn_decode_bytes
    assert peaks_gdnd.gdn_train_flops is peaks_hybrid.gdn_train_flops


def _run(scopes=None, pool=None, stats=None, config=CONFIG, loops=None):
    """Four timed steps of 64 sequences (prompt 128, 512 new) on the static
    decode program."""
    run = recorded(dict(QUIET, **(stats or {})), pool=pool)
    run.cell_name = CELL
    run.cell = {"route": "static"}
    run.model_cfg = _cfg(config)
    run.peaks = PEAKS
    for s in run.steps:
        s.update(
            spans={GEN: 4.0, TRAIN: 3.0},
            seq_lens=[640] * 64, prompt_lens=[128] * 64,
            gen={"lanes_dispatched": 0, "serving_lane_budget": 0},
        )
    run.trace = None if scopes is None else {
        "scope_seconds": scopes, "traced_steps": 2, "busy_s": 13.0,
        "loop_seconds": {GEN: loops or [512 * 6e-3, 512 * 6e-3]}}
    return run


POOL = {"kv_cache_bytes": 660_602_880, "state_cache_bytes": 437_944_320,
        "gdn_step_on_kernel": 0}
STATS = {"actor_train/linear_attn/rule_on_kernel": 1.0}
SCOPES = {
    "train/grad/layer/post_norm": {"fwd": 0.02, "recompute": 0.02, "bwd": 0.06},
    "train/grad/layer/linear_attn/delta_rule":
        {"fwd": 0.3, "recompute": 0.3, "bwd": 0.6},
    "train/grad/layer/mlp": {"fwd": 0.7, "recompute": 0.7, "bwd": 1.3},
}


def test_each_new_reader_on_a_recorded_toy_run():
    cfg = _cfg()
    run = _run(SCOPES, POOL, STATS)
    assert post_norm_share.read(run) == pytest.approx(100 * 0.05 / 2.0)
    assert gdn_state_share.read(run) == pytest.approx(
        100 * 437_944_320 / (437_944_320 + 660_602_880))
    assert gdn_kernel_forms.read(run) == 1.0  # the sweep, not the step
    assert mfu_train_gdnd.read(run) == pytest.approx(
        100 * peaks_gdnd.flops_train(cfg, [640] * 64) / 3.0 / 197e12)
    assert mfu_gen_gdnd.read(run) == pytest.approx(
        100 * peaks_gdnd.flops_generate(cfg, [128] * 64, [512] * 64)
        / 4.0 / 197e12)
    floor = peaks_gdnd.decode_bytes(cfg, [128 + 256.0] * 64) / 819e9
    assert decode_hbm_share_gdnd.read(run) == pytest.approx(
        100 * floor * 1e3 / 6.0)
    for reader in READERS:
        if reader is not gdn_kernel_forms:  # a share of a peak or a whole
            assert 0 < reader.read(run) <= 100, reader.__name__


def test_the_new_readers_say_nothing_for_a_program_without_the_names():
    """The parent of PR 59: no `layer/post_norm` scope, neither counter of
    the rule's forms; another model (the MoE twin); an untraced run."""
    bare = {k: v for k, v in SCOPES.items() if "post_norm" not in k}
    parent = _run(bare, {"kv_cache_bytes": 1, "state_cache_bytes": 1})
    assert post_norm_share.read(parent) is None
    assert gdn_kernel_forms.read(parent) is None
    twin = _run(SCOPES, POOL, STATS, config="qwen3-next-80b-a3b-l4-e64")
    for reader in (mfu_train_gdnd, mfu_gen_gdnd, decode_hbm_share_gdnd):
        assert reader.read(twin) is None, reader.__name__
    dense = _run(SCOPES, {}, config="qwen2.5-math-1.5b")
    for reader in (mfu_train_gdnd, mfu_gen_gdnd, decode_hbm_share_gdnd,
                   gdn_state_share, gdn_kernel_forms):
        assert reader.read(dense) is None, reader.__name__
    untraced = _run(None, POOL, STATS)
    for reader in (decode_hbm_share_gdnd, post_norm_share):
        assert reader.read(untraced) is None, reader.__name__


def test_the_cell_lists_what_it_reports():
    spec = files.benchmark_json()
    assert spec["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "rollout64-512",
        "chips": 1, "why": spec["workloads"][-1]["why"],
    }
    assert spec["configs"][-1]["name"] == CONFIG
    assert spec["configs"][-1]["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert len(spec["per_layer"]) == 128  # the contract's cap: none added
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in APPENDED_TO:
            assert m["workloads"][-1] == CELL, m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    cell, config, traffic = files.load_cell(CELL)
    assert cell["route"] == "static" and cell["traffic_seed"] == 59
    assert config["benchmark"]["weights_seed"] == 59
    # The traffic file is q3next-rollout64-512's, unedited.
    assert files.load_cell("q3next-rollout64-512")[2] == traffic
    reported = {m["name"] for m in files.metrics_for(CELL, traced=True)}
    assert set(APPENDED_TO[1:]) <= reported
    assert not {m for m in reported if m.startswith("moe_")
                or m.endswith("_hybrid")}

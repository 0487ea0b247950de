"""`moe_train_rows_gathered_share` (PR 41) on a recorded `Run`: the
trainer's stat at the median step, None for a program that keeps none, and
its entry in BENCHMARK.json where the issue put it."""
from benchmark import files
from benchmark.metrics import moe_train_rows_gathered_share as reader
from benchmark.tests.test_ledger_readers import QUIET, recorded

KEY = "actor_train/moe/rows_gathered_share"
SHARE_CELLS = [
    "q3next-rollout64-512", "glm47f-rollout64-1k", "nemo3n-rollout64-512"]


def test_it_reads_the_trainers_stat_at_the_median_step():
    assert reader.read(recorded(dict(QUIET, **{KEY: 25.0}))) == 25.0
    # An overflow in one layer of four, in one micro-batch of two, in one
    # step of four: that step is not the median.
    stats = [dict(QUIET, **{KEY: v}) for v in (25.0, 25.0, 34.375, 25.0)]
    assert reader.read(recorded(stats)) == 25.0
    stats = [dict(QUIET, **{KEY: v}) for v in (100.0, 25.0, 100.0, 100.0)]
    assert reader.read(recorded(stats)) == 100.0


def test_it_says_nothing_for_a_program_that_gathers_every_pair():
    """The parent of PR 41 keeps `moe/aux_loss` and no slab counter; a
    dense model keeps neither."""
    parent = dict(QUIET, **{"actor_train/moe/aux_loss": 1.01})
    assert reader.read(recorded(parent)) is None
    assert reader.read(recorded(QUIET)) is None
    assert reader.read(recorded(QUIET, walls=())) is None


def test_its_entry_is_the_last_and_lists_the_share_cells():
    spec = files.benchmark_json()
    at = next(i for i, m in enumerate(spec["per_layer"])
              if m["name"] == "moe_train_rows_gathered_share")
    assert spec["per_layer"][at] == {
        "name": "moe_train_rows_gathered_share", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "model step", "moves": "train_tokens_per_s",
        "workloads": SHARE_CELLS,
    }
    assert spec["per_layer"][at - 1]["name"] == "moe_train_mlp_mfu_ssm"
    cells = [w["name"] for w in spec["workloads"]]
    assert [c for c in cells
            if spec["per_layer"][at] in files.metrics_for(c, traced=True)
            ] == SHARE_CELLS

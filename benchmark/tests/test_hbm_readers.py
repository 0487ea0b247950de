"""The nine readers of the program's HBM ledger (PR 66) on step records
recorded from a chip run (`data/hbm_step_records.json`: the warm-up
step's and the timed steps' `hbm/*` stats of one v5e run of
`q1p5b-decode-static` and the harness's own `memory_peak_bytes`, as
`scripts/stall_probe.py` wrote them), on a record of the parent's (no
`hbm/*` key: every reader says None and the line leaves it out), and the
cap: `per_layer` holds the contract's 128 entries and none of the nine,
so they come as files for the `benchmark` PR that makes room (PERF.md
section 7)."""
import json
import os

import pytest

from benchmark import files
from benchmark.run import Run

READERS = {
    "hbm_weights_gb": "hbm/weights_gb",
    "hbm_moments_gb": "hbm/moments_gb",
    "hbm_cache_gb": "hbm/cache_gb",
    "hbm_other_live_gb": "hbm/other_live_gb",
    "hbm_code_gb": "hbm/code_gb",
    "hbm_step_temp_gb": "hbm/temp_gb",
    "hbm_peak_before_step_gb": "hbm/peak_before_step_gb",
    "hbm_unaccounted_gb": "hbm/unaccounted_gb",
}
WINDOW = "hbm_peak_rise_in_window_gb"
ACCOUNT = ("hbm_weights_gb", "hbm_moments_gb", "hbm_cache_gb",
           "hbm_other_live_gb", "hbm_code_gb", "hbm_step_temp_gb",
           "hbm_unaccounted_gb")

with open(os.path.join(os.path.dirname(__file__), "data",
                       "hbm_step_records.json")) as f:
    RECORDED = json.load(f)


def recorded(warmup, steps):
    run = Run(
        cell_name=RECORDED["cell"], cell={}, config={}, traffic={},
        model_cfg=None, chips=1, device_kind="TPU v5 lite", peaks={},
        seed=RECORDED["seed"], traced=True,
    )
    run.warmup = {"stats": dict(warmup)}
    run.steps = [{"wall_s": s["wall_s"], "stats": dict(s["stats"])}
                 for s in steps]
    return run


def read(name, run):
    return files.load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_the_warm_up_steps_key(name):
    run = recorded(RECORDED["warmup"], RECORDED["steps"])
    assert read(name, run) == RECORDED["warmup"][READERS[name]]


def test_the_account_adds_up_to_the_peak_the_harness_read():
    """weights + moments + cache + other_live + code + temp + unaccounted
    is the warm-up step's peak by construction; in the recorded run no
    later step raised it, so it is also the harness's own reading of the
    same counter of the same device, to the byte."""
    run = recorded(RECORDED["warmup"], RECORDED["steps"])
    total = sum(read(name, run) for name in ACCOUNT)
    assert total == pytest.approx(
        RECORDED["warmup"]["hbm/peak_step1_gb"], abs=1e-9)
    assert read(WINDOW, run) == 0.0
    last = RECORDED["steps"][-1]["stats"]["hbm/peak_gb"]
    assert round(last * 1e9) == RECORDED["memory_peak_bytes"]


def test_the_window_reader_sums_the_timed_steps_rises():
    steps = [json.loads(json.dumps(s)) for s in RECORDED["steps"]]
    steps[0]["stats"]["hbm/peak_rise_gb"] = 0.25  # the harness's sums
    steps[-1]["stats"]["hbm/peak_rise_gb"] = 0.5
    assert read(WINDOW, recorded(RECORDED["warmup"], steps)) == 0.75


@pytest.mark.parametrize("name", sorted(READERS) + [WINDOW])
def test_a_reader_says_nothing_on_a_record_of_the_parents(name):
    def parent(stats):
        return {k: v for k, v in stats.items() if not k.startswith("hbm/")}

    steps = [dict(s, stats=parent(s["stats"])) for s in RECORDED["steps"]]
    assert read(name, recorded(parent(RECORDED["warmup"]), steps)) is None
    assert read(name, recorded({}, [])) is None


def test_per_layer_holds_128_entries_and_none_of_the_nine():
    spec = files.benchmark_json()
    assert len(spec["per_layer"]) == 128
    names = {m["name"] for m in spec["per_layer"] + spec["end_to_end"]}
    assert not names & (set(READERS) | {WINDOW})
    assert not [m for m in spec["per_layer"] if m["moves"] == "peak_hbm_gb"]
    for name in sorted(READERS) + [WINDOW]:
        assert callable(files.load_module("metrics", name).read)

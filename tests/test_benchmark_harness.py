"""The benchmark harness inside tier-1 (`tests/` is all tier-1 collects;
`benchmark/tests/` is the harness's own suite): the any-block cases of
`benchmark/tests/test_any_block.py` and of
`benchmark/tests/test_ledger_readers.py` (the readers of the program's own
host watch, step ledger and counters, PR 36) by import, BENCHMARK.json
against the files it names, the readers of every later configuration on a
made-up reduction, and the CPU rehearsals of the cells through their config
files' `toy` groups (`--seconds 1`, held to `correct`).  The cells' OTHER
rehearsal, to the end of the window, is collected where
`tests/benchmark_windows.py` says (PR 62: a file is one worker's, and the
ten of them made this one 713 s longer)."""

import functools
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import files
from benchmark.metrics import (
    _moe, moe_decode_mlp_ms, moe_decode_mlp_roofline, moe_experts_touched,
    moe_route_share, moe_train_mlp_mfu,
)
from benchmark.tests.test_any_block import *  # noqa: F401,F403 — the cases
from benchmark.tests.test_any_block import OLMOE
from benchmark.tests import test_ledger_readers as ledger_cases
from benchmark.tests.test_moe_train_rows_gathered_share import *  # noqa: F401,F403 — the cases
from benchmark.tests.test_ledger_readers import *  # noqa: F401,F403 — the cases
from benchmark.tests import test_ssmd as ssmd_cases
from benchmark.tests.test_ssmd import *  # noqa: F401,F403 — the cases (PR 53)
from benchmark.tests import test_ssm_slab as slab_cases
from benchmark.tests.test_ssm_slab import *  # noqa: F401,F403 — the cases (PR 54)
from benchmark.tests import test_sala as sala_cases
from benchmark.tests.test_sala import *  # noqa: F401,F403 — the cases (PR 55)
from benchmark.tests import test_gdnd as gdnd_cases
from benchmark.tests.test_gdnd import *  # noqa: F401,F403 — the cases (PR 59)
from benchmark.tests import fixed_work_cases
from tests import benchmark_windows
from tests.benchmark_windows import cells_of, window_case

# The ten window cases came in with the star import above and made this
# file 713 s longer: each is collected where `tests/benchmark_windows.py`
# says, and here the dense cell's, whose home this file is.
test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(  # noqa: F811
    __name__)


SPEC = files.benchmark_json()
CELLS = [w["name"] for w in SPEC["workloads"]]
# Two metrics that had no `workloads` list — every cell reported them — got
# the list of the eleven cells before PR 55's: its cell runs no flash kernel,
# their readers find nothing there, and a list-less metric has to be
# reported by every cell that reports `train_tokens_per_s`.
LISTED_BY_PR55 = {"flash_fwd_share", "flash_bwd_share"}


GLM_CELL = "glm47f-rollout64-1k"
OLMOH_CELL = "olmoh-rollout64-512"  # PR 59's
DOTS_CELL = "dots3n-docrl8-longctx"  # PR 64's
SDAR_CELL = "sdar-rollout64-512"  # PR 68's, the last of `workloads`
# PR 38's entries, the last of `per_layer` but PR 39's one: the issue's
# eight in its order, then the two twins the review asked for (`mfu_gen` and
# `moe_train_mlp_mfu` over `benchmark/peaks_mla.py`, as the hybrid cell has).
GLM_ENTRIES = [
    ("mla_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("mla_decode_roofline", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("latent_cache_share", "%", "lower", "program_counter", "generator",
     "gen_tokens_per_s"),
    ("mla_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("mla_train_mfu", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
    ("decode_hbm_share_mla", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("mfu_train_mla", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("moe_decode_mlp_roofline_mla", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("mfu_gen_mla", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("moe_train_mlp_mfu_mla", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
]


NEMO_CELL = "nemo3n-rollout64-512"
NEMO_CONFIG = "nemotron-3-nano-30b-a3b-l9-e16"
# PR 40's entries, in ISSUE 40's order: five of the Mamba layers' own and
# the five twins the other two share cells have, over `benchmark/peaks_ssm.py`.
NEMO_ENTRIES = [
    ("ssm_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("ssm_decode_roofline", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("ssm_state_share", "%", "lower", "program_counter", "generator",
     "gen_tokens_per_s"),
    ("ssm_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("ssm_train_mfu", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
    ("decode_hbm_share_ssm", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("mfu_train_ssm", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("moe_decode_mlp_roofline_ssm", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("mfu_gen_ssm", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("moe_train_mlp_mfu_ssm", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
]


MELLUM_CELL = "mellum2-coderl32-4k"
MELLUM_CONFIG = "mellum2-12b-a2.5b-l4-e16"
# PR 44's entries, in ISSUE 44's order: seven of the window / full mix's own
# and the five twins the share cells have, over `benchmark/peaks_swa.py`.
MELLUM_ENTRIES = [
    ("swa_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("swa_window_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("swa_decode_roofline", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("window_cache_share", "%", "lower", "program_counter", "generator",
     "gen_tokens_per_s"),
    ("flash_window_live_tile_share", "%", "lower", "program_counter",
     "trainer", "train_tokens_per_s"),
    ("swa_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("swa_flash_mfu", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
    ("mfu_train_swa", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("mfu_gen_swa", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("decode_hbm_share_swa", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("moe_decode_mlp_roofline_swa", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("moe_train_mlp_mfu_swa", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
]


LFM2_CELL = "lfm2-ctxrl32-4k"
LFM2_CONFIG = "lfm2-8b-a1b-e8"
# PR 48's entries, in ISSUE 48's order: six of the short-convolution /
# attention mix's own and the five twins the share cells have, over
# `benchmark/peaks_sconv.py`.
LFM2_ENTRIES = [
    ("sconv_decode_ms", "ms", "lower", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("sconv_decode_roofline", "%", "higher", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("sconv_train_share", "%", "lower", "device_trace", "model step",
     "train_tokens_per_s"),
    ("sconv_train_mfu", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
    ("sconv_cache_share", "%", "lower", "program_counter", "generator",
     "gen_tokens_per_s"),
    ("flash_mfu_d64", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
    ("mfu_train_sconv", "%", "higher", "host_clock", "model step",
     "train_tokens_per_s"),
    ("mfu_gen_sconv", "%", "higher", "host_clock", "model step",
     "gen_tokens_per_s"),
    ("decode_hbm_share_sconv", "%", "higher", "device_trace", "model step",
     "gen_tokens_per_s"),
    ("moe_decode_mlp_roofline_sconv", "%", "higher", "device_trace",
     "kernels", "gen_tokens_per_s"),
    ("moe_train_mlp_mfu_sconv", "%", "higher", "device_trace", "kernels",
     "train_tokens_per_s"),
]
# PR 51's entries, in ISSUE 51's order: what `setup_s` is made of, from
# the program's own set-up ledger, in every cell.
SETUP_ENTRIES = [
    ("setup_import_s", "s"), ("setup_build_s", "s"),
    ("setup_weights_s", "s"), ("setup_trace_lower_s", "s"),
    ("setup_cache_load_s", "s"), ("setup_compile_s", "s"),
    ("setup_programs", "count"), ("setup_cache_misses", "count"),
    ("setup_first_step_other_s", "s"), ("setup_unaccounted_s", "s"),
]
# The lists a static MoE share cell joins (ISSUEs 38, 40, 44, 48).
SHARE_CELL_LISTS = {
    "gen_tokens_per_s", "decode_ms_per_step", "decode_loop_ms",
    "moe_experts_touched", "moe_decode_mlp_ms", "moe_route_share",
    "moe_local_rows_share", "sample_draw_ms", "moe_train_rows_gathered_share",
}


def test_every_cells_window_case_has_one_home_and_every_home_collects_its_cells():
    """No cell's window case runs twice and none is left out, whichever
    cell a later PR adds to BENCHMARK.json; and the same of the case that
    holds a cell the count closes to `correct`."""
    homes = benchmark_windows.CELLS
    assert sorted(homes) == sorted(
        fixed_work_cases.DENSE[:1] + fixed_work_cases.FIXED)
    assert sorted(c for c, row in homes.items() if row.requests) == sorted(
        fixed_work_cases.FIXED)
    for home in {row.home for row in homes.values()}:
        module = importlib.import_module(home)
        cases = {
            "test_the_window_closes_on_the_cells_count_or_on_the_clock":
                cells_of(home),
            "test_cpu_rehearsal_of_the_cell_is_correct":
                cells_of(home, correct=True),
        }
        for name, cells in cases.items():
            if not cells:  # the dense cell's home binds no `correct` case
                assert not hasattr(module, name), home
                continue
            (over,) = [m for m in getattr(module, name).pytestmark
                       if m.name == "parametrize"]
            assert over.args == ("cell", cells), (home, name)


def test_a_cells_two_cases_read_one_process(monkeypatch):
    """`rehearsal(cell)` starts one process for the window case and the
    `correct` case of a cell, whichever asks first and however often; the
    dense cell's window, which the clock closes, goes through to its own."""
    started = []

    def rehearse(cwd, cell, trace=0, seconds=1):
        started.append((cell, seconds))
        return subprocess.CompletedProcess([], 0, "", (
            "trial_seed=26 traffic_seed=26 timed_steps=4\n"
            "timed steps, walls [1.0, 1.0, 1.0, 1.0]\n" if seconds == 600 else
            "trial_seed=3 traffic_seed=3 timed_steps=None\n"
            "timed steps, walls [0.6, 0.6]\n"))

    monkeypatch.setattr(benchmark_windows, "rehearse", rehearse)
    monkeypatch.setattr(benchmark_windows, "rehearsal", functools.lru_cache(
        benchmark_windows.rehearsal.__wrapped__))
    window = window_case("tests.test_olmoe")
    window("olmoe-decode-tail")
    assert benchmark_windows.rehearsal("olmoe-decode-tail").returncode == 0
    window("olmoe-decode-tail")
    assert benchmark_windows.rehearsal.cache_info().misses == 1
    assert started == [("olmoe-decode-tail", 600)]
    window_case("tests.test_benchmark_harness")("q1p5b-decode-static")
    assert started[1:] == [("q1p5b-decode-static", 1)]


def _at(entries, name):
    """Index of the entry called `name`: the benchmark is pinned by NAME,
    so that what a later PR appends moves no case."""
    return next(i for i, m in enumerate(entries) if m["name"] == name)


def test_the_new_entries_are_where_the_issue_put_them(monkeypatch):  # noqa: F811
    """PR 36's case pins ITS eight entries as the last of `per_layer`;
    entries appended since (PR 37's `paged_attn_live_page_share`, PR 38's
    ten for the latent-attention cell, PR 39's `sample_draw_ms`, PR 40's
    ten for the Mamba cell) move them up.  So: the appended entries where
    their issues put them, found by name, then PR 36's case on the list as
    it stood before — `benchmark/tests/` is not a perf or a model_config
    PR's to edit (PERF.md §7)."""
    n = len(GLM_ENTRIES)
    per_layer = SPEC["per_layer"]
    draw = _at(per_layer, "sample_draw_ms")
    assert per_layer[draw + 1: draw + 1 + len(NEMO_ENTRIES)] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": [NEMO_CELL]}
        for name, unit, better, source, layer, moves in NEMO_ENTRIES
    ]
    assert per_layer[draw] == {
        "name": "sample_draw_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "gen_tokens_per_s",
        # Every cell that generates, but PR 68's: its block loop draws
        # twice a block, not once a token, so the reader's count of decode
        # steps (new tokens a row) is not the draws that program makes.
        "workloads": [
            w for m in SPEC["end_to_end"] if m["name"] == "gen_tokens_per_s"
            for w in m["workloads"] if w != SDAR_CELL
        ],
    }
    assert per_layer[draw - n: draw] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": [GLM_CELL]}
        for name, unit, better, source, layer, moves in GLM_ENTRIES
    ]
    # Both cells of the serving plane (PR 53 appended the second).
    serving = ["q1p5b-serving-waves", "granite4hm-serving-waves"]
    last = per_layer[draw - n - 1]
    assert last == {
        "name": "paged_attn_live_page_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "generator",
        "moves": "gen_tokens_per_s", "workloads": serving,
    }
    assert [
        c for c in CELLS if last in files.metrics_for(c, traced=True)
    ] == serving
    # ... and before PR 53 appended the second serving cell to the lists.
    before = dict(SPEC, per_layer=[
        dict(m, workloads=[w for w in m["workloads"] if w != serving[1]])
        if "workloads" in m else m for m in per_layer[: draw - n - 1]
    ])
    monkeypatch.setattr(files, "benchmark_json", lambda: before)
    ledger_cases.test_the_new_entries_are_where_the_issue_put_them()


def test_the_glm_cell_is_as_the_issue_parametrised_it():
    """ISSUE 38: one configuration, one cell, eight metrics of its own
    (and the review's two twins) and its name appended to the lists whose arithmetic holds for it — and to
    none whose arithmetic (`benchmark/peaks.py`: a GQA layer everywhere,
    every chosen expert local) is wrong for it."""
    cell, config, traffic = files.load_cell(GLM_CELL)
    entry = SPEC["workloads"][_at(SPEC["workloads"], GLM_CELL)]
    assert entry == {
        "name": GLM_CELL, "config": "glm-4.7-flash-l7-e8",
        "traffic": "rollout64-1k", "chips": 1, "why": entry["why"],
    }
    conf = SPEC["configs"][_at(SPEC["configs"], "glm-4.7-flash-l7-e8")]
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cell["route"], cell["timed_steps"], cell["traffic_seed"]) == (
        "static", 3, 38)
    assert traffic["n_prompts"] * traffic["group"] == 64
    assert (traffic["group"], traffic["max_new_tokens"]) == (4, 1024)
    assert traffic["prompt_len"] == {"dist": "uniform", "lo": 96, "hi": 160}
    assert traffic["dataset_max_length"] == 256
    assert traffic["generator"] == "math_prompts"
    listed = {
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
        if GLM_CELL in m.get("workloads", [])
    } - LISTED_BY_PR55
    assert listed == {name for name, *_ in GLM_ENTRIES} | {
        "gen_tokens_per_s", "decode_ms_per_step", "decode_loop_ms",
        "moe_experts_touched", "moe_decode_mlp_ms", "moe_route_share",
        "moe_local_rows_share", "sample_draw_ms",
        "moe_train_rows_gathered_share",
    }
    # Appended, and nothing else of those lists changed: the cell is the
    # last of the seven cells the benchmark then had, in every list.
    then = CELLS[: CELLS.index(GLM_CELL) + 1]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if GLM_CELL in m.get("workloads", []):
            assert [w for w in m["workloads"] if w in then][-1] == GLM_CELL, (
                m["name"])
    assert len(then) == 7 and sum(
        w["chips"] == 4 for w in SPEC["workloads"]) == 1


def test_the_nemotron_cell_is_as_the_issue_parametrised_it():
    """ISSUE 40: one configuration, one cell on the traffic file the hybrid
    cell uses, ten metrics of its own, and its name appended, last, to the
    eight lists whose arithmetic holds for it — and to none whose
    arithmetic (`benchmark/peaks.py`, `peaks_hybrid.py`, `peaks_mla.py`)
    is wrong for it."""
    cell, config, traffic = files.load_cell(NEMO_CELL)
    entry = SPEC["workloads"][_at(SPEC["workloads"], NEMO_CELL)]
    assert entry == {
        "name": NEMO_CELL, "config": NEMO_CONFIG,
        "traffic": "rollout64-512", "chips": 1, "why": entry["why"],
    }
    conf = SPEC["configs"][_at(SPEC["configs"], NEMO_CONFIG)]
    assert conf == {
        "name": NEMO_CONFIG,
        "source": config["benchmark"]["source"],
        "file": f"benchmark/configs/{NEMO_CONFIG}.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "why": conf["why"],
    }
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert CELLS.index(NEMO_CELL) == CELLS.index(GLM_CELL) + 1 == 7
    assert (cell["route"], cell["timed_steps"], cell["traffic_seed"]) == (
        "static", 4, 40)
    assert config["model_type"] == "nemotron_h"
    assert config["benchmark"]["reference"] == "nemotron_h"
    assert config["benchmark"]["layout"] == {
        "chips": 1, "actor_parallel": "d1", "gen_parallel": None}
    # The traffic file is the hybrid cell's, unchanged.
    assert files.load_cell("q3next-rollout64-512")[2] == traffic
    assert traffic["n_prompts"] * traffic["group"] == 64
    assert (traffic["group"], traffic["max_new_tokens"]) == (4, 512)
    assert traffic["prompt_len"] == {"dist": "uniform", "lo": 96, "hi": 160}
    listed = {
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
        if NEMO_CELL in m.get("workloads", [])
    } - LISTED_BY_PR55
    assert listed == {name for name, *_ in NEMO_ENTRIES} | {
        "gen_tokens_per_s", "decode_ms_per_step", "decode_loop_ms",
        "moe_experts_touched", "moe_decode_mlp_ms", "moe_route_share",
        "moe_local_rows_share", "sample_draw_ms",
        "moe_train_rows_gathered_share",
    }
    then = CELLS[: CELLS.index(NEMO_CELL) + 1]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if NEMO_CELL in m.get("workloads", []):
            assert [w for w in m["workloads"] if w in then][-1] == NEMO_CELL, (
                m["name"])
    # Every reader the cell is listed under exists, and so does every file.
    for name in listed:
        assert callable(files.load_module("metrics", name).read), name


@pytest.mark.parametrize(
    "pools,want",
    [
        ([{"pages_live": 30, "pages_addressed": 120},
          {"pages_live": 50, "pages_addressed": 200}], 25.0),
        ([{"pages_live": 0, "pages_addressed": 288}], 0.0),
        # the static program: no chunk ran, nothing was addressed
        ([{"pages_live": 0, "pages_addressed": 0}], None),
        # a program that keeps no such counter (the parent of PR 37)
        ([{"chunks": 11}], None),
    ],
    ids=["serving", "all_dead", "static_program", "no_counter"],
)
def test_live_page_share_is_live_over_addressed_pages(pools, want):
    from benchmark.metrics import paged_attn_live_page_share

    run = ledger_cases.recorded(
        ledger_cases.QUIET, walls=(2.0,) * len(pools)
    )
    for step, pool in zip(run.steps, pools):
        step["pool"] = pool
    assert paged_attn_live_page_share.read(run) == want


@pytest.mark.parametrize(
    "trace,gen,want",
    [
        # the serving plane: 704 lanes over a budget of 2 = 352 inner steps
        ({"scope_seconds": {
            "gen/serving_chunk/gen/decode_step/head_logprob/sample_draw":
                {"fwd": 0.1056, "recompute": 0.0, "bwd": 0.0},
            "gen/serving_chunk/gen/decode_step/head_logprob":
                {"fwd": 0.5, "recompute": 0.0, "bwd": 0.0}},
          "traced_steps": 2, "busy_s": 9.0},
         {"lanes_dispatched": 704, "serving_lane_budget": 2}, 0.15),
        # the static program: 1,024 new tokens a row
        ({"scope_seconds": {"gen/decode_step/head_logprob/sample_draw":
                            {"fwd": 0.0512, "recompute": 0.0, "bwd": 0.0}},
          "traced_steps": 2, "busy_s": 9.0},
         {"lanes_dispatched": 0}, 0.025),
        # a program without the scope (the parent of PR 39)
        ({"scope_seconds": {"gen/decode_step/head_logprob":
                            {"fwd": 0.5, "recompute": 0.0, "bwd": 0.0}},
          "traced_steps": 2, "busy_s": 9.0},
         {"lanes_dispatched": 0}, None),
        # an untraced run
        (None, {"lanes_dispatched": 0}, None),
    ],
    ids=["serving", "static", "no_scope", "untraced"],
)
def test_sample_draw_ms_is_the_scopes_seconds_per_decode_step(trace, gen, want):
    from benchmark.metrics import sample_draw_ms

    run = ledger_cases.recorded(ledger_cases.QUIET, walls=(2.0,))
    run.trace = trace
    run.steps[-1].update(
        gen=gen, seq_lens=[1124, 1184], prompt_lens=[100, 160])
    got = sample_draw_ms.read(run)
    assert got == (want if want is None else pytest.approx(want))


def test_its_entry_is_the_last_and_lists_the_share_cells(monkeypatch):  # noqa: F811
    """PR 41's case pins `moe_train_rows_gathered_share` to the three share
    cells it had; PR 44's cell is a fourth and PR 48's a fifth (they train
    on the slab too), each appended, last.  So: the case on the benchmark
    as it stood before those cells — `benchmark/tests/` is not a
    model_config PR's to edit."""
    from benchmark.tests import test_moe_train_rows_gathered_share as cases

    entry = SPEC["per_layer"][_at(SPEC["per_layer"], cases.reader.__name__.rsplit(".", 1)[1])]
    # PR 64's and PR 68's train on it too
    later = [MELLUM_CELL, LFM2_CELL, DOTS_CELL, SDAR_CELL]
    assert entry["workloads"] == cases.SHARE_CELLS + later
    before = json.loads(json.dumps(SPEC))
    before["workloads"] = [
        w for w in before["workloads"] if w["name"] not in later]
    for m in before["end_to_end"] + before["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for c in m["workloads"] if c not in later]
    monkeypatch.setattr(files, "benchmark_json", lambda: before)
    cases.test_its_entry_is_the_last_and_lists_the_share_cells()


def test_the_mellum_cell_is_as_the_issue_parametrised_it():
    """ISSUE 44: one configuration, one cell on a traffic file of its own,
    twelve metrics of its own at the end of `per_layer`, and its name
    appended, last, to the nine lists whose arithmetic holds for a static
    MoE share cell — and to none that divides by `benchmark/peaks.py`."""
    from benchmark.traffic.math_prompts import quantile_lengths

    cell, config, traffic = files.load_cell(MELLUM_CELL)
    entry = SPEC["workloads"][_at(SPEC["workloads"], MELLUM_CELL)]
    assert entry == {
        "name": MELLUM_CELL, "config": MELLUM_CONFIG,
        "traffic": "rollout32-ctx4k-512", "chips": 1, "why": entry["why"],
    }
    conf = SPEC["configs"][_at(SPEC["configs"], MELLUM_CONFIG)]
    assert conf == {
        "name": MELLUM_CONFIG, "source": config["benchmark"]["source"],
        "file": f"benchmark/configs/{MELLUM_CONFIG}.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": conf["why"],
    }
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert CELLS.index(MELLUM_CELL) == 8
    assert [w["name"] for w in SPEC["workloads"] if w["chips"] == 4] == [
        "q7b-realloc-4chip"]
    assert (cell["route"], cell["timed_steps"], cell["traffic_seed"]) == (
        "static", 4, 44)
    assert config["model_type"] == "mellum"
    assert config["benchmark"]["layout"] == {
        "chips": 1, "actor_parallel": "d1", "gen_parallel": None}
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.6, "lo": 768,
        "hi": 4096}
    assert (traffic["n_prompts"], traffic["group"], traffic["max_new_tokens"],
            traffic["dataset_max_length"], traffic["batches"],
            traffic["eos_reachable"], traffic["generator"]) == (
        8, 4, 512, 4096, 1, False, "math_prompts")
    lengths = quantile_lengths(traffic["prompt_len"], 8)
    assert lengths == [816, 1203, 1527, 1864, 2251, 2746, 3487, 4096]
    assert 4 * (sum(lengths) + 8 * 512) == 88344  # trained tokens a step
    n = len(MELLUM_ENTRIES)
    first = _at(SPEC["per_layer"], MELLUM_ENTRIES[0][0])
    # (PR 64's cell has a band too: `flash_window_live_tile_share` lists it)
    assert _spec_before_pr64()["per_layer"][first: first + n] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": [MELLUM_CELL]}
        for name, unit, better, source, layer, moves in MELLUM_ENTRIES
    ]
    assert SPEC["per_layer"][first - 1]["name"] == "moe_train_rows_gathered_share"
    listed = {
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
        if MELLUM_CELL in m.get("workloads", [])
    } - LISTED_BY_PR55
    assert listed == {name for name, *_ in MELLUM_ENTRIES} | SHARE_CELL_LISTS
    then = CELLS[: CELLS.index(MELLUM_CELL) + 1]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if MELLUM_CELL in m.get("workloads", []):
            assert [w for w in m["workloads"] if w in then][-1] == MELLUM_CELL, (
                m["name"])
    for name in listed:
        assert callable(files.load_module("metrics", name).read), name


def test_the_lfm2_cell_is_as_the_issue_parametrised_it():
    """ISSUE 48: one configuration, one cell on the EXISTING traffic file
    of the window / full cell, eleven metrics of its own at the end of
    `per_layer`, and its name appended, last, to the nine lists whose
    arithmetic holds for a static MoE share cell — and to none that
    divides by another family's `peaks*.py`."""
    cell, config, traffic = files.load_cell(LFM2_CELL)
    # The tenth cell and the eighth configuration (PR 53 appended behind).
    entry = SPEC["workloads"][9]
    assert entry == {
        "name": LFM2_CELL, "config": LFM2_CONFIG,
        "traffic": "rollout32-ctx4k-512", "chips": 1, "why": entry["why"],
    }
    conf = SPEC["configs"][7]
    assert conf == {
        "name": LFM2_CONFIG, "source": config["benchmark"]["source"],
        "file": f"benchmark/configs/{LFM2_CONFIG}.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": conf["why"],
    }
    assert conf["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    # PR 55 appended the twelfth cell and the tenth configuration, PR 59
    # the thirteenth and the eleventh, PR 64 the fourteenth and the twelfth,
    # PR 68 the fifteenth and the thirteenth.
    assert len(CELLS) == 15 and len(SPEC["configs"]) == 13
    assert [w["name"] for w in SPEC["workloads"] if w["chips"] == 4] == [
        "q7b-realloc-4chip"]
    assert (cell["route"], cell["timed_steps"], cell["traffic_seed"]) == (
        "static", 4, 48)
    assert config["model_type"] == "lfm2_moe"
    assert config["benchmark"]["reference"] == "lfm2_moe"
    assert config["benchmark"]["weights_seed"] == 48
    assert config["benchmark"]["layout"] == {
        "chips": 1, "actor_parallel": "d1", "gen_parallel": None}
    # The traffic file is the window / full cell's, unchanged.
    assert files.load_cell(MELLUM_CELL)[2] == traffic
    n = len(LFM2_ENTRIES)
    first = _at(SPEC["per_layer"], LFM2_ENTRIES[0][0])
    assert SPEC["per_layer"][first: first + n] == [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": [LFM2_CELL]}
        for name, unit, better, source, layer, moves in LFM2_ENTRIES
    ]
    assert SPEC["per_layer"][first - 1]["name"] == MELLUM_ENTRIES[-1][0]
    listed = {
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
        if LFM2_CELL in m.get("workloads", [])
    } - LISTED_BY_PR55
    assert listed == {name for name, *_ in LFM2_ENTRIES} | SHARE_CELL_LISTS
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if LFM2_CELL in m.get("workloads", []):  # the last static cell's
            static = [w for w in m["workloads"]  # ... before PR 55's, 59's
                      if "serving" not in w
                      and w not in (
                          "sala-docrl8-longctx", OLMOH_CELL, DOTS_CELL,
                          SDAR_CELL)]
            assert static[-1] == LFM2_CELL, m["name"]
    for name in listed:
        assert callable(files.load_module("metrics", name).read), name
    # Every metric without a list is the cell's too, and its reader loads.
    for m in files.metrics_for(LFM2_CELL, traced=True):
        assert hasattr(files.load_module("metrics", m["name"]), "read")


def test_the_set_up_entries_are_the_last_ten_and_every_cells():
    """ISSUE 51: ten readers of the program's set-up ledger appended to
    `per_layer` in the issue's order, each without a `workloads` list (all
    ten cells report them), right after PR 48's; pinned by name, so what a
    later PR appends (PR 52's one) moves nothing."""
    n = len(SETUP_ENTRIES)
    first = _at(SPEC["per_layer"], SETUP_ENTRIES[0][0])
    assert SPEC["per_layer"][first: first + n] == [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_counter", "layer": "build", "moves": "setup_s"}
        for name, unit in SETUP_ENTRIES
    ]
    assert SPEC["per_layer"][first - 1]["name"] == LFM2_ENTRIES[-1][0]
    setup = {name for name, _ in SETUP_ENTRIES}
    assert not [m["name"] for m in SPEC["per_layer"]
                if m["moves"] == "setup_s" and m["name"] not in setup]
    for cell in CELLS:
        mine = [m["name"] for m in files.metrics_for(cell, traced=True)]
        at = mine.index(SETUP_ENTRIES[0][0])
        assert mine[at: at + n] == [name for name, _ in SETUP_ENTRIES], cell
    for name, _ in SETUP_ENTRIES:
        assert callable(files.load_module("metrics", name).read), name


def test_the_delta_rule_share_is_the_last_entry_and_the_hybrid_cells():
    """ISSUE 52: one per-layer metric appended, last — the share of the
    Gated DeltaNet mixer's device seconds that lie under its `delta_rule`
    scope, in the gradient program — listed for the one cell with such
    layers; it reads scopes the program has had since PR 32, and returns
    None, never raises, where there is no trace or no such scope."""
    from benchmark.metrics import gdn_delta_rule_share
    from benchmark.run import Run

    at = _at(SPEC["per_layer"], "gdn_delta_rule_share")
    assert SPEC["per_layer"][at] == {
        "name": "gdn_delta_rule_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "train_tokens_per_s",
        # PR 59 appended the second cell with such layers.
        "workloads": ["q3next-rollout64-512", OLMOH_CELL],
    }
    assert SPEC["per_layer"][at - 1]["name"] == SETUP_ENTRIES[-1][0]
    # PR 53's eight follow it (`benchmark/tests/test_ssmd.py`).
    assert SPEC["per_layer"][at + 1]["name"] == "mfu_train_ssmd"

    def run(scopes):
        return Run(
            cell_name="x", cell={}, config={}, traffic={}, model_cfg=None,
            chips=1, device_kind="TPU v5 lite", peaks=None, seed=0,
            traced=True,
            trace={"scope_seconds": scopes, "traced_steps": 2, "busy_s": 9.0},
        )

    def phases(fwd, recompute, bwd):
        return {"fwd": fwd, "recompute": recompute, "bwd": bwd}

    mixer = "train/grad/layer/linear_attn"
    got = gdn_delta_rule_share.read(run({
        f"{mixer}/in_proj": phases(0.1, 0.1, 0.2),
        f"{mixer}/delta_rule": phases(0.1, 0.1, 0.1),
        f"{mixer}/delta_rule/gdn_chunk_bwd": phases(0.0, 0.0, 0.3),
        "gen/decode_step/layer/linear_attn/delta_step": phases(5.0, 0, 0),
        "train/grad/layer/mlp": phases(1.0, 1.0, 1.0),
    }))
    assert got == pytest.approx(100.0 * 0.6 / 1.0)
    assert gdn_delta_rule_share.read(run({})) is None
    assert gdn_delta_rule_share.read(run(
        {"train/grad/layer/attn": phases(1.0, 0.0, 1.0)})) is None
    untraced = run({})
    untraced.trace = None
    assert gdn_delta_rule_share.read(untraced) is None


def _setup_run(stats, setup_s=100.0):
    from benchmark.run import Run

    return Run(
        cell_name="x", cell={}, config={}, traffic={}, model_cfg=None,
        chips=1, device_kind="TPU v5 lite", peaks=None, seed=0, traced=True,
        warmup={"stats": stats}, setup_s=setup_s,
    )


# Step 1's stats as one process writes them: its own `setup/*`, and each
# node's `perf/*` with the node's name in front.
_SETUP_STATS = {
    "time/step_s": 60.0,
    "setup/to_import_s": 3.0, "setup/to_run_s": 12.0, "setup/build_s": 20.0,
    "setup/weights_s": 8.0, "setup/engines_s": 5.0, "setup/programs": 40.0,
    "setup/trace_s": 9.0, "setup/lower_s": 6.0, "setup/compile_s": 0.5,
    "setup/cache_load_s": 14.0, "setup/cache_hits": 30.0,
    "setup/cache_misses": 2.0, "setup/load_max_s": 4.0,
    "actor_gen/perf/trace_s": 4.0, "actor_gen/perf/lower_s": 3.0,
    "actor_gen/perf/compile_s": 7.0, "actor_gen/perf/cache_load_s": 6.0,
    "actor_train/perf/trace_s": 4.5, "actor_train/perf/lower_s": 2.5,
    "actor_train/perf/compile_s": 6.0, "actor_train/perf/cache_load_s": 5.0,
    "rew_inf/perf/trace_s": 0.0, "rew_inf/perf/lower_s": 0.0,
    "rew_inf/perf/compile_s": 0.0, "rew_inf/perf/cache_load_s": 0.0,
}


@pytest.mark.parametrize("name,want", [
    ("setup_import_s", 12.0), ("setup_build_s", 20.0),
    ("setup_weights_s", 8.0), ("setup_trace_lower_s", 15.0),
    ("setup_cache_load_s", 14.0), ("setup_compile_s", 0.5),
    ("setup_programs", 40.0), ("setup_cache_misses", 2.0),
    # 60 s of step less 8.5 traced, 5.5 lowered and 13 in the backend
    # phase, which holds the loads.
    ("setup_first_step_other_s", 33.0),
    # 100 s of set-up less 12 to the build, 20 of build, 60 of step.
    ("setup_unaccounted_s", 8.0),
])
def test_a_set_up_reader_reads_step_ones_stats(name, want, capsys):
    from areal_tpu.base import tracer

    read = files.load_module("metrics", name).read
    assert read(_setup_run(_SETUP_STATS)) == pytest.approx(want)
    # The parent of PR 51 keeps no such ledger: the line leaves them out.
    bare = {k: v for k, v in _SETUP_STATS.items()
            if "setup/" not in k and "trace_s" not in k and "lower_s" not in k}
    assert read(_setup_run(bare)) is None
    assert read(_setup_run({})) is None
    run = _setup_run({})
    run.warmup = None  # a run that never reached its first step's end
    assert read(run) is None
    # Workers in processes of their own reply for themselves: summed.
    apart = {
        (f"{node}/{k}" if k.startswith("setup/") else k): v
        for node in ("actor_gen", "actor_train")
        for k, v in _SETUP_STATS.items()
    }
    want_apart = {"setup_first_step_other_s": want,
                  "setup_unaccounted_s": 100.0 - 2 * 32.0 - 60.0}
    assert read(_setup_run(apart)) == pytest.approx(
        want_apart.get(name, 2 * want))
    if name == "setup_programs":
        # ... and writes step 1's longest rows where the run's log is.
        tracer._reset_for_tests()
        tracer.program_event(
            "/jax/core/compile/backend_compile_duration", 2.5,
            fun_name="jit_gen")
        tracer.close_step(1, 1.0)
        capsys.readouterr()
        read(_setup_run(_SETUP_STATS))
        err = capsys.readouterr().err
        tracer._reset_for_tests()
        assert err.startswith("[benchmark] set-up ledger: {'setup/to_import_s")
        assert "the longest of 1 programs" in err
        assert "compile 2.50" in err and "jit_gen" in err
        assert "compiled and written" not in err  # nothing was served


def test_cpu_rehearsal_of_a_dense_cell_reports_the_whole_set_up():
    """A traced rehearsal of `q1p5b-decode-static`: all ten readers find
    their keys, the four parts make `setup_s` by construction, and the
    table of programs is in the log."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=files.ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "q1p5b-decode-static", "--seed", "2200000051", "--seconds", "1",
         "--trace", "1", "--cpu-rehearsal"],
        cwd=files.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stderr.splitlines()
    out = json.loads(
        [l for l in lines if "would print: " in l][-1].split("would print: ")[1])
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()
           if k.startswith("setup_")}
    assert set(got) == {name for name, _ in SETUP_ENTRIES}
    assert all(v >= 0 for k, v in got.items() if k != "setup_unaccounted_s")
    assert got["setup_weights_s"] <= got["setup_build_s"]
    assert got["setup_programs"] >= 3 and got["setup_trace_lower_s"] > 0
    said = [l for l in lines if "(warm-up step " in l][-1]
    warmup_s = float(said.split("(warm-up step ")[1].split("s;")[0])
    setup_s = float(said.split("] set-up ")[1].split("s ")[0])
    assert (got["setup_import_s"] + got["setup_build_s"] + warmup_s
            + got["setup_unaccounted_s"]) == pytest.approx(setup_s, abs=0.2)
    assert abs(got["setup_unaccounted_s"]) < 0.5 * setup_s
    assert any("] set-up ledger: {'setup/to_import_s'" in l for l in lines)
    table = lines.index(next(
        l for l in lines if l.startswith("the longest of ")))
    assert " trace " in lines[table + 1] and " lower " in lines[table + 1]


def test_every_name_in_benchmark_json_is_a_cell_and_its_files_resolve():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for cell in SPEC["workloads"]:
        cell_file, config, traffic = files.load_cell(cell["name"])
        assert cell_file["config"] == cell["config"] in configs
        assert cell_file["traffic"] == cell["traffic"]
        assert cell_file["chips"] == cell["chips"] == (
            config["benchmark"]["layout"]["chips"])
        entry = configs[cell["config"]]
        assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
        assert entry["source"] == config["benchmark"]["source"]
        assert sorted(entry["reduced"]) == sorted(config["benchmark"]["reduced"])
        files.load_module("references", config["benchmark"]["reference"])
        files.load_module("traffic", traffic["generator"])
    assert {c["config"] for c in SPEC["workloads"]} == set(configs)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    for m in metrics:
        assert set(m.get("workloads", [])) <= set(CELLS), m["name"]
        assert hasattr(files.load_module("metrics", m["name"]), "read")
        if "moves" in m:  # reported wherever this metric is
            target = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
            assert m["moves"] in end_to_end
            assert set(m.get("workloads", CELLS)) <= set(
                target.get("workloads", CELLS)), m["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_the_olmoe_config_file_holds_the_published_keys():
    config = files.load_json("configs", "olmoe-1b-7b-0125-l3.json")
    published = {  # catalog row OLMoE-1B-7B-0125-Instruct
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["benchmark"]["reduced"]) == {"num_hidden_layers"}
    assert set(config["benchmark"]["assumed"]) == {
        "head_dim", "expert_width", "router_aux_loss_coef"}
    from benchmark import peaks, run

    cfg = run.model_config(config)
    assert (cfg.n_layers, cfg.qk_norm, cfg.moe_norm_topk) == (3, True, False)
    assert peaks.mlp_params(cfg) == peaks.mlp_params(OLMOE)
    total = 3 * (peaks.attn_params(cfg) + 64 * peaks.expert_params(cfg)
                 + 2048 * 64 + 2 * 2048 + 2 * 2048) + 2 * 2048 * 50304 + 2048
    assert round(total / 1e9, 3) == 1.465


def reduced(ops, scopes):
    return types.SimpleNamespace(
        trace={"op_seconds_scoped": ops, "scope_seconds": scopes,
               "traced_steps": 2, "busy_s": 100.0},
        model_cfg=OLMOE, chips=1,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        cell={"route": "static"},
        steps=[{"seq_lens": [1152] * 8, "prompt_lens": [128] * 8,
                "gen": {"lanes_dispatched": 0},
                "pool": {"moe_experts_touched": 40.0}}],
    )


def phases(fwd=0.0, recompute=0.0, bwd=0.0):
    return {"fwd": fwd, "recompute": recompute, "bwd": bwd}


def test_the_moe_readers_find_the_unscoped_ragged_kernels_by_their_rows():
    """XLA's ragged-dot kernels carry no scope; the scoped activation
    multiply beside them says which program has how many rows."""
    call = "custom-call:tpu_custom_call"
    ops = {
        "mul.2 fusion bf16[64,1024] @gen/decode_step/layer/mlp/experts:fwd": 0.2,
        "mul.9 fusion bf16[16384,1024] @gen/prefill/layer/mlp/experts:fwd": 0.1,
        "mul.7 fusion bf16[65536,1024] @train/grad/layer/mlp/experts:fwd": 0.3,
        "mul.8 fusion bf16[65536,1024] @train/grad/layer/mlp/experts:recompute": 0.3,
        f"ragged-dot-none.1 {call} bf16[64,1024]": 4.0,
        f"ragged-dot-none.2 {call} bf16[64,2048]": 2.0,
        f"ragged-dot-none.3 {call} bf16[16384,1024]": 0.5,
        f"ragged-dot-none.4 {call} bf16[65536,1024]": 3.0,
        f"ragged-dot-none.6 {call} bf16[64,1024,2048]": 1.0,  # dW: train
        f"ragged-dot-metadata {call} (s32[65], s32[64], s32[64], s32[1])": 0.01,
        "fusion.1 fusion f32[8,64] @gen/decode_step/layer/mlp/router:fwd": 1.0,
        "sort.1 sort s32[64] @gen/decode_step/layer/mlp/dispatch:fwd": 0.5,
        "scatter.1 fusion bf16[8,2048] @gen/decode_step/layer/mlp/combine:fwd": 0.3,
    }
    scopes = {
        "gen/decode_step/layer/mlp/experts": phases(0.2),
        "gen/decode_step/layer/mlp/router": phases(1.0),
        "gen/decode_step/layer/mlp/dispatch": phases(0.5),
        "gen/decode_step/layer/mlp/combine": phases(0.3),
        "gen/prefill/layer/mlp/experts": phases(0.1),
        "train/grad/layer/mlp/experts": phases(0.3, 0.3),
    }
    run = reduced(ops, scopes)
    assert _moe.ragged_seconds(run, _moe.DECODE) == pytest.approx(3.0)
    assert _moe.ragged_seconds(run, _moe.PREFILL) == pytest.approx(0.25)
    assert _moe.ragged_seconds(run, _moe.TRAIN) == pytest.approx(2.0)
    # 2.0 s scoped + 6.0 s of kernels over two steps of 1,024 iterations.
    assert moe_decode_mlp_ms.read(run) == pytest.approx(1e3 * 4.0 / 1024)
    assert moe_route_share.read(run) == pytest.approx(100 * 1.8 / 8.0)
    assert moe_experts_touched.read(run) == 40.0
    from benchmark import peaks

    floor = 16 * peaks.moe_layer_bytes(OLMOE, 8, experts_touched=40.0) / 819e9
    assert moe_decode_mlp_roofline.read(run) == pytest.approx(
        100 * floor / (4.0 / 1024))
    flops = 3 * 16 * peaks.moe_layer_flops(OLMOE, 8 * 1152)
    assert moe_train_mlp_mfu.read(run) == pytest.approx(
        100 * flops / (0.3 + 2.0) / 197e12)
    # Two programs with one row count cannot be told apart: say nothing.
    clash = dict(ops)
    clash["mul.5 fusion bf16[64,1024] @gen/prefill/layer/mlp/experts:fwd"] = 0.1
    assert _moe.ragged_seconds(reduced(clash, scopes), _moe.DECODE) is None


def test_the_moe_readers_say_nothing_for_a_dense_model_or_no_trace():
    dense = types.SimpleNamespace(
        trace={"op_seconds_scoped": {
            "fusion.356 fusion bf16[8,8960] @gen/decode_step/layer/mlp:fwd": 2.0},
            "scope_seconds": {"gen/decode_step/layer/mlp": phases(2.0)},
            "traced_steps": 2, "busy_s": 10.0},
        model_cfg=types.SimpleNamespace(n_experts=0), chips=1, peaks={},
        cell={"route": "static"},
        steps=[{"seq_lens": [1152] * 8, "prompt_lens": [128] * 8,
                "gen": {"lanes_dispatched": 0}, "pool": {}}],
    )
    untraced = types.SimpleNamespace(
        trace=None, model_cfg=OLMOE, chips=1, peaks=None, steps=dense.steps,
        cell=dense.cell)
    for run in (dense, untraced):
        for reader in (moe_decode_mlp_ms, moe_decode_mlp_roofline,
                       moe_experts_touched, moe_route_share,
                       moe_train_mlp_mfu):
            assert reader.read(run) is None, reader.__name__






def test_the_hybrid_readers_say_nothing_without_their_scopes_or_counters():
    """On a program that lacks what PR 32 added (the parent, or any other
    configuration) every new reader returns None and does not raise."""
    from benchmark.metrics import (
        decode_hbm_share_hybrid, gdn_decode_ms, gdn_decode_roofline,
        gdn_train_mfu, gdn_train_share, mfu_gen_hybrid, mfu_train_hybrid,
        moe_decode_mlp_roofline_hybrid, moe_local_rows_share,
        moe_train_mlp_mfu_hybrid,
    )
    from benchmark.run import Run
    from areal_tpu.models.config import tiny_config

    step = {"pool": {}, "gen": {"lanes_dispatched": 0}, "seq_lens": [8, 8],
            "prompt_lens": [4, 4], "spans": {"actor:train_step": 1.0}}
    bare = Run(
        cell_name="x", cell={"route": "static"}, config={}, traffic={},
        model_cfg=tiny_config(), chips=1, device_kind="TPU v5 lite",
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, seed=0,
        traced=True, steps=[step],
        trace={"scope_seconds": {"train/grad/layer/mlp": {
            "fwd": 1.0, "recompute": 0.0, "bwd": 1.0}},
            "busy_s": 2.0, "traced_steps": 1},
    )
    for reader in (gdn_decode_ms, gdn_decode_roofline, gdn_train_mfu,
                   gdn_train_share, mfu_train_hybrid, moe_local_rows_share,
                   moe_decode_mlp_roofline_hybrid, moe_train_mlp_mfu_hybrid,
                   mfu_gen_hybrid, decode_hbm_share_hybrid):
        assert reader.read(bare) is None, reader.__name__
    bare.trace["scope_seconds"].update({
        "gen/decode_step/layer/linear_attn/delta_step": {
            "fwd": 0.008, "recompute": 0.0, "bwd": 0.0},
        "train/grad/layer/linear_attn/delta_rule": {
            "fwd": 0.5, "recompute": 0.5, "bwd": 1.0},
    })
    assert gdn_decode_ms.read(bare) == 2.0  # 8 ms over 4 decode steps
    assert gdn_train_share.read(bare) == 50.0


def test_the_hybrid_rooflines_of_the_expert_half_count_the_share():
    """`peaks_hybrid` at the published widths (64 of 512 experts held, a
    shared expert, three recurrent states to one K/V layer): what a decode
    step's MLPs and the whole step must move, what a train step's MLPs
    and a generate request must compute — and the four readers over a
    hand-made run divide them by what the run says it took."""
    import dataclasses

    from benchmark import peaks_hybrid as ph
    from benchmark.metrics import (
        decode_hbm_share_hybrid, mfu_gen_hybrid,
        moe_decode_mlp_roofline_hybrid, moe_train_mlp_mfu_hybrid,
    )
    from benchmark.run import Run, model_config

    cfg = model_config(
        files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json"))
    expert = 3 * 2048 * 512
    assert ph.experts_expected(cfg, 64) == pytest.approx(45.889, abs=1e-3)
    # A layer's MLP at 64 rows: 45.6 experts touched (287 MB of the 298),
    # the 512-wide router, the shared expert; 80 local rows are nothing.
    layer = ph.experts_decode_bytes(cfg, 64, 45.6, 80) / cfg.n_layers
    weights = 2 * (45.6 * expert + 2048 * 512 + expert + 2048)
    assert weights < layer < 1.02 * weights
    # The step: ISSUE 32's floor, 2.4 GB.
    ctx = [386.0] * 64
    step = ph.decode_bytes(cfg, ctx, 45.6, 80)
    assert step == pytest.approx(
        ph.gdn_decode_bytes(cfg, 64) + 4 * layer
        + 2 * (ph.full_attn_params(cfg) + 2048 * 18992)
        + 2 * 2 * 256 * 2 * 386.0 * 64)
    assert step == pytest.approx(2.40e9, rel=0.01)
    # Counters absent: the expectations (45.9 experts, 1.25 rows a token).
    assert ph.decode_bytes(cfg, ctx) == pytest.approx(step, rel=0.01)
    # Train: 1.25 of a token's 10 choices are multiplied here.
    per_token = 2 * (1.25 * expert + 2048 * 512 + expert + 2048)
    assert ph.experts_train_flops(cfg, 1000) == pytest.approx(
        3 * 4 * per_token * 1000, rel=0.01)
    gen = ph.flops_generate(cfg, [130], [512])
    # Token by token or at once, the causal half of the scores is the same.
    assert gen == pytest.approx(ph.flops_forward(cfg, [642]))

    pool = {"moe_experts_touched": 45.6, "moe_rows_local": 80.0 * 512 * 4,
            "moe_decode_steps": 512}
    step_rec = {"pool": pool, "gen": {"lanes_dispatched": 0, "decode_steps": 512},
                "seq_lens": [642] * 64, "prompt_lens": [130] * 64,
                "spans": {"actor:train_step": 3.0, "actor_gen:generate": 3.0}}
    run = Run(
        cell_name="x", cell={"route": "static"}, config={}, traffic={},
        model_cfg=cfg, chips=1, device_kind="TPU v5 lite",
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, seed=0,
        traced=True, steps=[step_rec],
        trace={"scope_seconds": {
            "gen/decode_step/layer/mlp/router": {
                "fwd": 512 * 2.0e-3, "recompute": 0.0, "bwd": 0.0},
            "train/grad/layer/mlp/shared": {
                "fwd": 0.1, "recompute": 0.1, "bwd": 0.2}},
            "busy_s": 6.0, "traced_steps": 1,
            "loop_seconds": {"actor_gen:generate": [512 * 5.0e-3]}},
    )
    assert moe_decode_mlp_roofline_hybrid.read(run) == pytest.approx(
        100 * 4 * layer / 819e9 / 2.0e-3)
    assert decode_hbm_share_hybrid.read(run) == pytest.approx(
        100 * step / 819e9 / 5.0e-3)
    assert moe_train_mlp_mfu_hybrid.read(run) == pytest.approx(
        100 * ph.experts_train_flops(cfg, 64 * 642) / 0.4 / 197e12)
    assert mfu_gen_hybrid.read(run) == pytest.approx(
        100 * 64 * gen / 3.0 / 197e12)
    # Every one a share of a peak: under 100%.
    for reader in (moe_decode_mlp_roofline_hybrid, decode_hbm_share_hybrid,
                   moe_train_mlp_mfu_hybrid, mfu_gen_hybrid):
        assert 0 < reader.read(run) < 100
    # Another configuration's run reads nothing.
    from areal_tpu.models.config import tiny_config
    other = dataclasses.replace(run, model_cfg=tiny_config())
    assert moe_decode_mlp_roofline_hybrid.read(other) is None


def _glm_run(model_cfg, pool, scopes):
    from benchmark.run import Run

    step = {"pool": pool, "gen": {"lanes_dispatched": 0},
            "seq_lens": [12, 12], "prompt_lens": [4, 4],
            "spans": {"actor:train_step": 1.0, "actor_gen:generate": 1.0}}
    return Run(
        cell_name="x", cell={"route": "static"}, config={}, traffic={},
        model_cfg=model_cfg, chips=1, device_kind="TPU v5 lite",
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, seed=0,
        traced=True, steps=[step],
        trace={"scope_seconds": scopes, "busy_s": 4.0, "traced_steps": 1,
               "loop_seconds": {"actor_gen:generate": [0.016]}},
    )


def _glm_readers():
    from benchmark.metrics import (
        decode_hbm_share_mla, latent_cache_share, mfu_gen_mla, mfu_train_mla,
        mla_decode_ms, mla_decode_roofline, mla_train_mfu, mla_train_share,
        moe_decode_mlp_roofline_mla, moe_train_mlp_mfu_mla,
    )
    return locals()


def test_the_mla_readers_say_nothing_without_their_scopes_or_counters():
    """On a program that lacks what PR 38 added (the parent, or any other
    configuration) every new reader returns None and does not raise; on a
    latent configuration each reads its scopes and counters."""
    from areal_tpu.models.config import tiny_config
    from benchmark import peaks_mla
    from benchmark.run import model_config

    r = _glm_readers()
    assert sorted(r) == sorted(name for name, *_ in GLM_ENTRIES)
    scopes = {
        "train/grad/layer/mlp": {"fwd": 1.0, "recompute": 0.0, "bwd": 1.0},
        "train/grad/layer/attn": {"fwd": 0.5, "recompute": 0.5, "bwd": 1.0},
        "gen/decode_step/layer/attn": {"fwd": 0.008, "recompute": 0.0, "bwd": 0.0},
    }
    bare = _glm_run(tiny_config(), {}, scopes)
    for name, reader in r.items():
        assert reader.read(bare) is None, name
    big = model_config(files.load_json("configs", "glm-4.7-flash-l7-e8.json"))
    pool = {"latent_cache_bytes": 576.0, "kv_cache_bytes_as_heads": 10240.0,
            "moe_experts_touched": 7.5, "moe_rows_local": 8 * 6 * 1.0,
            "moe_decode_steps": 8}
    scopes.update({
        "gen/decode_step/layer/attn_qkv/absorb_q": {
            "fwd": 0.004, "recompute": 0.0, "bwd": 0.0},
        "gen/decode_step/layer/attn_out/absorb_out": {
            "fwd": 0.004, "recompute": 0.0, "bwd": 0.0},
        "gen/decode_step/layer/mlp/shared": {
            "fwd": 0.008, "recompute": 0.0, "bwd": 0.0},
    })
    run = _glm_run(big, pool, scopes)
    assert r["latent_cache_share"].read(run) == 5.625
    assert r["mla_decode_ms"].read(run) == 2.0  # 16 ms over 8 decode steps
    assert r["mla_train_share"].read(run) == 50.0
    floor_ms = 1e3 * peaks_mla.mla_decode_bytes(big, [8.0, 8.0]) / 819e9
    assert r["mla_decode_roofline"].read(run) == pytest.approx(
        100 * floor_ms / 2.0)
    assert r["mla_train_mfu"].read(run) == pytest.approx(
        100 * peaks_mla.mla_train_flops(big, [12, 12]) / 2.0 / 197e12)
    assert r["mfu_train_mla"].read(run) == pytest.approx(
        100 * peaks_mla.flops_train(big, [12, 12]) / 197e12)
    loop_ms, mlp_ms = 16.0 / 8, 8.0 / 8
    assert r["decode_hbm_share_mla"].read(run) == pytest.approx(
        100 * 1e3 * peaks_mla.decode_bytes(big, [8.0, 8.0], 7.5, 1.0)
        / 819e9 / loop_ms)
    assert r["moe_decode_mlp_roofline_mla"].read(run) == pytest.approx(
        100 * 1e3 * peaks_mla.mlp_decode_bytes(big, 2, 7.5, 1.0)
        / 819e9 / mlp_ms)
    assert 0 < r["mla_decode_roofline"].read(run) < 100
    assert r["mfu_gen_mla"].read(run) == pytest.approx(
        100 * peaks_mla.flops_generate(big, [4, 4], [8, 8]) / 197e12)
    # Two seconds of `train/grad` + `layer/mlp` (no ragged kernel in this
    # made-up trace): the MLPs' forward + backward over 24 trained tokens.
    assert r["moe_train_mlp_mfu_mla"].read(run) == pytest.approx(
        100 * peaks_mla.mlps_train_flops(big, 24) / 2.0 / 197e12)
    assert peaks_mla.mlps_train_flops(big, 24) == pytest.approx(3 * 2 * 24 * (
        6 * (peaks_mla.sparse_mlp_params(big) + 0.5 * big.hidden_dim / 2)
        + peaks_mla.dense_mlp_params(big)), rel=1e-3)


def _nemo_readers():
    from benchmark.metrics import (
        decode_hbm_share_ssm, mfu_gen_ssm, mfu_train_ssm,
        moe_decode_mlp_roofline_ssm, moe_train_mlp_mfu_ssm, ssm_decode_ms,
        ssm_decode_roofline, ssm_state_share, ssm_train_mfu, ssm_train_share,
    )
    return locals()


def test_the_ssm_readers_say_nothing_without_their_scopes_or_counters():
    """On a program that lacks what PR 40 added (the parent, or any other
    configuration) every new reader returns None and does not raise; on a
    pattern of one-branch layers each reads its scopes and counters."""
    from areal_tpu.models.config import tiny_config
    from benchmark import peaks_ssm
    from benchmark.run import model_config

    r = _nemo_readers()
    assert sorted(r) == sorted(name for name, *_ in NEMO_ENTRIES)
    scopes = {
        "train/grad/layer/mlp": {"fwd": 1.0, "recompute": 0.0, "bwd": 1.0},
        "train/grad/layer/attn": {"fwd": 0.5, "recompute": 0.5, "bwd": 1.0},
        "gen/decode_step/layer/mlp": {"fwd": 0.008, "recompute": 0.0, "bwd": 0.0},
    }
    bare = _glm_run(tiny_config(), {}, scopes)
    for name, reader in r.items():
        assert reader.read(bare) is None, name
    # A hybrid configuration's counters are not this family's either.
    hybrid = _glm_run(
        model_config(files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json")),
        {"state_cache_bytes": 9.0, "kv_cache_bytes": 1.0}, scopes)
    for name, reader in r.items():
        assert reader.read(hybrid) is None, name
    big = model_config(files.load_json("configs", f"{NEMO_CONFIG}.json"))
    pool = {"state_cache_bytes": 546.0, "kv_cache_bytes": 50.0,
            "moe_experts_touched": 15.25, "moe_rows_local": 8 * 4 * 1.5,
            "moe_decode_steps": 8}
    scopes.update({
        "gen/decode_step/layer/ssm/ssm_step": {
            "fwd": 0.012, "recompute": 0.0, "bwd": 0.0},
        "gen/decode_step/layer/ssm/in_proj": {
            "fwd": 0.004, "recompute": 0.0, "bwd": 0.0},
        "train/grad/layer/ssm/ssd_scan": {
            "fwd": 0.5, "recompute": 0.5, "bwd": 1.0},
    })
    run = _glm_run(big, pool, scopes)
    assert r["ssm_state_share"].read(run) == pytest.approx(100 * 546 / 596)
    assert r["ssm_decode_ms"].read(run) == 2.0  # 16 ms over 8 decode steps
    assert r["ssm_train_share"].read(run) == pytest.approx(100 * 2.0 / 6.0)
    assert r["ssm_decode_roofline"].read(run) == pytest.approx(
        100 * 1e3 * peaks_ssm.ssm_decode_bytes(big, 2) / 819e9 / 2.0)
    assert r["ssm_train_mfu"].read(run) == pytest.approx(
        100 * peaks_ssm.ssm_train_flops(big, 24) / 2.0 / 197e12)
    assert r["mfu_train_ssm"].read(run) == pytest.approx(
        100 * peaks_ssm.flops_train(big, [12, 12]) / 197e12)
    loop_ms, mlp_ms = 16.0 / 8, 8.0 / 8
    assert r["decode_hbm_share_ssm"].read(run) == pytest.approx(
        100 * 1e3 * peaks_ssm.decode_bytes(big, [8.0, 8.0], 15.25, 1.5)
        / 819e9 / loop_ms)
    assert r["moe_decode_mlp_roofline_ssm"].read(run) == pytest.approx(
        100 * 1e3 * peaks_ssm.experts_decode_bytes(big, 2, 15.25, 1.5)
        / 819e9 / mlp_ms)
    assert r["mfu_gen_ssm"].read(run) == pytest.approx(
        100 * peaks_ssm.flops_generate(big, [4, 4], [8, 8]) / 197e12)
    assert r["moe_train_mlp_mfu_ssm"].read(run) == pytest.approx(
        100 * peaks_ssm.experts_train_flops(big, 24) / 2.0 / 197e12)
    # Two matrices an expert, 0.75 of a token's 6 choices held here, the
    # router's whole width and the shared expert, over the FOUR layers.
    h, f = big.hidden_dim, big.moe_intermediate_dim
    assert peaks_ssm.experts_train_flops(big, 24) == pytest.approx(
        3 * 2 * 24 * 4 * (0.75 * (2 * h * f + h) + h * 128 + 2 * h * 3712),
        rel=1e-3)


def _mellum_readers():
    from benchmark.metrics import (
        decode_hbm_share_swa, flash_window_live_tile_share, mfu_gen_swa,
        mfu_train_swa, moe_decode_mlp_roofline_swa, moe_train_mlp_mfu_swa,
        swa_decode_ms, swa_decode_roofline, swa_flash_mfu, swa_train_share,
        swa_window_decode_ms, window_cache_share,
    )
    return locals()


def test_the_swa_readers_say_nothing_without_their_scopes_or_counters():
    """On a program that lacks what PR 44 added (the parent, or any other
    configuration) every new reader returns None and does not raise; on a
    window / full mix each reads its scopes and counters."""
    from areal_tpu.models.config import tiny_config
    from benchmark import peaks_swa
    from benchmark.run import model_config

    r = _mellum_readers()
    assert sorted(r) == sorted(name for name, *_ in MELLUM_ENTRIES)
    phase = lambda fwd=0.0, recompute=0.0, bwd=0.0: {  # noqa: E731
        "fwd": fwd, "recompute": recompute, "bwd": bwd}
    scopes = {
        "train/grad/layer/mlp": phase(1.0, 0.0, 1.0),
        "train/grad/layer/attn/flash_fwd": phase(0.25, 0.25),
        "train/grad/layer/attn/flash_dq": phase(bwd=0.5),
        "train/grad/layer/attn/flash_dkv": phase(bwd=0.5),
        "gen/decode_step/layer/mlp": phase(0.008),
        "gen/decode_step/layer/attn": phase(0.004),
    }
    pack = {"flash_live_tiles": 120, "flash_grid_tiles": 480,
            "real_tokens": 12}
    bare = _glm_run(tiny_config(), {}, scopes)
    bare.steps[0]["pack"] = pack
    for name, reader in r.items():
        assert reader.read(bare) is None, name
    # Another share cell's counters are not this family's either.
    other = _glm_run(
        model_config(files.load_json("configs", f"{NEMO_CONFIG}.json")),
        {"state_cache_bytes": 9.0, "kv_cache_bytes": 1.0,
         "moe_experts_touched": 15.0, "moe_rows_local": 8.0,
         "moe_decode_steps": 8}, scopes)
    other.steps[0]["pack"] = pack
    for name, reader in r.items():
        assert reader.read(other) is None, name
    big = model_config(files.load_json("configs", f"{MELLUM_CONFIG}.json"))
    slot = peaks_swa.kv_token_bytes(big)
    assert slot == 2048
    pool = {"window_cache_bytes": 3 * 2 * 1024 * slot,
            "kv_cache_bytes": 1 * 2 * 4608 * slot,
            "kv_cache_bytes_unwindowed": 4 * 2 * 4608 * slot,
            "window_slots": 2 * 1024, "window_slots_live": 1500.0,
            "moe_experts_touched": 12.5, "moe_rows_local": 8 * 4 * 3.5,
            "moe_decode_steps": 8}
    scopes.update({
        "gen/decode_step/layer/attn_qkv/window": phase(0.006),
        "gen/decode_step/layer/attn/window": phase(0.009),
        "gen/decode_step/layer/attn_out/window": phase(0.003),
        "gen/decode_step/layer/attn_qkv/full": phase(0.002),
        "gen/decode_step/layer/attn/full": phase(0.011),
        "gen/decode_step/layer/attn_out/full": phase(0.001),
        "train/grad/layer/attn_qkv/window": phase(0.25, 0.25, 0.5),
        "train/grad/layer/attn/window/flash_fwd": phase(0.125, 0.125),
        "train/grad/layer/attn/window/flash_dq": phase(bwd=0.25),
        "train/grad/layer/attn/full/flash_dkv": phase(bwd=0.5),
    })
    del scopes["gen/decode_step/layer/attn"]
    for k in [k for k in scopes if k.startswith("train/grad/layer/attn/flash")]:
        del scopes[k]
    run = _glm_run(big, pool, scopes)
    run.steps[0]["pack"] = dict(pack, flash_live_tiles_window=60)
    assert r["window_cache_share"].read(run) == pytest.approx(
        100 * (3 * 1024 + 4608) / (4 * 4608))
    assert r["window_cache_share"].read(run) == pytest.approx(41.667, abs=1e-3)
    assert r["flash_window_live_tile_share"].read(run) == 12.5
    assert r["swa_decode_ms"].read(run) == pytest.approx(4.0)  # 32 ms / 8
    assert r["swa_window_decode_ms"].read(run) == pytest.approx(18.0 / 8)
    assert r["swa_train_share"].read(run) == pytest.approx(100 * 2.0 / 4.0)
    assert r["swa_decode_roofline"].read(run) == pytest.approx(
        100 * 1e3 * peaks_swa.attn_decode_bytes(big, 1500.0, 2 * 4608, 2)
        / 819e9 / 4.0)
    # 24 trained tokens against the last minibatch's 12: tiles x 2; the
    # forward kernel ran in two phases.
    assert r["swa_flash_mfu"].read(run) == pytest.approx(
        100 * 2 * peaks_swa.flash_tile_flops(big, 120, 60, 2) / 1.0 / 197e12)
    assert peaks_swa.flash_tile_flops(big, 120, 60, 2) == (
        22 * 128 ** 3 * 32 * (1 * 120 + 3 * 60))
    assert r["mfu_train_swa"].read(run) == pytest.approx(
        100 * peaks_swa.flops_train(big, [12, 12]) / 197e12)
    assert r["mfu_gen_swa"].read(run) == pytest.approx(
        100 * peaks_swa.flops_generate(big, [4, 4], [8, 8]) / 197e12)
    loop_ms, mlp_ms = 16.0 / 8, 8.0 / 8
    assert r["decode_hbm_share_swa"].read(run) == pytest.approx(
        100 * 1e3 * peaks_swa.decode_bytes(big, [8.0, 8.0], 12.5, 3.5)
        / 819e9 / loop_ms)
    assert r["moe_decode_mlp_roofline_swa"].read(run) == pytest.approx(
        100 * 1e3 * peaks_swa.experts_decode_bytes(big, 2, 12.5, 3.5)
        / 819e9 / mlp_ms)
    assert r["moe_train_mlp_mfu_swa"].read(run) == pytest.approx(
        100 * peaks_swa.experts_train_flops(big, 24) / 2.0 / 197e12)
    # A 4,096-token sequence: 1,024 x 4,096 - 1,024 x 1,023 / 2 pairs in a
    # window layer, half the square in a full one.
    assert peaks_swa.window_pairs(4096, 1024) == 1024 * 4096 - 1024 * 1023 / 2
    assert peaks_swa.window_pairs(500, 1024) == 500 * 501 / 2
    h, f = big.hidden_dim, big.moe_intermediate_dim
    assert peaks_swa.flops_forward(big, [4096]) == pytest.approx(
        2 * 4096 * (4 * (21_233_664 + 2 * 3 * h * f + h * 64) + h * 24576)
        + 4 * 32 * 128 * (1 * 4096 ** 2 / 2
                          + 3 * peaks_swa.window_pairs(4096, 1024)))


def _lfm2_readers():
    from benchmark.metrics import (
        decode_hbm_share_sconv, flash_mfu_d64, mfu_gen_sconv, mfu_train_sconv,
        moe_decode_mlp_roofline_sconv, moe_train_mlp_mfu_sconv,
        sconv_cache_share, sconv_decode_ms, sconv_decode_roofline,
        sconv_train_mfu, sconv_train_share,
    )
    return locals()


def test_the_sconv_readers_say_nothing_without_their_scopes_or_counters():
    """On a program that lacks what PR 48 added (the parent, or any other
    configuration) every new reader returns None and does not raise; on a
    short-convolution / attention mix each reads its scopes and counters."""
    import dataclasses

    from areal_tpu.models.config import tiny_config
    from benchmark import peaks_sconv
    from benchmark.run import model_config

    r = _lfm2_readers()
    assert sorted(r) == sorted(name for name, *_ in LFM2_ENTRIES)
    phase = lambda fwd=0.0, recompute=0.0, bwd=0.0: {  # noqa: E731
        "fwd": fwd, "recompute": recompute, "bwd": bwd}
    scopes = {
        "train/grad/layer/mlp": phase(1.0, 0.0, 1.0),
        "train/grad/layer/attn/flash_fwd": phase(0.125, 0.125),
        "train/grad/layer/attn/flash_dq": phase(bwd=0.25),
        "train/grad/layer/attn/flash_dkv": phase(bwd=0.5),
        "gen/decode_step/layer/mlp": phase(0.008),
        "gen/decode_step/layer/attn": phase(0.004),
    }
    pack = {"flash_live_tiles": 120, "flash_grid_tiles": 480,
            "real_tokens": 12}
    bare = _glm_run(tiny_config(), {}, scopes)
    bare.steps[0]["pack"] = pack
    for name, reader in r.items():
        assert reader.read(bare) is None, name
    # Another share cell's counters and scopes are not this family's
    # either: the window / full mix states its plan in the same field.
    other = _glm_run(
        model_config(files.load_json("configs", f"{MELLUM_CONFIG}.json")),
        {"window_cache_bytes": 9.0, "kv_cache_bytes": 1.0,
         "kv_cache_bytes_unwindowed": 20.0, "moe_experts_touched": 12.5,
         "moe_rows_local": 8.0, "moe_decode_steps": 8}, scopes)
    other.steps[0]["pack"] = dict(pack, flash_live_tiles_window=60)
    for name, reader in r.items():
        assert reader.read(other) is None, name
    big = model_config(files.load_json("configs", f"{LFM2_CONFIG}.json"))
    slot = peaks_sconv.kv_token_bytes(big)
    assert slot == 2048 and big.n_layers == 6
    assert (peaks_sconv.n_sconv(big), peaks_sconv.n_attn(big),
            peaks_sconv.n_sparse(big)) == (5, 1, 4)
    pool = {"conv_cache_bytes": 5 * 2 * 2 * 2048 * 2,
            "kv_cache_bytes": 1 * 2 * 4608 * slot,
            "kv_cache_bytes_all_attention": 6 * 2 * 4608 * slot,
            "moe_experts_touched": 7.5, "moe_rows_local": 8 * 4 * 2.5,
            "moe_decode_steps": 8}
    scopes.update({
        "gen/decode_step/layer/sconv/in_proj": phase(0.009),
        "gen/decode_step/layer/sconv/conv": phase(0.002),
        "gen/decode_step/layer/sconv/out_proj": phase(0.005),
        "train/grad/layer/sconv/in_proj": phase(0.25, 0.25, 0.5),
        "train/grad/layer/sconv/conv": phase(0.125, 0.125, 0.25),
        "train/grad/layer/sconv/out_proj": phase(0.125, 0.125, 0.25),
    })
    run = _glm_run(big, pool, scopes)
    run.steps[0]["pack"] = pack
    assert r["sconv_cache_share"].read(run) == pytest.approx(
        100 * peaks_sconv.cache_share(big, 4608))
    assert r["sconv_cache_share"].read(run) == pytest.approx(16.739, abs=1e-3)
    ten = dataclasses.replace(  # ISSUE 48's first depth: two periods
        big, n_layers=10, window_pattern=big.window_pattern + "FCCC")
    assert 100 * peaks_sconv.cache_share(ten, 4608) == pytest.approx(
        20.069, abs=1e-3)
    assert r["sconv_decode_ms"].read(run) == pytest.approx(2.0)  # 16 ms / 8
    # Of train/grad's 2 (mlp) + 1 (flash) + 2 (sconv) seconds.
    assert r["sconv_train_share"].read(run) == pytest.approx(100 * 2.0 / 5.0)
    assert r["sconv_decode_roofline"].read(run) == pytest.approx(
        100 * 1e3 * peaks_sconv.sconv_decode_bytes(big, 2) / 819e9 / 2.0)
    assert peaks_sconv.sconv_decode_bytes(big, 2) == 5 * 2 * (
        16_777_216 + 3 * 2048 + 2 * 2 * 2 * 2048 + 2 * 2 * 2048)
    assert r["sconv_train_mfu"].read(run) == pytest.approx(
        100 * peaks_sconv.sconv_train_flops(big, 24) / 2.0 / 197e12)
    assert peaks_sconv.sconv_train_flops(big, 24) == (
        3 * 5 * 2 * 16_777_216 * 24)
    # 24 trained tokens against the last minibatch's 12: tiles x 2; the
    # forward kernel ran in two phases; a tile's product is 128 x 128 x 64.
    assert r["flash_mfu_d64"].read(run) == pytest.approx(
        100 * 2 * peaks_sconv.flash_tile_flops(big, 120, 2) / 1.0 / 197e12)
    assert peaks_sconv.flash_tile_flops(big, 120, 2) == (
        22 * 128 * 128 * 64 * 32 * 1 * 120)
    assert r["mfu_train_sconv"].read(run) == pytest.approx(
        100 * peaks_sconv.flops_train(big, [12, 12]) / 197e12)
    assert r["mfu_gen_sconv"].read(run) == pytest.approx(
        100 * peaks_sconv.flops_generate(big, [4, 4], [8, 8]) / 197e12)
    loop_ms, mlp_ms = 16.0 / 8, 8.0 / 8
    assert r["decode_hbm_share_sconv"].read(run) == pytest.approx(
        100 * 1e3 * peaks_sconv.decode_bytes(big, [8.0, 8.0], 7.5, 2.5)
        / 819e9 / loop_ms)
    assert r["moe_decode_mlp_roofline_sconv"].read(run) == pytest.approx(
        100 * 1e3 * peaks_sconv.mlps_decode_bytes(big, 2, 7.5, 2.5)
        / 819e9 / mlp_ms)
    assert r["moe_train_mlp_mfu_sconv"].read(run) == pytest.approx(
        100 * peaks_sconv.mlps_train_flops(big, 24) / 2.0 / 197e12)
    h, f, fm = big.hidden_dim, big.intermediate_dim, big.moe_intermediate_dim
    assert peaks_sconv.matmul_params(big) == (
        5 * 16_777_216 + 10_485_760 + 2 * 3 * h * f
        + 4 * (1 * 3 * h * fm + h * 32) + h * 16384)
    assert peaks_sconv.flops_forward(big, [4096]) == pytest.approx(
        2 * 4096 * peaks_sconv.matmul_params(big)
        + 4 * 32 * 64 * 1 * 4096 ** 2 / 2)


def _without_last_cell(spec, cell):
    """`spec` as it stood before its last configuration and its last cell,
    `cell`, were appended and the cell's name to `workloads` lists."""
    assert spec["workloads"][-1]["name"] == cell

    def without(m):
        if cell not in m.get("workloads", ()):
            return m
        return dict(m, workloads=[w for w in m["workloads"] if w != cell])

    return dict(
        spec, workloads=spec["workloads"][:-1], configs=spec["configs"][:-1],
        end_to_end=[without(m) for m in spec["end_to_end"]],
        per_layer=[without(m) for m in spec["per_layer"]])


def _spec_before_pr68():
    """BENCHMARK.json as it stood before PR 68 appended its configuration,
    its cell and the cell's name to `workloads` lists (no per-layer entry:
    the list is full)."""
    return _without_last_cell(SPEC, SDAR_CELL)


def _spec_before_pr64():
    """... and before PR 64 did the same."""
    return _without_last_cell(_spec_before_pr68(), DOTS_CELL)


def _spec_before_pr59():
    """... and before PR 59 did the same, to eleven lists."""
    spec = _spec_before_pr64()
    assert spec["workloads"][-2]["name"] == "sala-docrl8-longctx"
    return _without_last_cell(spec, OLMOH_CELL)


def test_the_cell_lists_what_it_reports(monkeypatch):  # noqa: F811
    """PR 59's case pins ITS cell and configuration as the last; PR 64
    appended a cell, a configuration and the cell's name to lists.  So: PR
    59's case on the lists as they stood before."""
    monkeypatch.setattr(files, "benchmark_json", _spec_before_pr64)
    gdnd_cases.test_the_cell_lists_what_it_reports()


def _spec_before(first_later_entry):
    """BENCHMARK.json as it stood before the per-layer entry called
    `first_later_entry` was appended, and before PR 55's configuration and
    cell (the last of their lists before PR 59's)."""
    spec = _spec_before_pr59()
    at = _at(spec["per_layer"], first_later_entry)
    return dict(
        spec, per_layer=spec["per_layer"][:at],
        workloads=spec["workloads"][:-1], configs=spec["configs"][:-1])


def test_the_sala_entries_are_the_last_and_the_cell_lists_what_it_reports(  # noqa: F811
        monkeypatch):
    """PR 55's case pins ITS cell and configuration as the last and the
    flash shares' lists as the eleven cells before it; PR 59 appended a
    cell, a configuration and the cell's name to those lists.  So: PR 55's
    case on the lists as they stood before — `benchmark/tests/` is not a
    later PR's to edit."""
    monkeypatch.setattr(files, "benchmark_json", _spec_before_pr59)
    sala_cases.test_the_sala_entries_are_the_last_and_the_cell_lists_what_it_reports()


def test_the_entries_are_the_last_and_the_cell_lists_what_it_reports(  # noqa: F811
        monkeypatch):
    """PR 53's case pins ITS eight entries as the last of `per_layer`; PR
    54 appended two behind them for the same cell (`benchmark/tests/
    test_ssm_slab.py` pins those as the last) and PR 55 seven more, a
    configuration and a cell (`test_sala.py`).  So: PR 53's case on the
    lists as they stood before — `benchmark/tests/` is not a later PR's to
    edit, as above."""
    at = _at(SPEC["per_layer"], "ssm_serving_state_ms")
    assert [m["name"] for m in SPEC["per_layer"][at: at + 3]] == [
        "ssm_serving_state_ms", "ssm_slot_step_live_share",
        "sparse_decode_ms"]
    monkeypatch.setattr(
        files, "benchmark_json", lambda: _spec_before("ssm_serving_state_ms"))
    ssmd_cases.test_the_entries_are_the_last_and_the_cell_lists_what_it_reports()


def test_the_slab_entries_are_the_last_of_per_layer(monkeypatch):  # noqa: F811
    """PR 54's case on the list as it stood before PR 55's entries."""
    monkeypatch.setattr(
        files, "benchmark_json", lambda: _spec_before("sparse_decode_ms"))
    slab_cases.test_the_slab_entries_are_the_last_of_per_layer()

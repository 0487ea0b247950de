"""The one place that runs a cell's CPU rehearsal for tier-1: one process a
cell, read by both of the cell's cases.

A cell with `timed_steps`, `traffic_seed` and `weights_seed` draws nothing
from `--seed` and closes its window on its count, so its two rehearsals —
`test_the_window_closes_on_the_cells_count_or_on_the_clock` of
`benchmark/tests/fixed_work_cases.py` (`--seconds 600`) and the family's
`test_cpu_rehearsal_of_the_<family>_cell_is_correct` (`--seconds 1`) — were
one computation run twice (PR 71: the same steps, the same programs, the
same reference numbers to the last digit), four of them in two files, so
that from an empty compile cache two workers compiled the same programs
side by side.  `rehearsal(cell)` is that process, once; `window_case` and
`correct_case` read it, and `CELLS` has one row a cell.  A later
`model_config` PR adds a row here and the two one-line bindings to its
family's file.

With every core the worker has, NOT on a share of them: an affinity mask
that the child inherits sizes its XLA pools, and six toy rehearsals started
together take 22 / 26 / 55 / 113 / 260 s on 1 / 2 / 3 / 4 / 8 cores each —
but a whole run never starts them together (each closes its family's file),
the workers' own pools are what a rehearsal shares its cores with, and under
masks of 3 and of 4 cores the nine toy cells' windows took what they took
without (850 and 860 s against 879, summed) while `sala-docrl8-longctx`'s,
real arithmetic over rows of 13 k tokens, took 314 and 305 s against 216
(whole cold runs, CHANGES.md, PR 71): nearer the 420 s ceiling for nothing.

Where each is collected — in the test file of its cell's model family, the
dense cell's in the harness file: `--dist loadfile` gives a file to one
worker, so the memo serves both cases, and hands files out by their NUMBER
OF CASES, most first (xdist's `loadscope-reorder`), so a long case belongs
in a file of many (28-100 here) and never in a thin file of its own: four
files of window cases were tried first (PR 62) and the run spent its last
minutes on them alone.  `test_benchmark_harness.py` holds `CELLS` to the
cells of BENCHMARK.json and every home to its cells, so no cell's case
runs twice and none is left out."""

import functools
from typing import NamedTuple
from unittest import mock

import pytest

from benchmark import files
from benchmark.tests import fixed_work_cases
from benchmark.tests.test_harness import rehearse, would_print


class Row(NamedTuple):
    home: str  # the module that collects the cell's cases
    requests: int = 0  # a step's; 0: a cell the clock closes, window case only
    leaves: int = 0  # of the weight check after the hand-back
    said: tuple = ()  # of each tuple, one line of the log holds every string
    checked: str = ""  # the line of the generator / state check: ends " ok"


CELLS = {
    "q1p5b-decode-static": Row("tests.test_benchmark_harness"),
    # Sparse / lightning layers over rows of 13 k tokens: real arithmetic
    # at toy width, the longest rehearsal of the run.
    "sala-docrl8-longctx": Row(
        "tests.test_minicpm_sala", 8, 23,
        (("minicpm_sala reference",),), "minicpm_sala generator check"),
    "olmoe-decode-tail": Row(
        "tests.test_olmoe", 8, 15, (("olmoe reference", "router_flips"),)),
    # The static program through both kinds of cache, the chunked scan in
    # the train step, the token-by-token reference for generator and trainer.
    "q3next-rollout64-512": Row(
        "tests.test_qwen3_next", 64, 28,
        (("qwen3_next reference", "[0, 4) of 8"),), "qwen3_next state check"),
    # The `toy` group shrinks the five MLA sizes, the experts and the
    # share: the latent cache, the leading dense layer outside the scan,
    # the router's bias unchanged, the generator's own 64-slot program.
    "glm47f-rollout64-1k": Row(
        "tests.test_glm4_moe_lite", 64, 34,
        (("glm4_moe_lite reference", "[0, 4) of 8"),),
        "glm4_moe_lite generator check"),
    # The pattern MEMEM*EME whole (4 heads x 16, state 16, 2 groups, 4 of 8
    # experts): the three populations of the cache, the router's bias.
    "nemo3n-rollout64-512": Row(
        "tests.test_nemotron_h", 64, 22,
        (("nemotron_h reference", "[0, 4) of 8"),),
        "nemotron_h generator check"),
    # The periods SSSF SSSF whole, a window of 16 under prompts of 48-256
    # tokens: rings wrapped in prefill and in decode.
    "mellum2-coderl32-4k": Row(
        "tests.test_mellum", 32, 15,
        (("mellum reference", "[0, 4) of 8"),), "mellum generator check"),
    # The plan c c A c c c: both leading dense layers and the period whole;
    # tails and K/V rows of the generator's own 32-slot program.
    "lfm2-ctxrl32-4k": Row(
        "tests.test_lfm2_moe", 32, 26,
        (("lfm2_moe reference", "[0, 4) of 8"),), "lfm2_moe generator check"),
    # The serving plane in waves over the Mamba / attention hybrid.
    "granite4hm-serving-waves": Row(
        "tests.test_granite_hybrid", 96, 19,
        (("granitemoehybrid reference",),), "granitemoehybrid generator check"),
    # d_v = 2 d_k (heads of 12 x 24): both populations of the cache, the
    # chunked rule with beta in (0, 2), the norms on the branch outputs.
    "olmoh-rollout64-512": Row(
        "tests.test_olmo_hybrid", 64, 22,
        (("olmo_hybrid reference", "largest beta"),),
        "olmo_hybrid state check"),
    # Latent rows, index keys and rings, the leading dense layer outside
    # the scan, the indexer's leaves and the router's bias handed back.
    "dots3n-docrl8-longctx": Row(
        "tests.test_dots3_note", 8, 55,
        (("dots3_note reference", "heads 2 / 2 of 4 / 4", "[0, 4) of 8"),),
        "dots3_note generator check"),
    # The loop over blocks (prompts of every tail), the two-stream train
    # step in rows of stream slots, the rows the commits left.
    "sdar-rollout64-512": Row(
        "tests.test_sdar", 64, 15,
        (("sdar_moe reference", "blocks of 4", "[0, 4) of 8"),
         ("programs ['blocks']",)),
        "sdar_moe generator check"),
}


def cells_of(home, correct=False):
    """The cells collected in the module `home`: all of them for the window
    case, those a count closes for the `correct` case."""
    return [cell for cell, row in CELLS.items()
            if row.home == home and (row.requests or not correct)]


@functools.lru_cache(maxsize=None)
def rehearsal(cell):
    """The CPU rehearsal of a cell that closes on its count, to the end of
    its window: one process, whichever of the cell's cases asks first."""
    return rehearse(files.ROOT, cell, seconds=600)


def _rehearse(cwd, cell, trace=0, seconds=1):
    """`rehearse` for the benchmark's case: the memo where the arguments
    are the memo's, a process of its own otherwise (the dense cell's)."""
    if (cwd, trace, seconds) == (files.ROOT, 0, 600) and CELLS[cell].requests:
        return rehearsal(cell)
    return rehearse(cwd, cell, trace=trace, seconds=seconds)


def window_case(home):
    """The imported case over the cells whose home is the module `home`,
    to be bound there under the case's own name."""

    @pytest.mark.parametrize("cell", cells_of(home))
    def test_the_window_closes_on_the_cells_count_or_on_the_clock(cell):
        with mock.patch.object(fixed_work_cases, "rehearse", _rehearse):
            fixed_work_cases.test_the_window_closes_on_the_cells_count_or_on_the_clock(
                cell)

    return test_the_window_closes_on_the_cells_count_or_on_the_clock


def correct_case(home):
    """The cell end to end at toy size (its config's `toy` group), held to
    `correct` and to what its row says the run must print; bound in `home`
    like the window case, and reading the same process."""

    @pytest.mark.parametrize("cell", cells_of(home, correct=True))
    def test_cpu_rehearsal_of_the_cell_is_correct(cell):
        row, proc = CELLS[cell], rehearsal(cell)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert proc.stdout.strip() == ""  # platform=cpu: no result line
        lines = proc.stderr.splitlines()
        out = would_print(proc)
        assert out["correct"] is True and out["failed"] == 0
        steps = files.load_cell(cell)[0]["timed_steps"]
        assert out["attempted"] == steps * row.requests  # whole steps
        assert {m["name"] for m in files.metrics_for(cell, traced=False)
                } - {"peak_hbm_gb"} == set(out["metrics"])
        check = [l for l in lines if "weight check: " in l][-1]
        assert "'ok': True" in check, check
        assert f"'leaves': {row.leaves}" in check, check
        for said in row.said:
            assert any(all(s in l for s in said) for l in lines), said
        assert not row.checked or any(
            row.checked in l and l.endswith(" ok") for l in lines)

    return test_cpu_rehearsal_of_the_cell_is_correct

"""Where each cell's window case is collected.

`test_the_window_closes_on_the_cells_count_or_on_the_clock` of
`benchmark/tests/fixed_work_cases.py` is a CPU rehearsal of a cell to the
end of its window, a process of its own, 8-206 s a cell (PR 61's run).  All
ten came into `tests/test_benchmark_harness.py` with a star import and
made it 713 s longer — of a run that six workers otherwise end in 750, and
`--dist loadfile` hands a file to one worker.  So each is collected in the
test file of its cell's model family (the dense cell's stays in the harness
file), and the `--seconds 1` rehearsals of the four heaviest families went
with them: the harness file keeps four, 100-200 s.

Not in thin files of their own: `loadfile` hands files out by their NUMBER
OF CASES, most first (xdist's `loadscope-reorder`), so a file of a few long
cases is handed out last.  Four files of window cases and one of the eight
short rehearsals were tried first (PR 62): the run spent its last minutes on
them alone (six from an empty compile cache, two rehearsals of one cell
compiling the same programs side by side; 269 s of 848 warm).  A family's
file has 28-58 cases, the harness file a hundred: they are among the first
out.  (The same rule says where NOT to put a long case: a file of few.)

A cell that a later PR adds names its home here; `test_benchmark_harness.py`
holds `HOMES` to the cells the case is parametrised over, and every home to
its cells, so no cell's case runs twice and none is left out."""

import pytest

from benchmark.tests import fixed_work_cases

HOMES = {
    "q1p5b-decode-static": "tests.test_benchmark_harness",
    "sala-docrl8-longctx": "tests.test_minicpm_sala",
    "olmoe-decode-tail": "tests.test_olmoe",
    "q3next-rollout64-512": "tests.test_qwen3_next",
    "glm47f-rollout64-1k": "tests.test_glm4_moe_lite",
    "nemo3n-rollout64-512": "tests.test_nemotron_h",
    "mellum2-coderl32-4k": "tests.test_mellum",
    "lfm2-ctxrl32-4k": "tests.test_lfm2_moe",
    "granite4hm-serving-waves": "tests.test_granite_hybrid",
    "olmoh-rollout64-512": "tests.test_olmo_hybrid",
    "dots3n-docrl8-longctx": "tests.test_dots3_note",
    "sdar-rollout64-512": "tests.test_sdar",
}


def cells_of(home):
    return [cell for cell, h in HOMES.items() if h == home]


def window_case(home):
    """The imported case over the cells whose home is the module `home`,
    to be bound there under the case's own name."""

    @pytest.mark.parametrize("cell", cells_of(home))
    def test_the_window_closes_on_the_cells_count_or_on_the_clock(cell):
        fixed_work_cases.test_the_window_closes_on_the_cells_count_or_on_the_clock(
            cell)

    return test_the_window_closes_on_the_cells_count_or_on_the_clock

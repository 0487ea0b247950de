"""The readers of the program's own host watch, step ledger and counters
(PR 36) on a recorded `Run`: a number wherever the program keeps the key
(0.0 where nothing happened, never None), and None — the result line
leaves the metric out — for a program that keeps no such key, as the
parent of PR 36 does not."""
import pytest

from benchmark import files
from benchmark.metrics import (
    admit_passed_over, flash_live_tile_share, host_gc_share, host_late_share,
    host_runq_share, mfc_self_s, pack_empty_row_share, slow_step_share,
)
from benchmark.run import Run

QUIET = {"host/late_s": 0.0, "host/runq_wait_s": 0.0, "host/gc_s": 0.0,
         "time/slow_excess_s": 0.0}
PACK = {"n_rows": 8, "empty_rows": 2, "flash_live_tiles": 120,
        "flash_grid_tiles": 480, "pack_efficiency": 0.9}


def recorded(stats, walls=(2.0, 2.0, 2.0, 4.0), pack=PACK, pool=None):
    """A run of len(walls) timed steps; `stats` is one dict for every
    step or a list of one per step."""
    if isinstance(stats, dict):
        stats = [stats] * len(walls)
    run = Run(
        cell_name="q1p5b-serving-waves", cell={}, config={}, traffic={},
        model_cfg=None, chips=1, device_kind="TPU v5 lite", peaks={},
        seed=1, traced=True,
    )
    run.steps = [
        {"wall_s": w, "stats": dict(s), "pack": dict(pack or {}),
         "pool": dict(pool or {}), "host": {"late_s": 0.0}}
        for w, s in zip(walls, stats)
    ]
    return run


SHARES = [
    (host_late_share, "host/late_s"),
    (host_runq_share, "host/runq_wait_s"),
    (host_gc_share, "host/gc_s"),
    (slow_step_share, "time/slow_excess_s"),
]


@pytest.mark.parametrize("reader,key", SHARES, ids=[k for _, k in SHARES])
def test_a_share_is_the_keys_sum_over_the_wall_in_percent(reader, key):
    assert reader.read(recorded(QUIET)) == 0.0
    stats = [dict(QUIET) for _ in range(4)]
    stats[1][key] = 0.13
    stats[3][key] = 0.37
    assert reader.read(recorded(stats)) == pytest.approx(100 * 0.5 / 10.0)


@pytest.mark.parametrize("reader,key", SHARES, ids=[k for _, k in SHARES])
def test_a_share_says_nothing_for_a_program_without_a_host_watch(reader, key):
    """`host/late_s` tells: the parent of PR 36 has none of the keys."""
    parent = {"time/step_s": 2.0}
    assert reader.read(recorded(parent)) is None
    partly = [dict(QUIET), parent, dict(QUIET), dict(QUIET)]
    assert reader.read(recorded(partly)) is None
    assert reader.read(recorded(QUIET, walls=())) is None


def test_a_key_the_hosts_kernel_does_not_keep_reads_zero():
    """The chip machine has no schedstat: the watch leaves `runq_wait_s`
    out, and the metric, which every cell must report, reads 0.0."""
    sandboxed = {k: v for k, v in QUIET.items() if k != "host/runq_wait_s"}
    assert host_runq_share.read(recorded(sandboxed)) == 0.0


def test_host_late_share_is_host_pause_shares_twin():
    """Same rule, same denominator: where both watches saw the same
    pauses the two metrics are equal."""
    from benchmark.metrics import host_pause_share

    stats = [dict(QUIET, **{"host/late_s": late})
             for late in (0.0, 0.131, 0.0, 0.0)]
    run = recorded(stats)
    for step, s in zip(run.steps, stats):
        step["host"]["late_s"] = s["host/late_s"]
    assert host_late_share.read(run) == pytest.approx(
        host_pause_share.read(run))


def test_mfc_self_s_sums_the_nodes_and_takes_the_median_step():
    per_step = [
        {"actor_gen/perf/self_s": 0.001, "rew_inf/perf/self_s": 0.002,
         "actor_train/perf/self_s": s, "actor_train/perf/time_s": 9.0}
        for s in (0.004, 0.005, 0.006, 0.9)
    ]
    assert mfc_self_s.read(recorded(per_step)) == pytest.approx(0.0085)
    # A graph of one node keeps its keys bare.
    assert mfc_self_s.read(recorded({"perf/self_s": 0.25})) == 0.25
    assert mfc_self_s.read(recorded({"perf/time_s": 1.0})) is None


def test_the_pack_counters_readers():
    run = recorded(QUIET)
    assert flash_live_tile_share.read(run) == 25.0
    assert pack_empty_row_share.read(run) == 25.0
    # Nothing packed, no tile: a number, not None.
    empty = recorded(QUIET, pack=dict(PACK, n_rows=0, empty_rows=0,
                                      flash_live_tiles=0, flash_grid_tiles=0))
    assert flash_live_tile_share.read(empty) == 0.0
    assert pack_empty_row_share.read(empty) == 0.0
    # A program from before the counters (PR 31 / PR 34).
    old = recorded(QUIET, pack={"pack_efficiency": 0.9})
    assert flash_live_tile_share.read(old) is None
    assert pack_empty_row_share.read(old) is None


def test_admit_passed_over_is_the_counters_median_step():
    run = recorded(QUIET, pool={"admit_passed_over": 0})
    assert admit_passed_over.read(run) == 0.0
    for step, n in zip(run.steps, (3, 5, 4, 40)):
        step["pool"]["admit_passed_over"] = n
    assert admit_passed_over.read(run) == 4.5
    assert admit_passed_over.read(recorded(QUIET, pool={"chunks": 9})) is None


def test_the_new_entries_are_where_the_issue_put_them():
    spec = files.benchmark_json()
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = [w["name"] for w in spec["workloads"]]
    want = {
        "host_late_share": ("master", "samples_per_s"),
        "host_runq_share": ("master", "samples_per_s"),
        "host_gc_share": ("master", "samples_per_s"),
        "slow_step_share": ("master", "samples_per_s"),
        "mfc_self_s": ("worker MFC", "samples_per_s"),
        "flash_live_tile_share": ("kernels", "train_tokens_per_s"),
        "pack_empty_row_share": ("trainer", "train_tokens_per_s"),
        "admit_passed_over": ("generator", "gen_tokens_per_s"),
    }
    assert [m["name"] for m in spec["per_layer"]][-8:] == list(want)
    for name, (layer, moves) in want.items():
        m = entries[name]
        assert (m["layer"], m["moves"]) == (layer, moves), name
        assert m["source"] == "program_counter", name
        listed = [c for c in cells
                  if m in files.metrics_for(c, traced=True)]
        if name == "admit_passed_over":
            assert listed == ["q1p5b-serving-waves"]
        else:
            assert listed == cells, name

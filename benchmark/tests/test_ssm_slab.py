"""PR 54's two readers in the harness's own cases, on `test_ssmd.py`'s
recorded toy run: the state's part of the mixers' time
(`ssm_serving_state_ms`: scope `ssd_scan` under the chunk, whatever runs
under it) and the share of (slot, inner step) pairs that hold a lane
(`ssm_slot_step_live_share`); that both say nothing for a program without
the scope or the counter (the parent of PR 54), for another model and —
the traced one — for an untraced run; and the entries in BENCHMARK.json
where the issue put them."""
import pytest

from benchmark import files
from benchmark.metrics import ssm_serving_state_ms, ssm_slot_step_live_share
from benchmark.tests.test_ssmd import CELL, POOL, SCOPES, _run

SLAB_ENTRIES = [
    ("ssm_serving_state_ms", "ms", "lower", "device_trace", "kernels",
     "gen_tokens_per_s"),
    ("ssm_slot_step_live_share", "%", "higher", "program_counter",
     "generator", "gen_tokens_per_s"),
]
# 350 inner steps over 64 slots, 52 of them with a lane on the mean.
SLAB_POOL = dict(POOL, ssm_slot_steps_live=350 * 52)
# The kernel's scope lies UNDER `ssd_scan`: parent and change read one span.
SLAB_SCOPES = dict(SCOPES, **{
    "gen/serving_chunk/gen/decode_step/layer/ssm/ssm_ragged/ssd_scan/"
    "ssm_slab_step": {"fwd": 2 * 350 * 2e-3},
})


def test_the_state_reader_sums_what_runs_under_ssd_scan():
    assert ssm_serving_state_ms.read(_run(SCOPES, POOL)) == pytest.approx(3.0)
    assert ssm_serving_state_ms.read(
        _run(SLAB_SCOPES, SLAB_POOL)) == pytest.approx(5.0)


def test_the_live_share_counts_slot_steps_over_slots_times_inner_steps():
    run = _run(SCOPES, SLAB_POOL)
    assert ssm_slot_step_live_share.read(run) == pytest.approx(100 * 52 / 64)
    assert 0 < ssm_slot_step_live_share.read(run) <= 100
    assert ssm_slot_step_live_share.read(_run(None, SLAB_POOL)) == (
        pytest.approx(100 * 52 / 64))  # a counter: no trace needed


def test_the_slab_readers_say_nothing_for_a_program_without_the_names():
    """The parent of PR 54 keeps the scope but not the counter; the parent
    of PR 53 neither; another model; an untraced run."""
    assert ssm_slot_step_live_share.read(_run(SCOPES, POOL)) is None
    bare = {k: v for k, v in SCOPES.items() if "layer/ssm" not in k}
    older = _run(bare, {"chunks": 11, "pages_live": 1, "page_size": 128})
    assert ssm_serving_state_ms.read(older) is None
    assert ssm_slot_step_live_share.read(older) is None
    assert ssm_slot_step_live_share.read(
        _run(SCOPES, SLAB_POOL, model=False)) is None
    assert ssm_serving_state_ms.read(_run(None, SLAB_POOL)) is None


def test_the_slab_entries_are_the_last_of_per_layer():
    spec = files.benchmark_json()
    assert [
        (m["name"], m["unit"], m["better"], m["source"], m["layer"],
         m["moves"]) for m in spec["per_layer"][-len(SLAB_ENTRIES):]
    ] == SLAB_ENTRIES
    for m in spec["per_layer"][-len(SLAB_ENTRIES):]:
        assert m["workloads"] == [CELL]
    reported = {m["name"] for m in files.metrics_for(CELL, traced=True)}
    assert {name for name, *_ in SLAB_ENTRIES} <= reported

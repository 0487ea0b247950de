"""The reduction from a trace to numbers: on hand-made events, and on a
small trace recorded on a TPU v5e (`data/tiny_v5e.xplane.pb`, written by
a two-program script: `bench:` spans around a matmul and a 3-step scan)."""

import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def test_union_self_time_and_gaps_with_nesting():
    ev = [
        (10, 50, "while"),      # parent
        (12, 20, "fusion.1"),   # child
        (20, 30, "fusion.2"),   # child, back to back
        (35, 45, "fusion.1"),   # child again
        (70, 80, "copy"),       # after a gap
    ]
    busy, self_s, gaps, tops = trace.union_and_self(ev, 0, 100)
    assert busy == pytest.approx(50e-9)
    assert self_s["while"] == pytest.approx(12e-9)
    assert self_s["fusion.1"] == pytest.approx(18e-9)
    assert self_s["fusion.2"] == pytest.approx(10e-9)
    assert sum(self_s.values()) == pytest.approx(busy)
    assert gaps == [(0, 10), (50, 70), (80, 100)]
    assert tops == [(10, 50, "while"), (70, 80, "copy")]


def test_a_parent_that_starts_with_its_first_child_still_encloses_it():
    ev = [(10, 20, "copy.11"), (10, 50, "while"), (20, 45, "fusion.8")]
    busy, self_s, gaps, tops = trace.union_and_self(ev, 0, 60)
    assert tops == [(10, 50, "while")]
    assert self_s == {"while": pytest.approx(5e-9),
                      "copy.11": pytest.approx(10e-9),
                      "fusion.8": pytest.approx(25e-9)}
    assert busy == pytest.approx(40e-9) and gaps == [(0, 10), (50, 60)]


def test_short_op_name():
    full = ("%fusion.356 = bf16[8,8960]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[28,"
            "1536,8960]{2,1,0:T(8,128)(2,1)} %get-tuple-element.2329), "
            "kind=kOutput, calls=%fused_computation.10.clone.clone")
    assert trace.short_op_name(full) == "fusion.356 fusion bf16[8,8960]"
    kernel = ('%closed_call.4 = (bf16[96,256,128]{2,1,0:T(8,128)(2,1)S(1)}, '
              'f32[96,256,1]{2,1,0:T(8,128)}) custom-call(s32[8,256,8]{2,1,0} '
              '%broadcast_in_dim.242), custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata={}}')
    assert trace.short_op_name(kernel) == (
        "closed_call.4 custom-call:tpu_custom_call "
        "(bf16[96,256,128], f32[96,256,1])"
    )
    ag = "%all-gather.3 = bf16[3584,18944]{1,0} all-gather(bf16[896,18944]{1,0} %p), dimensions={0}"
    assert trace.short_op_name(ag) == "all-gather.3 all-gather bf16[3584,18944]"
    assert trace.short_op_name("no equals sign") == "no equals sign"


def test_window_clips_events():
    busy, self_s, gaps, _ = trace.union_and_self([(0, 100, "a")], 40, 60)
    assert busy == pytest.approx(20e-9) and gaps == []
    assert self_s == {"a": pytest.approx(20e-9)}


def test_idle_goes_to_the_shortest_covering_span():
    spans = [(0, 100, "bench:actor:train_step"), (40, 60, "bench:fetch")]
    idle = trace.idle_by_phase([(45, 55), (70, 80), (150, 170)], spans)
    assert idle == {
        "fetch": pytest.approx(10e-9),
        "actor:train_step": pytest.approx(10e-9),
        "between requests (master)": pytest.approx(20e-9),
    }


def test_longest_outermost_loop_of_each_span():
    tops = [
        (5, 9, "while.1 while (s32[])"),        # prefill's layer scan
        (10, 90, "while.2 while (s32[], f32[8])"),  # the decode loop
        (92, 95, "fusion.3 fusion f32[8]"),
        (120, 180, "while.2 while (s32[], f32[8])"),
        (300, 340, "while.7 while (s32[])"),    # inside no span
    ]
    spans = [(100, 200, "bench:actor_gen:generate"),
             (0, 99, "bench:actor_gen:generate"),
             (200, 250, "bench:actor:train_step")]
    chip0 = trace.longest_loops(tops, spans)
    assert chip0 == {spans[1]: pytest.approx(80e-9),
                     spans[0]: pytest.approx(60e-9)}
    # A second chip whose loop ran in the first span only: a span's mean
    # is over the chips that have one, and spans come out in time order.
    chip1 = trace.longest_loops([(20, 80, "while.2 while (s32[])")], spans)
    both = {sp: [chip0[sp]] + ([chip1[sp]] if sp in chip1 else [])
            for sp in chip0}
    assert trace.loops_by_label(both) == {
        "actor_gen:generate": [pytest.approx(70e-9), pytest.approx(60e-9)]
    }


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    out = trace.reduce(ProfileData.from_file(DATA), chips=1)
    assert 0 < out["busy_s"] < out["window_s"] < 1.0
    assert out["n_events"] > 5
    # Self times add up to the busy time (no double counting of the scan).
    assert sum(out["op_seconds"].values()) == pytest.approx(
        out["busy_s"], rel=1e-6
    )
    # Three tiny programs in a 5.7 ms window: the device is idle nearly
    # all of it, while the host dispatches inside the two spans.
    assert out["busy_s"] < 0.01 * out["window_s"]
    assert {n.split()[1] for n in out["op_seconds"]} >= {"fusion", "while"}
    assert set(out["idle_seconds"]) <= {
        "between requests (master)", "actor_gen:generate", "actor:train_step"
    }
    assert len(out["breakdown"]["device_ops"]) <= 10
    # Both scans ran outside the two spans (dispatch is asynchronous and
    # the programs are microseconds long): no loop is attributed.  The
    # scan's 3 iterations take 4,722 ns of the `while`'s 4,747.
    assert out["loop_seconds"] == {}
    whiles = [s for n, s in out["op_seconds"].items() if " while " in n]
    assert whiles == [pytest.approx(25e-9, abs=2e-9)]
    assert sum(out["idle_seconds"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6
    )

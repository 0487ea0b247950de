"""What the program's own names add to a trace (`program_trace.py`), on
hand-made events and on a trace recorded on a TPU v5e by
`record_scoped_trace.py`: `areal:` spans, named scopes under
`jax.checkpoint` and `grad`, the repo's three flash kernels."""

import os
import types

import pytest

from benchmark import program_trace as pt
from benchmark import trace
from benchmark.metrics import (
    admit_wait_s, chunk_host_ms, compile_s, flash_bwd_share,
    flash_fwd_share, flash_time_share, handback_gb_per_s, train_host_s,
)

HERE = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(HERE, "scoped_v5e.xplane.pb")
TINY = os.path.join(HERE, "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(SCOPED)
    return (trace.reduce(profile, chips=1),
            pt.reduce(profile, pt.op_paths(SCOPED), chips=1))


@pytest.mark.parametrize("path,expected", [
    ("jit(grad_fn)/train/grad/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/layer/attn/flash_fwd/flash_fwd/"
     "pallas_call:", ("train/grad/layer/attn/flash_fwd", "recompute")),
    ("jit(g)/train/grad/transpose(jvp(train/grad))/jvp()/checkpoint/"
     "layer/mlp/mul", ("train/grad/layer/mlp", "bwd")),
    ("jit(g)/train/grad/transpose(jvp(head_logprob))/while/body/"
     "dot_general:", ("train/grad/head_logprob", "bwd")),
    ("jit(g)/train/grad/jvp(embed)/gather:", ("train/grad/embed", "fwd")),
    ("jit(fn)/gen/serving_chunk/while/body/gen/decode_step/layer/attn/"
     "ragged_stream/ragged_stream/pallas_call:",
     ("gen/serving_chunk/gen/decode_step/layer/attn/ragged_stream", "fwd")),
    ("jit(<lambda>)/while:", ("", "fwd")),
    ("", ("", "fwd")),
])
def test_scope_and_phase_of_a_path(path, expected):
    assert pt.scope_of(path) == expected


def test_wire_reader_finds_the_path_beside_each_operation():
    paths = pt.op_paths(SCOPED)["/device:TPU:0"]
    by_name = {line.split(" = ")[0]: p for line, p in paths.items()}
    assert by_name["%flash_dkv.10"] == (
        "jit(grad_fn)/train/grad/transpose(jvp())/while/body/closed_call/"
        "checkpoint/layer/attn/flash_dkv/flash_dkv/pallas_call:"
    )
    # PR 22's recording has paths too, from before the program named any.
    assert "jit(<lambda>)/while:" in pt.op_paths(TINY)["/device:TPU:0"].values()


def test_self_time_of_nested_program_spans():
    lines = [[(0, 100, "areal:step"), (10, 40, "areal:mfc:a"),
              (15, 25, "areal:pack"), (50, 70, "areal:mfc:a")],
             [(20, 30, "areal:pack")]]  # another thread
    out = pt.span_totals(lines, 0, 100)
    assert out["step"] == {"n": 1, "total_s": pytest.approx(100e-9),
                           "self_s": pytest.approx(50e-9)}
    assert out["mfc:a"]["n"] == 2
    assert out["mfc:a"]["self_s"] == pytest.approx(40e-9)
    assert out["pack"] == {"n": 2, "total_s": pytest.approx(20e-9),
                           "self_s": pytest.approx(20e-9)}


def test_idle_goes_to_bench_label_and_innermost_program_span():
    bench = [(0, 100, "bench:param_sync:actor_gen")]
    program = [(0, 100, "areal:param_sync:actor_gen@0"),
               (10, 60, "areal:params_put")]
    idle = pt.idle_by_program_span(
        [(20, 30), (70, 80), (150, 170)], bench, program
    )
    assert idle == {
        "param_sync:actor_gen/params_put": pytest.approx(10e-9),
        "param_sync:actor_gen/param_sync:actor_gen@0": pytest.approx(10e-9),
        "between requests (master)": pytest.approx(20e-9),
    }


def test_recorded_program_spans(scoped):
    _, new = scoped
    spans = new["program_spans"]
    assert {n: s["n"] for n, s in spans.items()} == dict.fromkeys(
        ("step", "mfc:actor@0:train_step", "pack", "grad_dispatch",
         "apply_dispatch", "stats_sync", "param_sync:actor_gen@0",
         "params_put"), 2)
    mfc = spans["mfc:actor@0:train_step"]
    children = sum(spans[n]["total_s"] for n in
                   ("pack", "grad_dispatch", "apply_dispatch", "stats_sync"))
    assert mfc["self_s"] == pytest.approx(mfc["total_s"] - children)
    assert spans["pack"]["self_s"] == spans["pack"]["total_s"] > 0.004


def test_recorded_idle_keeps_each_bench_labels_total(scoped):
    old, new = scoped
    totals = {}
    for label, s in new["idle_by_program_span"].items():
        # bench labels hold `:` but no `/`; program spans come after it
        bench = label.split("/")[0]
        totals[bench] = totals.get(bench, 0.0) + s
    assert totals == pytest.approx(old["idle_seconds"])
    assert max(new["idle_by_program_span"].items(), key=lambda kv: kv[1])[
        0] == "param_sync:actor_gen/params_put"


def test_recorded_scope_seconds_by_phase(scoped):
    old, new = scoped
    sc = new["scope_seconds"]
    fwd = sc["train/grad/layer/attn/flash_fwd"]
    assert fwd["bwd"] == 0 and fwd["fwd"] > 0
    assert fwd["recompute"] == pytest.approx(fwd["fwd"], rel=0.15)
    for kernel in ("flash_dq", "flash_dkv"):
        by_phase = sc[f"train/grad/layer/attn/{kernel}"]
        assert by_phase["bwd"] > 0 == by_phase["fwd"] == by_phase["recompute"]
    assert sc["train/grad/layer/mlp"]["bwd"] > sc["train/grad/layer/mlp"]["fwd"]
    assert new["kernel_seconds"].keys() == {"flash_fwd", "flash_dq", "flash_dkv"}
    # Every second is still there, under a longer name.
    assert sum(new["op_seconds_scoped"].values()) == pytest.approx(
        old["busy_s"], rel=1e-6
    )
    assert pt.scope_total(new, "train/grad") + pt.scope_total(
        new, "train/apply") <= old["busy_s"]
    assert pt.scope_total(new, "flash_fwd", phase="recompute") == fwd["recompute"]
    top = new["breakdown"]["device_ops"][0][0]
    assert top.startswith("flash_fwd.") and top.endswith(
        "@train/grad/layer/attn/flash_fwd:fwd")


@pytest.mark.parametrize("data", [TINY, SCOPED])
def test_names_only_grow_a_suffix(data):
    """What `trace.reduce` returns is untouched; the scoped names are its
    names plus ` @scope:phase`, with the same seconds."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(data)
    old = trace.reduce(profile, chips=1)
    new = pt.reduce(profile, pt.op_paths(data), chips=1)
    folded = {}
    for name, s in new["op_seconds_scoped"].items():
        short = name.partition(" @")[0]
        folded[short] = folded.get(short, 0.0) + s
    assert folded == pytest.approx(old["op_seconds"])
    assert not set(new) & (set(old) - {"breakdown"})


def _run(trace_dict=None, **step):
    return types.SimpleNamespace(trace=trace_dict, steps=[step] if step else [])


def test_kernel_shares_add_up_to_the_flash_share(scoped):
    old, _ = scoped
    run = _run(old)
    fwd, bwd = flash_fwd_share.read(run), flash_bwd_share.read(run)
    assert fwd > bwd > 0
    assert fwd + bwd == pytest.approx(flash_time_share.read(run), abs=1e-9)


def test_readers_say_nothing_about_a_program_without_the_names():
    from jax.profiler import ProfileData

    bare = _run(trace.reduce(ProfileData.from_file(TINY), chips=1),
                pool={}, pack={"pack_efficiency": 0.5}, stats={"loss": 1.0})
    for reader in (flash_fwd_share, flash_bwd_share, chunk_host_ms,
                   admit_wait_s, train_host_s, handback_gb_per_s, compile_s):
        assert reader.read(bare) is None, reader.__name__
    assert flash_fwd_share.read(_run(None)) is None


def test_counter_readers():
    run = _run(
        None,
        pool={"chunks": 14, "chunk_host_s": 0.14, "admit_wait_mean_s": 0.6},
        pack={"host_s": 0.25},
        stats={"actor_gen/sync/bytes": 5.9e9, "actor_gen/sync/time_s": 10.0,
               "actor_gen/perf/compile_s": 0.5, "actor_train/perf/compile_s": 1.5,
               "rew_inf/perf/compile_s": 0.0},
    )
    assert chunk_host_ms.read(run) == pytest.approx(10.0)
    assert admit_wait_s.read(run) == 0.6
    assert train_host_s.read(run) == 0.25
    assert handback_gb_per_s.read(run) == pytest.approx(0.59)
    assert compile_s.read(run) == 2.0

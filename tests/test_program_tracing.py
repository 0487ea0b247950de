"""The program's own observability (PR 23): every tracer span is also a
profiler annotation `areal:<name>`, tracing never changes the schedule,
the master's step number crosses the hop to the workers, the engines keep
the counters the benchmark reads, compilation is charged to the MFC that
needed it, and every part of the device programs carries a stable name."""

import contextlib
import threading
import time
import timeit
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.base import tracer
from tests import fixtures


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer._reset_for_tests()
    yield
    tracer._reset_for_tests()


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what a live
    profiler session would have been handed."""

    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.log.append(("open", self.name, self.kw))

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("close", self.name))


class _FakeStep(_FakeAnnotation):
    pass


@pytest.fixture
def annotations(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(
        tracer, "_ANNOTATIONS", (_FakeAnnotation, _FakeStep)
    )
    return _FakeAnnotation.log


@contextlib.contextmanager
def _own_cache(path):
    """jax's persistent cache in a directory of the caller's own, keeping
    every program however quickly it compiled; the suite's cache and its
    threshold are put back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = [getattr(jax.config, k) for k in keys]
    jax.config.update(keys[0], str(path))
    jax.config.update(keys[1], 0.0)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in zip(keys, before):
            jax.config.update(k, v)
        cc.reset_cache()


# ---------------- spans on the profiler's clock ----------------


@pytest.mark.parametrize("enabled", [False, True])
def test_span_opens_one_areal_annotation(tmp_path, annotations, enabled):
    tracer.configure("t", dir=str(tmp_path), enabled=enabled, force=True)
    with tracer.step_span(7):
        with tracer.span("mfc:actor:train_step", cat="compute", step=7):
            with tracer.span("pack", cat="host"):
                pass
    assert annotations == [
        ("open", "areal:step", {"step_num": 7}),
        ("open", "areal:mfc:actor:train_step", {"step": 7}),
        ("open", "areal:pack", {}),
        ("close", "areal:pack"),
        ("close", "areal:mfc:actor:train_step"),
        ("close", "areal:step"),
    ]


def test_span_annotates_with_tracing_off(annotations):
    with tracer.span("load_data", cat="host"):
        pass
    assert [a[:2] for a in annotations] == [
        ("open", "areal:load_data"), ("close", "areal:load_data")
    ]


def test_real_annotation_is_inert_without_a_profiler_session():
    # jax is loaded in this process: the real TraceAnnotation class is
    # used, and with no session it records nothing and raises nothing.
    assert tracer._annotations()[0] is jax.profiler.TraceAnnotation
    with tracer.step_span(1), tracer.span("x", cat="compute", a=1, s="b"):
        pass


def test_no_jax_means_no_annotation(monkeypatch):
    import sys

    monkeypatch.setattr(tracer, "_ANNOTATIONS", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    with tracer.span("x") as args:
        args["late"] = 1
    assert tracer._ANNOTATIONS is None


def test_disabled_path_allocates_no_ring_entry():
    seen = []

    def work():
        for _ in range(100):
            with tracer.span("x", cat="compute", a=1):
                pass
        seen.append(getattr(tracer._tls, "buf", None))

    before = len(tracer._buffers)
    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert seen == [None] and len(tracer._buffers) == before

    def one():
        with tracer.span("x", cat="compute", a=1):
            pass

    n = 20000
    ns = (timeit.timeit(one, number=n)
          - timeit.timeit(lambda: None, number=n)) / n * 1e9
    print(f"disabled tracer.span: {ns:.0f} ns (CPU host, no session)")


def test_ring_event_names_its_parent_and_keeps_late_args(tmp_path):
    tracer.configure("t", dir=str(tmp_path), enabled=True, force=True)
    with tracer.span("outer", cat="host"):
        with tracer.span("inner", cat="compute") as args:
            pass
        args["late"] = 3  # empty at exit, written after: still attached
        tracer.complete("compile", 0, 10, cat="host")
    _, events = tracer.read_shard(tracer.flush())
    by_name = {e["name"]: e for e in events}
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["inner"]["args"] == {"late": 3}
    assert by_name["compile"]["parent"] == "outer"
    assert "parent" not in by_name["outer"]


def test_trace_report_spans_gives_self_seconds_per_step(tmp_path, capsys):
    from areal_tpu.apps import trace_report

    def x(name, ts, dur, tid=1, **args):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": 1,
                "tid": tid, "args": args}

    trace = {"traceEvents": [
        x("step", 0, 100, step=1), x("step", 100, 200, step=2),
        x("mfc:a", 10, 80, tid=2), x("pack", 10, 30, tid=2),
        x("stats_sync", 50, 40, tid=2),
        x("mfc:a", 110, 180, tid=2), x("pack", 110, 30, tid=2),
        x("stats_sync", 150, 140, tid=2),  # the step that stalled
    ]}
    rows = {(r["step"], r["name"]): r for r in trace_report.span_rows(trace)}
    assert rows[(1, "mfc:a")]["self_us"] == 10
    assert rows[(2, "mfc:a")]["self_us"] == 10
    assert rows[(1, "stats_sync")]["self_us"] == 40
    assert rows[(2, "stats_sync")]["self_us"] == 140
    assert rows[(2, "pack")] == {
        "step": 2, "name": "pack", "n": 1, "total_us": 30, "self_us": 30
    }
    assert "stats_sync" in trace_report.format_spans(trace)


def test_reshard_never_blocks_under_tracing(tmp_path, monkeypatch):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.parallel import realloc

    tracer.configure("t", dir=str(tmp_path), enabled=True, force=True)

    def refuse(*a, **k):
        raise AssertionError("reshard waited for its result")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    mesh = make_mesh(ParallelConfig.from_str("d2"), jax.devices()[:2])
    tree = {"w": jnp.ones((4, 8), jnp.float32), "b": jnp.ones((8,))}
    out = realloc.reshard(tree, NamedSharding(mesh, P()), dtype=jnp.bfloat16)
    assert out["w"].dtype == jnp.bfloat16
    _, events = tracer.read_shard(tracer.flush())
    (ev,) = [e for e in events if e["name"] == "reshard"]
    assert ev["args"]["bytes"] == (4 * 8 + 8) * 2  # from shapes, in bf16


# ---------------- a toy PPO trial on the serving plane ----------------


class _Spy:
    """A jitted function that remembers the shapes it was called with, so
    the test can lower the engine's own program again."""

    def __init__(self, fn):
        self.fn, self.avals = fn, None

    def __call__(self, *args):
        self.avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x)),
            args,
        )
        return self.fn(*args)

    def text(self):
        return self.fn.lower(*self.avals).as_text(debug_info=True)


def _spy_on(obj, getter, spies):
    inner = getattr(obj, getter)
    memo = {}

    def wrapped(*a, **k):
        got = inner(*a, **k)
        if id(got) not in memo:
            fns = got if isinstance(got, tuple) else (got,)
            memo[id(got)] = tuple(_Spy(f) for f in fns)
            spies.setdefault(getter, []).extend(memo[id(got)])
        out = memo[id(got)]
        return out if isinstance(got, tuple) else out[0]

    setattr(obj, getter, wrapped)


@pytest.fixture(scope="module")
def trial(tmp_path_factory):
    """Three steps of toy PPO, 12 requests over 4 decode slots, tracing
    on; the generator's programs are dropped before the third step."""
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.experiments.common import (
        PPOMathConfig,
        build_ppo_math,
        run_experiment,
    )
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl

    root = tmp_path_factory.mktemp("trial")
    tracer._reset_for_tests()
    tracer.configure(
        "master", dir=str(root / "trace"), enabled=True, force=True
    )
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(6, seed=4)
    cfg = PPOMathConfig(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows, "max_length": 64},
        ),
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 2, "kl_ctl": 0.0},
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        batch_size=6,
        total_train_epochs=3,
        ctrl=ExperimentSaveEvalControl(benchmark_steps=3),
        fileroot=str(root),
        gen_backend_args={"max_decode_batch": 4},
    )
    seen = {"spies": {}, "pool": [], "pack": [], "sync": []}

    def inspect(master, stage):
        if stage != "built":
            return
        engines = {
            k.split("@")[0]: m.engine
            for w in master.pool.workers for k, m in w.models.items()
        }
        gen, train = engines["actor_gen"], engines["actor"]
        _spy_on(train, "_get_grad_fn", seen["spies"])
        _spy_on(train, "_get_apply_fn", seen["spies"])
        _spy_on(gen, "_get_serving_chunk_fn", seen["spies"])
        inner_log = master.stats_logger.log

        def log(step, stats):
            inner_log(step, stats)
            seen["pool"].append(dict(gen.last_pool_stats))
            seen["pack"].append(dict(train.last_pack_stats))
            seen["sync"].append(dict(gen.last_sync_stats))
            if step == 2:  # force step 3's generate to build its program
                gen._gen_fns.clear()

        master.stats_logger.log = log

    with _own_cache(root / "jax_cache"):
        _, stats = run_experiment(
            build_ppo_math(cfg, tok), tokenizer=tok, inspect=inspect
        )
    seen["age_s"] = (time.monotonic_ns() - tracer._process_start_ns()) / 1e9
    seen["ledger"] = tracer.step_ledger()
    tracer.flush()
    events = []
    for path in sorted((root / "trace").glob("trace_*.jsonl")):
        events += tracer.read_shard(str(path))[1]
    tracer._reset_for_tests()
    return {"stats": stats, "events": events, **seen}


def test_step_number_crosses_the_master_worker_hop(trial):
    spans = [e for e in trial["events"] if e.get("ph") == "X"]
    assert sorted(
        e["args"]["step"] for e in spans if e["name"] == "step"
    ) == [1, 2, 3]
    for prefix in ("mfc:actor_gen@0:generate", "mfc:actor@0:train_step",
                   "param_sync:actor_gen@0", "fetch"):
        steps = sorted(
            e["args"].get("step") for e in spans
            # the master's own param_sync span (no step argument, it is
            # inside `step`) has the same name as the worker's
            if e["name"] == prefix and "step" in e["args"]
        )
        assert steps == [1, 2, 3], (prefix, steps)


def test_engine_spans_nest_under_the_mfc(trial):
    parents = {}
    for e in trial["events"]:
        if e.get("ph") == "X":
            parents.setdefault(e["name"], set()).add(e.get("parent"))
    assert parents["chunk_host"] == {"generate"}
    assert parents["chunk_dispatch"] == parents["chunk_wait"] == {
        "serving_chunk"
    }
    assert parents["pack"] == parents["stats_sync"] == {
        "mfc:actor@0:train_step"
    }
    assert {"mb_upload", "grad_dispatch", "apply_dispatch"} <= set(parents)
    # (the engine's first set_params is its constructor's, at build)
    assert parents["params_put"] == {"setup:engine", "param_sync:actor_gen@0"}
    # The cast is inside realloc.reshard's placement now (in its compiled
    # program for device leaves), not an eager pass of its own.
    assert "params_cast" not in parents
    assert parents["reshard"] >= {"params_put"}


def test_serving_counters_are_filled_and_reset_per_generate(trial):
    for pool in trial["pool"]:
        assert pool["admitted"] == pool["retired"] == 12
        assert 0 < pool["n_waited"] <= 8  # 4 slots: the rest waited
        assert pool["chunks"] >= 2 and pool["chunk_host_s"] > 0
        assert (0 < pool["admit_wait_mean_s"] <= pool["admit_wait_max_s"])
        assert pool["admit_wait_p50_s"] <= pool["admit_wait_max_s"]
    # Per call, not running totals: every step admits the same 12.
    assert len({p["admitted"] for p in trial["pool"]}) == 1


def test_pack_and_sync_counters(trial):
    for pack, sync, stats in zip(
        trial["pack"], trial["sync"], trial["stats"]
    ):
        assert 0 < pack["host_s"] < stats["actor_train/perf/time_s"]
        assert stats["actor_gen/sync/bytes"] == sync["bytes"] > 0
        assert stats["actor_gen/sync/put_s"] == sync["put_s"]
        assert (stats["actor_gen/sync/time_s"]
                >= sync["put_s"] + sync["alias_copy_s"])
        # One device, one dtype: every leaf stays in place, and the
        # route counters reach the step stats key by key.
        assert sync["leaves_aliased"] > 0
        assert sync["leaves_resharded"] == sync["leaves_put"] == 0
        assert sync["bytes_resharded"] == sync["bytes_put"] == 0
        for k in ("leaves_aliased", "leaves_resharded", "leaves_put",
                  "bytes_resharded", "bytes_put"):
            assert stats[f"actor_gen/sync/{k}"] == sync[k]


def test_pack_counter_carries_rows_and_empty_rows(trial):
    """How often balancing over the mesh's rows engages: `last_pack_stats`
    and the `pack` counter track say how many rows a call packed and how
    many hold no real token (one device here: FFD's rows, none empty)."""
    for pack in trial["pack"]:
        assert pack["n_rows"] >= pack["n_micro_batches"] >= 1
        assert pack["empty_rows"] == 0
        assert pack["grid_tokens"] % pack["n_rows"] == 0
    counters = [
        e["args"] for e in trial["events"]
        if e.get("ph") == "C" and e["name"] == "pack"
    ]
    # One per train_batch call: two PPO minibatches a step, three steps.
    assert len(counters) == 6
    keys = {"n_rows", "empty_rows", "flash_live_tiles", "flash_grid_tiles"}
    assert all(set(c) == keys for c in counters)
    assert counters[-1] == {k: trial["pack"][-1][k] for k in keys}
    # What the flash kernels visit of the rows' squares.
    for pack in trial["pack"]:
        assert 0 < pack["flash_live_tiles"] <= pack["flash_grid_tiles"]


def test_compiles_are_charged_to_the_mfc_that_compiled(trial):
    first, _, third = trial["stats"]
    assert first["actor_gen/perf/compiles"] >= 1
    assert first["actor_train/perf/compile_s"] > 0
    # Step 3: the generator had to build its serving chunk again.
    assert third["actor_gen/perf/compiles"] >= 1
    assert third["actor_gen/perf/compile_s"] > 0
    assert (third["actor_gen/perf/compile_s"]
            >= third["actor_gen/perf/cache_load_s"] >= 0)
    for stats in trial["stats"]:  # the verifier never compiles
        assert stats["rew_inf/perf/compiles"] == 0
    compiles = [e for e in trial["events"] if e["name"] == "compile"]
    assert compiles and all(
        e["args"]["event"] in ("backend_compile_duration",
                               "cache_retrieval_time_sec")
        for e in compiles
    )


def test_step_one_carries_the_set_up_once(trial):
    first, second, third = trial["stats"]
    keys = {k for k in first if k.startswith("setup/")}
    assert keys == {"setup/" + k for k in (
        "to_import_s", "to_run_s", "build_s", "weights_s", "engines_s",
        "programs", "trace_s", "lower_s", "compile_s", "cache_load_s",
        "cache_hits", "cache_misses", "load_max_s",
    )}
    assert not any(k.startswith("setup/") for k in {**second, **third})
    # The process's life so far holds its way to the build, the build and
    # the step, one after another.
    assert 0 <= first["setup/to_import_s"] <= first["setup/to_run_s"]
    assert (first["setup/to_run_s"] + first["setup/build_s"]
            + first["time/step_s"] <= trial["age_s"])
    assert (0 < first["setup/weights_s"] + first["setup/engines_s"]
            <= first["setup/build_s"])
    # Every program was looked up in the cache of the trial's own: read
    # from it, or compiled and written to it.
    assert first["setup/programs"] >= 3  # generate, gradient, apply
    assert first["setup/programs"] == (
        first["setup/cache_hits"] + first["setup/cache_misses"])
    assert first["setup/trace_s"] > 0 and first["setup/lower_s"] > 0
    assert first["setup/compile_s"] > 0 or first["setup/cache_load_s"] > 0
    assert first["setup/load_max_s"] <= first["setup/cache_load_s"]


def test_the_ledger_has_a_row_a_program_under_the_span_that_compiled(trial):
    step1, _, step3 = trial["ledger"]
    assert len(step1["programs"]) == trial["stats"][0]["setup/programs"]
    spans = {r["span"] for r in step1["programs"]}
    # (the initialiser under `setup:weights` is cached a process: another
    # file's trial may have compiled it)
    assert {"grad_dispatch", "apply_dispatch"} <= spans
    for row in step1["programs"] + step3["programs"]:
        # (no byte columns: a CPU backend hands the HBM ledger no reader)
        assert set(row) == {"fun", "trace_s", "lower_s", "compile_s",
                            "cache_load_s", "hit", "written", "span",
                            "request", "t_ns"}
        assert row["request"].startswith(("mfc:", "setup:")), row
        assert row["hit"] == (row["cache_load_s"] > 0)
        assert not (row["hit"] and row["written"])
    # Step 3 built the generator's chunk again: traced and lowered in
    # full, and the executable read from the cache step 1 wrote it to.
    chunks = [r for r in step3["programs"] if r["span"] == "chunk_dispatch"]
    assert chunks and all(
        r["hit"] and r["trace_s"] > 0 and r["lower_s"] > 0 for r in chunks
    )


def test_trace_and_lower_seconds_reach_the_mfc_that_compiled(trial):
    first, second, third = trial["stats"]
    for node in ("actor_gen", "actor_train"):
        assert first[f"{node}/perf/trace_s"] > 0
        assert first[f"{node}/perf/lower_s"] > 0
    # Nothing new to the trainer in step 3; the generator's chunk is.
    assert third["actor_train/perf/trace_s"] == 0
    assert third["actor_train/perf/lower_s"] == 0
    assert third["actor_gen/perf/trace_s"] > 0
    # The phases are the step's own: they fit into its wall.
    for stats in trial["stats"]:
        spent = sum(
            v for k, v in stats.items()
            if k.endswith(("perf/trace_s", "perf/lower_s", "perf/compile_s"))
        )
        assert spent <= stats["time/step_s"]
    phased = [e for e in trial["events"] if e["name"].startswith("compile")]
    assert {(e["name"], e["args"]["phase"]) for e in phased} == {
        ("compile:trace", "trace"), ("compile:lower", "lower"),
        ("compile", "compile"), ("compile", "load"),
    }


def test_a_program_is_one_row_cold_and_then_warm(tmp_path):
    from areal_tpu.system import worker

    worker._install_compile_listener()

    def scaled_sum(x):
        return (x * 3.0).sum()

    x = np.arange(8, dtype=np.float32)
    rows = []
    with _own_cache(tmp_path / "jax_cache"):
        for step in (1, 2):
            with tracer.span("setup:weights"):
                assert float(jax.jit(scaled_sum)(x)) == 84.0
            jax.clear_caches()  # the next call starts over, cache aside
            tracer.close_step(step, 1.0)
            (row,) = [r for r in tracer.step_ledger()[-1]["programs"]
                      if "scaled_sum" in r["fun"]]
            rows.append(row)
    cold, warm = rows
    assert not cold["hit"] and cold["written"]
    assert cold["compile_s"] > 0 and cold["cache_load_s"] == 0
    assert warm["hit"] and not warm["written"] and warm["cache_load_s"] > 0
    for row in rows:
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert row["span"] == "setup:weights"
    counts = tracer.take_compiles()
    assert counts["perf/compiles"] >= 2
    assert counts["perf/cache_load_s"] >= warm["cache_load_s"]
    assert tracer.take_compiles() == dict.fromkeys(counts, 0.0)


_TRACE, _LOWER, _BACKEND, _LOAD = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


@pytest.fixture
def clock(monkeypatch):
    """The tracer's monotonic clock in the test's hands (seconds)."""
    now = [100.0]
    monkeypatch.setattr(tracer, "time", types.SimpleNamespace(
        monotonic_ns=lambda: int(now[0] * 1e9), time=time.time,
    ))
    return now


def test_a_phase_keeps_its_own_seconds(clock):
    """A jit traced inside another's trace, and an eager operation
    compiled while tracing, are inside the outer trace's seconds as jax
    reports them; the ledger counts each second once."""
    def end(event, at, seconds, **kw):
        clock[0] = at
        tracer.program_event(event, seconds, **kw)

    end(_TRACE, 100.3, 0.2, fun_name="inner")  # [100.1, 100.3]
    end(_TRACE, 100.5, 0.1, fun_name="eager")  # an eager op: a program
    end(_LOWER, 100.6, 0.1, fun_name="jit_eager")
    end(_BACKEND, 100.9, 0.3, fun_name="jit_eager")
    end(_TRACE, 101.0, 1.0, fun_name="outer")  # [100.0, 101.0], all of it
    end(_LOWER, 101.5, 0.5, fun_name="jit_outer")
    end(_LOAD, 101.9, 0.3)
    tracer.program_event("/jax/compilation_cache/cache_hits")
    end(_BACKEND, 102.0, 0.5, fun_name="jit_outer")
    tracer.close_step(1, 2.0)
    eager, outer = tracer.step_ledger()[-1]["programs"]
    assert eager["fun"] == "jit_eager" and outer["fun"] == "jit_outer"
    # The nested jit's trace ended first and went to the first row closed.
    assert eager["trace_s"] == pytest.approx(0.3)
    assert outer["trace_s"] == pytest.approx(1.0 - 0.2 - 0.1 - 0.1 - 0.3)
    assert outer["lower_s"] == pytest.approx(0.5)
    assert (outer["compile_s"], outer["cache_load_s"], outer["hit"]) == (
        pytest.approx(0.2), pytest.approx(0.3), True)
    counts = tracer.take_compiles()
    assert counts == {
        "perf/compiles": 2.0, "perf/compile_s": pytest.approx(0.8),
        "perf/cache_load_s": pytest.approx(0.3),
        "perf/trace_s": pytest.approx(0.6), "perf/lower_s": pytest.approx(0.6),
    }
    # trace + lower + backend phases: the two seconds that passed.
    assert sum(counts[k] for k in (
        "perf/trace_s", "perf/lower_s", "perf/compile_s"
    )) == pytest.approx(2.0)


def test_a_compile_phase_is_annotated_only_for_a_live_profiler(
        annotations, monkeypatch):
    """The listener fires when a phase has ENDED: like `host_pause`, the
    annotation is written at the end and carries the duration."""
    tracer.program_event(_LOWER, 0.25, fun_name="jit_gen")
    assert annotations == [
        ("open", "areal:compile",
         {"dur_ms": 250.0, "phase": "lower", "fun": "jit_gen"}),
        ("close", "areal:compile"),
    ]
    del annotations[:]
    tracer.program_event("/jax/compilation_cache/cache_misses")  # no phase
    tracer.program_event("/jax/core/compile/some_other_duration", 1.0)
    assert annotations == []
    # A process without jax has no profiler to write to; with jax and no
    # session the real annotation is inert.
    import sys

    monkeypatch.setattr(tracer, "_ANNOTATIONS", None)
    with monkeypatch.context() as m:
        m.setitem(sys.modules, "jax", None)
        tracer.program_event(_TRACE, 0.1, fun_name="f")
        assert tracer._ANNOTATIONS is None
    tracer.program_event(_TRACE, 0.1, fun_name="f")
    assert tracer._ANNOTATIONS[0] is jax.profiler.TraceAnnotation


def test_a_process_that_built_nothing_reports_no_set_up():
    with tracer.span("step"):
        pass
    assert not any(
        k.startswith("setup/") for k in tracer.close_step(1, 1.0)
    )
    with tracer.setup_span("build"):
        with tracer.setup_span("weights", model="actor"):
            pass
    stats = tracer.close_step(2, 1.0)
    assert 0 <= stats["setup/weights_s"] <= stats["setup/build_s"]
    assert stats["setup/to_run_s"] >= stats["setup/to_import_s"] >= 0
    assert stats["setup/programs"] == 0
    assert tracer.setup_take() == {}
    assert tracer.step_ledger()[-1]["spans"]["setup:weights"][0] == 1


MODEL_SCOPES = ("embed", "layer/attn_qkv", "layer/attn", "layer/attn_out",
                "layer/mlp", "final_norm", "head_logprob")


def _assert_scopes(text, scopes):
    # In the lowered text a scope opens a location (`"layer/mlp/dot...`),
    # sits inside one (`.../gen/prefill/embed/...`), or inside the
    # transform that wrapped it (`transpose(jvp(head_logprob))`).
    import re

    missing = [
        s for s in scopes
        if not re.search(r'["/(]%s[/)"]' % re.escape(s), text)
    ]
    assert not missing, missing


def test_train_step_programs_carry_every_scope(trial):
    grad = trial["spies"]["_get_grad_fn"][0].text()
    _assert_scopes(grad, ("train/grad",) + MODEL_SCOPES)
    assert "transpose(jvp(" in grad and "rematted_computation" in grad
    apply = trial["spies"]["_get_apply_fn"][0].text()
    _assert_scopes(apply, ("train/apply",))


def test_serving_chunk_program_carries_every_scope(trial):
    text = trial["spies"]["_get_serving_chunk_fn"][0].text()
    _assert_scopes(
        text, ("gen/serving_chunk", "gen/decode_step") + MODEL_SCOPES
    )


def _static_generate_text():
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), mesh,
        eos_token_id=7,
    )
    spies = {}
    _spy_on(eng, "_get_gen_fn", spies)
    sample = SequenceSample(
        keys={"packed_prompts"}, ids=["a", "b"],
        seqlens={"packed_prompts": [[5], [9]]},
        data={"packed_prompts": np.arange(8, 22, dtype=np.int32)},
    )
    eng.generate(
        sample, MicroBatchSpec(),
        GenerationHyperparameters(n=1, max_new_tokens=4), inflight=False,
    )
    assert eng.last_pool_stats.get("chunks") is None  # not the serving loop
    return spies["_get_gen_fn"][0].text()


def test_static_generate_program_carries_every_scope():
    _assert_scopes(
        _static_generate_text(),
        ("gen/prefill", "gen/decode_step") + MODEL_SCOPES,
    )


def _head_locations(text):
    """The `loc(...)` names of the operations that make the `[*, V]`
    logits: the einsum of `transformer._head`."""
    import re

    return [
        m.group(1) for m in re.finditer(r'loc\("([^"]*bsd,dv->bsv[^"]*)"', text)
    ]


@pytest.mark.parametrize("program", ["static", "serving"])
def test_decode_head_lowers_under_head_logprob(program, trial):
    """The decode head (`transformer._head` of `prefill`, `decode_step`
    and the serving chunk) carries the scope `head_logprob`, as the train
    head and the sampler do: `head_share` measures both heads."""
    if program == "serving":
        text = trial["spies"]["_get_serving_chunk_fn"][0].text()
        want = ("gen/decode_step/",)  # the chunk's own decode step
    else:
        text = _static_generate_text()
        want = ("gen/prefill/", "gen/decode_step/")
    locs = _head_locations(text)
    assert locs, "no logits einsum in the lowered program"
    for loc in locs:
        assert "/head_logprob/" in loc, loc
    for scope in want:
        assert any(scope in loc for loc in locs), (scope, locs)


def test_gae_program_is_scoped():
    from areal_tpu.ops.gae import gae_packed

    t = jnp.zeros((16,), jnp.float32)
    seg = jnp.ones((16,), jnp.int32)
    text = gae_packed.lower(t, t, seg, t, 1.0, 0.95).as_text(debug_info=True)
    _assert_scopes(text, ("ppo/gae",))


@pytest.mark.parametrize(
    "kernel", ["flash_fwd", "flash_dq", "flash_dkv", "ragged_paged"]
)
def test_every_pallas_call_is_named_and_scoped(kernel, monkeypatch):
    # Lowered for the TPU, as on a chip: the Mosaic call itself is there.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if kernel == "ragged_paged":
        from areal_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention_kernel as fn,
        )

        t, n_layers, n_pool, ps, mp = 40, 3, 64, 128, 4
        pool = jax.ShapeDtypeStruct((n_layers, n_pool, ps, 2 * 128), jnp.bfloat16)
        args = (
            jax.ShapeDtypeStruct((t, 12, 128), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((t, mp), jnp.int32),
            jax.ShapeDtypeStruct((t,), jnp.int32),
        )
    else:
        from areal_tpu.ops.pallas.flash_attention import flash_attention

        q = jax.ShapeDtypeStruct((2, 256, 12, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((2, 256, 2, 128), jnp.bfloat16)
        args = (q, kv, kv, jax.ShapeDtypeStruct((2, 256), jnp.int32))

        def fn(q, k, v, seg):
            return jax.grad(
                lambda q, k, v: flash_attention(q, k, v, seg)
                .astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args
    ).mlir_module()
    assert "tpu_custom_call" in text
    # The scope reaches op_name; the kernel's own name reaches the call.
    _assert_scopes(text, (kernel,))
    assert f'kernel_name = "{kernel}"' in text


def test_a_seed_past_int32_builds_the_same_model_as_eagerly():
    # The benchmark's driver hands out seeds a little over 2**31; a Python
    # int that large used to overflow the jitted initialiser's argument.
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.worker import _build_params_and_config

    cfg, seed = tiny_config(), 2**31 + 12345
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    _, params = _build_params_and_config(
        ModelAbstraction("random", {"config": cfg}), seed=seed, mesh=mesh
    )
    want = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    np.testing.assert_array_equal(params["embed"], want["embed"])

"""The program's own observability (PR 23): every tracer span is also a
profiler annotation `areal:<name>`, tracing never changes the schedule,
the master's step number crosses the hop to the workers, the engines keep
the counters the benchmark reads, compilation is charged to the MFC that
needed it, and every part of the device programs carries a stable name."""

import threading
import timeit

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


def test_decorator_annotates_with_tracing_off(annotations):
    @tracer.trace("load_data", cat="host")
    def f(x):
        return x + 1

    assert f(1) == 2
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

    _, stats = run_experiment(
        build_ppo_math(cfg, tok), tokenizer=tok, inspect=inspect
    )
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
    # (the engine's first set_params, at build, is under no span)
    assert parents["params_put"] == {None, "param_sync:actor_gen@0"}
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

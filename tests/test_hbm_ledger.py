"""The program's HBM ledger (PR 66): marks of the device's memory at the
open and the close of every request, phase and engine wait; a rise of the
peak owned by the innermost span open over it; the step record's `hbm`,
the `hbm/*` stats, the owners' bytes, the programs' byte columns and the
account with its remainder row -- all of it on a SCRIPTED device reader
handed to the tracer, as the worker hands it the real one."""

import ast
import threading

import jax
import jax.numpy as jnp
import pytest

from areal_tpu.base import tracer
from areal_tpu.system import worker
# Tier-1 collects `tests/` alone: the nine readers' cases come by import.
from benchmark.tests.test_hbm_readers import *  # noqa: F401,F403 — the cases

GB = 10 ** 9


@pytest.fixture(autouse=True)
def _fresh():
    def reset():
        tracer._reset_for_tests()
        worker._hbm_devices.clear()
        worker._hbm_programs_seen.clear()

    reset()
    yield
    reset()


class Devices:
    """`memory_stats()` of n devices in the test's hands."""

    def __init__(self, n=1, limit=16 * GB):
        self.stats = {
            i: {"bytes_in_use": 0, "peak_bytes_in_use": 0,
                "bytes_limit": limit}
            for i in range(n)
        }
        self.calls = 0

    def use(self, in_use, device=0):
        s = self.stats[device]
        s["bytes_in_use"] = in_use
        s["peak_bytes_in_use"] = max(s["peak_bytes_in_use"], in_use)

    def __call__(self):
        self.calls += 1
        return {i: dict(s) for i, s in self.stats.items()}


@pytest.fixture
def dev():
    d = Devices()
    tracer.hbm_readers(d)
    return d


def _rises(step=-1):
    return [
        (r["span"], r["request"], r["from"], r["to"])
        for r in tracer.step_ledger()[step]["hbm"]["rises"]
    ]


# ---------------- whose rise it is ----------------


def test_a_rise_belongs_to_the_innermost_span_open_over_it(dev):
    dev.use(1 * GB)
    with tracer.span("mfc:actor_gen@0:generate", cat="compute"):
        with tracer.span("generate"):
            dev.use(2 * GB)  # dispatch: seen at gen_wait's open
            with tracer.span("gen_chunk", cat="compute"):
                with tracer.span("gen_wait", cat="compute"):
                    dev.use(5 * GB)
            dev.use(3 * GB)
    tracer.close_step(1, 1.0)
    mfc = "mfc:actor_gen@0:generate"
    assert _rises() == [
        (tracer.BETWEEN_REQUESTS, tracer.BETWEEN_REQUESTS, 0, 1 * GB),
        # gen_chunk opened after the mark before: not open over all of it
        ("generate", mfc, 1 * GB, 2 * GB),
        ("gen_wait", mfc, 2 * GB, 5 * GB),
    ]


def test_what_rises_after_the_engine_returned_is_the_requests_own(dev):
    """The harness's reference check lives between `generate`'s close and
    the MFC's: self bytes, as self seconds are."""
    with tracer.span("mfc:actor_gen@0:generate", cat="compute"):
        with tracer.span("generate"):
            dev.use(2 * GB)
        dev.use(7 * GB)
        dev.use(1 * GB)
    tracer.close_step(1, 1.0)
    mfc = "mfc:actor_gen@0:generate"
    assert _rises() == [
        ("generate", mfc, 0, 2 * GB), (mfc, mfc, 2 * GB, 7 * GB),
    ]
    hbm = tracer.step_ledger()[-1]["hbm"]
    assert (hbm["peak"], hbm["in_use"]) == (7 * GB, 1 * GB)


def test_a_rise_outside_any_request_is_between_requests(dev):
    with tracer.span("fetch", cat="host"):
        pass
    dev.use(4 * GB)  # nobody's span: the harness, a hook on the logger
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        pass
    dev.use(6 * GB)
    tracer.close_step(1, 1.0)
    assert _rises() == [
        (tracer.BETWEEN_REQUESTS, tracer.BETWEEN_REQUESTS, 0, 4 * GB),
        (tracer.BETWEEN_REQUESTS, tracer.BETWEEN_REQUESTS, 4 * GB, 6 * GB),
    ]


def test_an_unmarked_span_owns_a_rise_it_was_open_over(dev):
    """Marks are taken at named spans only; ownership is any span's."""
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        with tracer.span("ppo_train", cat="compute"):
            with tracer.span("stats_sync", cat="compute"):
                pass  # marks: ppo_train is open over what follows
            dev.use(3 * GB)
            with tracer.span("stats_sync", cat="compute"):
                pass
    tracer.close_step(1, 1.0)
    assert _rises() == [
        ("ppo_train", "mfc:actor@0:train_step", 0, 3 * GB)
    ]


def test_nested_spans_on_two_threads(dev):
    """A mark on a thread with nothing open over the interval looks at the
    other threads' MARKED spans; its own stack comes first."""
    started, release = threading.Event(), threading.Event()

    def generate():
        with tracer.span("mfc:actor_gen@1:generate", cat="compute"):
            with tracer.span("generate"):
                started.set()
                release.wait(10)

    t = threading.Thread(target=generate)
    t.start()
    assert started.wait(10)
    dev.use(2 * GB)
    # This thread opens a request: nothing of its own was open before.
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        dev.use(3 * GB)
        with tracer.span("stats_sync", cat="compute"):
            dev.use(4 * GB)
    release.set()
    t.join(10)
    assert not t.is_alive()
    tracer.close_step(1, 1.0)
    gen, train = "mfc:actor_gen@1:generate", "mfc:actor@0:train_step"
    assert _rises() == [
        ("generate", gen, 0, 2 * GB),  # seen from the other thread
        (train, train, 2 * GB, 3 * GB),
        ("stats_sync", train, 3 * GB, 4 * GB),
    ]


def test_the_fullest_of_four_devices_is_the_one_reported():
    dev = Devices(4)
    tracer.hbm_readers(dev)
    for i, b in enumerate((3 * GB, 9 * GB, 5 * GB, 1 * GB)):
        dev.use(b, device=i)
    dev.use(2 * GB, device=1)  # chip 1 holds the peak, chip 2 is fullest now
    with tracer.span("mfc:actor@0:train_step", cat="compute") as _:
        pass
    stats = tracer.close_step(1, 1.0)
    mark = tracer.hbm_marks()[-1]
    assert (mark["device"], mark["peak"], mark["in_use"]) == (
        1, 9 * GB, 5 * GB)
    assert stats["hbm/peak_gb"] == 9.0 and stats["hbm/in_use_gb"] == 5.0
    assert tracer.step_ledger()[-1]["hbm"]["account"]["device"] == 1


# ---------------- what costs what ----------------


def test_marks_are_taken_at_the_named_spans_only(dev):
    with tracer.span("mfc:actor@0:train_step", cat="compute"):  # 2
        for name in ("pack", "mb_upload", "grad_dispatch", "mfc_perf"):
            with tracer.span(name, cat="host"):
                pass
        with tracer.span("stats_sync", cat="compute"):  # 2
            pass
    with tracer.setup_span("weights"):  # 2
        pass
    for name in ("fetch", "clear_cache", "param_sync:actor_gen"):  # 6
        with tracer.span(name, cat="host"):
            pass
    assert dev.calls == 12
    assert [m["label"] for m in tracer.hbm_marks()[:4]] == [
        "open:mfc:actor@0:train_step", "open:stats_sync",
        "close:stats_sync", "close:mfc:actor@0:train_step",
    ]
    stats = tracer.close_step(1, 1.0)  # and one at the close
    assert stats["hbm/marks"] == 13.0 and stats["hbm/mark_s"] > 0


def test_a_backend_without_memory_stats_costs_one_call_and_no_key():
    calls = []
    tracer.hbm_readers(lambda: calls.append(1))  # returns None, as CPU
    for step in (1, 2):
        with tracer.span("mfc:actor@0:train_step", cat="compute") as _:
            with tracer.span("stats_sync", cat="compute"):
                pass
        stats = tracer.close_step(step, 1.0)
        assert not [k for k in stats if k.startswith("hbm/")]
        assert "hbm" not in tracer.step_ledger()[-1]
    assert len(calls) == 1
    assert tracer.hbm_marks() == [] and tracer.hbm_take() == {}


def test_the_worker_hands_a_cpu_backend_no_reader():
    from areal_tpu.base.topology import ParallelConfig, make_mesh

    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    worker._hbm_watch(object(), mesh)
    assert tracer._hbm["reader"] is None and not worker._hbm_devices
    with tracer.span("mfc:actor@0:train_step", cat="compute") as _:
        pass
    assert tracer.close_step(1, 1.0).keys() == {"time/slow_excess_s"}


def test_the_tracer_still_imports_no_jax():
    tree = ast.parse(open(tracer.__file__).read())
    imported = {
        (n.module if isinstance(n, ast.ImportFrom) else a.name) or ""
        for n in ast.walk(tree)
        if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names
    }
    assert not [m for m in imported if m.split(".")[0] == "jax"]


# ---------------- the step record and the stats ----------------


def test_rises_is_empty_in_a_steady_step(dev):
    for step in (1, 2, 3):
        with tracer.span("mfc:actor@0:train_step", cat="compute"):
            dev.use(8 * GB)
            dev.use(5 * GB)
        stats = tracer.close_step(step, 1.0)
        hbm = tracer.step_ledger()[-1]["hbm"]
        assert (hbm["peak"], hbm["in_use"]) == (8 * GB, 5 * GB)
        assert stats["hbm/peak_gb"] == 8.0 and stats["hbm/in_use_gb"] == 5.0
        if step == 1:
            assert stats["hbm/peak_rise_gb"] == 8.0 and len(hbm["rises"]) == 1
        else:
            assert stats["hbm/peak_rise_gb"] == 0.0 and hbm["rises"] == []
            assert "owners" not in hbm  # no walk over the live arrays


def test_the_first_close_returns_the_owner_keys_once(dev):
    walks = []

    def owners(device):
        walks.append(device)
        return {"weights": 3 * GB, "moments": 6 * GB, "cache": 0,
                "other_live": GB // 2}

    tracer.hbm_readers(dev, owners=owners)
    with tracer.setup_span("build"):
        dev.use(4 * GB)
    dev.use(5 * GB)  # between `built` and the step
    once = ("hbm/peak_before_step_gb", "hbm/peak_step1_gb", "hbm/weights_gb",
            "hbm/moments_gb", "hbm/cache_gb", "hbm/other_live_gb",
            "hbm/code_gb", "hbm/temp_gb", "hbm/temp_max_gb",
            "hbm/unaccounted_gb", "hbm/released_gb", "hbm/programs_read_s",
            "hbm/owners_read_s")
    always = ("hbm/in_use_gb", "hbm/peak_gb", "hbm/peak_rise_gb",
              "hbm/marks", "hbm/mark_s")
    for step in (1, 2, 3):
        with tracer.span("fetch", cat="host"):
            pass
        with tracer.span("mfc:actor@0:train_step", cat="compute"):
            dev.use(13 * GB if step < 3 else 14 * GB)
            dev.use(10 * GB)
        stats = tracer.close_step(step, 1.0)
        keys = {k for k in stats if k.startswith("hbm/")}
        assert keys == set(always + once if step == 1 else always), step
        if step == 1:
            assert stats["hbm/peak_before_step_gb"] == 5.0
            assert stats["hbm/peak_step1_gb"] == 13.0
            assert stats["hbm/weights_gb"] == 3.0
            assert stats["hbm/moments_gb"] == 6.0
            assert stats["hbm/other_live_gb"] == 0.5
            # peak = owners + code + temp + the remainder, by construction
            assert stats["hbm/unaccounted_gb"] == 13.0 - 9.5
    # The walk: at the first close, and again only where the peak rose.
    assert walks == [0, 0]
    assert "owners" not in tracer.step_ledger()[1]["hbm"]
    assert tracer.step_ledger()[2]["hbm"]["owners"]["weights"] == 3 * GB
    assert tracer.step_ledger()[2]["hbm"]["rises"][0]["to"] == 14 * GB


def test_a_worker_of_its_own_process_takes_the_close_with_its_next_reply(dev):
    """Its `clear_cache` closes the step and nobody reads that reply: the
    stats wait for `hbm_take`, once."""
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        dev.use(2 * GB)
    assert tracer.hbm_take() == {}  # nothing is closed yet
    tracer._hbm_close()
    stats = tracer.hbm_take()
    assert stats["hbm/peak_gb"] == 2.0 and "hbm/unaccounted_gb" in stats
    assert tracer.hbm_take() == {}


# ---------------- the request's perf keys ----------------


def test_perf_hbm_comes_from_the_closing_mark_and_the_kill_still_trips(
        dev, monkeypatch):
    span = tracer.span("mfc:actor@0:train_step", cat="compute")
    with span:
        dev.use(15 * GB)
        before = dev.calls
    assert dev.calls == before + 1  # the close's one reading, no second
    perf = worker._hbm_perf(span.mark)
    assert perf == {"perf/hbm_gb": 15.0, "perf/hbm_frac": 15 / 16}
    monkeypatch.setenv("AREAL_HBM_KILL_FRAC", "0.95")
    worker._check_hbm_kill(perf)
    monkeypatch.setenv("AREAL_HBM_KILL_FRAC", "0.9")
    with pytest.raises(MemoryError, match="0.9"):
        worker._check_hbm_kill(perf)
    # Nothing to read: no key, and nothing to trip on.
    assert worker._hbm_perf(None) == {}
    assert tracer.span("pack").mark is None


def test_the_kill_sees_the_fullest_chip_not_chip_zero(monkeypatch):
    dev = Devices(4)
    tracer.hbm_readers(dev)
    span = tracer.span("mfc:actor_gen@1:generate", cat="compute")
    with span:
        dev.use(1 * GB, device=0)
        dev.use(15.5 * GB, device=3)
    monkeypatch.setenv("AREAL_HBM_KILL_FRAC", "0.9")
    with pytest.raises(MemoryError):
        worker._check_hbm_kill(worker._hbm_perf(span.mark))


# ---------------- owners: each buffer once ----------------


class _Engine:
    def __init__(self, **owned):
        self.owned = owned

    def hbm_owned(self):
        return self.owned


class _Model:
    def __init__(self, engine):
        self.engine = engine


class _Worker:
    def __init__(self, **engines):
        self.models = {k: _Model(e) for k, e in engines.items()}


def test_an_aliased_weight_tree_is_counted_once():
    d = jax.devices()[0]
    weights = {"w": jnp.ones((256, 128), jnp.float32),
               "b": jnp.ones((128,), jnp.float32)}
    moments = jax.tree.map(jnp.zeros_like, weights)
    # The colocated generator's tree: other Array objects, same buffers.
    aliased = jax.tree.map(lambda a: jax.device_put(a, d), weights)
    assert aliased["w"] is not weights["w"]
    pool = jnp.zeros((64, 64), jnp.bfloat16)
    stray = jnp.ones((1000,), jnp.float32)  # nobody's: other_live
    w = _Worker(
        actor=_Engine(weights=weights, moments=moments),
        actor_gen=_Engine(weights=aliased, cache=[(pool, None)]),
        reward=_Model(None).engine,
    )
    worker._hbm_workers.add(w)
    try:
        owners = worker._hbm_owners(d.id)
        elsewhere = worker._hbm_owners(d.id + 100)
    finally:
        worker._hbm_workers.discard(w)
    n = 256 * 128 * 4 + 128 * 4
    assert owners["weights"] == n and owners["moments"] == n
    assert owners["cache"] == 64 * 64 * 2
    assert owners["other_live"] >= stray.nbytes
    assert elsewhere == {
        "weights": 0, "moments": 0, "cache": 0, "other_live": 0}


def test_the_engines_say_what_they_keep():
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.engines.inference import InferenceEngine
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    train = TrainEngine(cfg, params, mesh)
    assert set(train.hbm_owned()) == {"weights", "moments"}
    assert train.hbm_owned()["weights"] is train.params
    assert train.hbm_owned()["moments"] is train.opt_state
    inf = InferenceEngine(cfg, params, mesh)
    assert set(inf.hbm_owned()) == {"weights"}
    gen = GeneratorEngine(cfg, params, mesh, eos_token_id=1)
    owned = gen.hbm_owned()
    assert owned["weights"] is gen.params and owned["cache"] == []
    # The existing counters keep their names for analysis/profile.py.
    assert {"param_bytes", "opt_bytes", "compiles"} <= set(
        train.perf_counters())
    assert {"param_bytes", "compiles"} <= set(gen.perf_counters())


def test_what_was_released_since_the_peak_and_the_reserve_are_named(dev):
    """The 1.5B cells (PR 66): the peak is set in the warm-up generate,
    while the generator still holds the weights it was built with; the
    gradient program's temporaries lie in the runtime's reserve."""
    tracer.hbm_readers(dev, owners=lambda device: {
        "weights": 3 * GB, "moments": 6 * GB, "cache": 0, "other_live": 0})
    with tracer.setup_span("build"):
        dev.use(12 * GB)  # two trees of weights and the moments
    with tracer.span("mfc:actor_gen@0:generate", cat="compute"):
        dev.use(13 * GB)  # the harness's reference check
        dev.use(12 * GB)
    with tracer.span("param_sync:actor_gen@0", cat="comms"):
        dev.use(9 * GB)  # the generator's own tree goes
    dev.stats[0].update(bytes_reserved=4 * GB, peak_bytes_reserved=4 * GB)
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        dev.use(12 * GB)  # the gradients; the temporaries are not in use
        dev.use(9 * GB)
    stats = tracer.close_step(1, 1.0)
    assert stats["hbm/peak_gb"] == 13.0 and stats["hbm/unaccounted_gb"] == 4.0
    assert stats["hbm/released_gb"] == 3.0  # in use at the open: 12, now 9
    assert stats["hbm/reserved_gb"] == stats["hbm/peak_reserved_gb"] == 4.0
    text = tracer.setup_report(stats, [])
    assert "set inside `mfc:actor_gen@0:generate`" in text
    assert "of the remainder 3.0000 GB were in use when the peak's" in text
    assert "reserve, which `peak_bytes_in_use` does not hold, stood at 4.0" \
        in text
    assert tracer.hbm_marks()[-1]["reserved"] == 4 * GB


# ---------------- programs and the account ----------------

_BACKEND = "/jax/core/compile/backend_compile_duration"


def _stub_programs(loaded):
    """A `programs()` reader over a list the test appends to."""
    seen = [0]

    def read(expect):
        new, seen[0] = loaded[seen[0]:], len(loaded)
        if len(new) == expect:  # in order: no name is read back
            new = [dict(e, name=None) for e in new]
        return new, {0: sum(e["code_b"] for e in loaded)}

    return read


def _exe(name, code, temp, arg=0, out=0, alias=0):
    return {"name": name, "code_b": code, "temp_b": temp, "arg_b": arg,
            "out_b": out, "alias_b": alias}


def test_program_rows_gain_the_five_byte_columns_and_the_remainder_is_printed(
        dev):
    loaded = []
    tracer.hbm_readers(
        dev, _stub_programs(loaded),
        lambda device: {"weights": 3 * GB, "moments": 6 * GB, "cache": 0,
                        "other_live": 100 * 10 ** 6},
    )
    with tracer.setup_span("build"):
        with tracer.setup_span("weights"):
            tracer.program_event(_BACKEND, 0.1, fun_name="jit(init)")
            loaded.append(_exe("jit_init", 10 ** 6, 2 * 10 ** 6))
            dev.use(3 * GB)
    with tracer.span("mfc:actor_gen@0:generate", cat="compute"):
        with tracer.span("generate"):
            with tracer.span("gen_dispatch", cat="compute"):
                tracer.program_event(_BACKEND, 2.0, fun_name="jit(rollout)")
                loaded.append(_exe("jit_rollout", 40 * 10 ** 6, GB))
            dev.use(9 * GB)
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        with tracer.span("grad_dispatch", cat="compute"):
            # two programs of one name, and one the ledger has no row for
            tracer.program_event(_BACKEND, 3.0, fun_name="jit(grad_step)")
            tracer.program_event(_BACKEND, 3.0, fun_name="jit(grad_step)")
            loaded.append(_exe("jit_grad_step", 300 * 10 ** 6, 2 * GB,
                               arg=9 * GB, out=GB, alias=GB))
            loaded.append(_exe("jit_eager_op", 10 ** 5, 0))
            loaded.append(_exe("jit_grad_step", 200 * 10 ** 6, GB))
        with tracer.span("stats_sync", cat="compute"):
            dev.use(13 * GB)
        dev.use(9 * GB)
    stats = tracer.close_step(1, 20.0)
    rows = tracer.step_ledger()[-1]["programs"]
    assert [(r["fun"], r["request"], r["code_b"], r["temp_b"]) for r in rows
            ] == [
        ("jit(init)", "setup:weights", 10 ** 6, 2 * 10 ** 6),
        ("jit(rollout)", "mfc:actor_gen@0:generate", 40 * 10 ** 6, GB),
        ("jit(grad_step)", "mfc:actor@0:train_step", 300 * 10 ** 6, 2 * GB),
        ("jit(grad_step)", "mfc:actor@0:train_step", 200 * 10 ** 6, GB),
    ]
    assert (rows[2]["arg_b"], rows[2]["out_b"], rows[2]["alias_b"]) == (
        9 * GB, GB, GB)
    assert rows[2]["span"] == "grad_dispatch"
    code = (10 ** 6 + 40 * 10 ** 6 + 500 * 10 ** 6 + 10 ** 5) / GB
    assert stats["hbm/code_gb"] == pytest.approx(code)
    assert stats["hbm/temp_max_gb"] == 2.0
    assert stats["hbm/temp_gb/mfc:actor@0:train_step"] == 2.0
    assert stats["hbm/temp_gb/mfc:actor_gen@0:generate"] == 1.0
    assert stats["hbm/temp_gb/setup:weights"] == 0.002
    # The train step set the peak: its programs' temporaries are the row.
    assert stats["hbm/temp_gb"] == 2.0
    assert stats["hbm/unaccounted_gb"] == pytest.approx(
        13.0 - 9.1 - code - 2.0)
    account = tracer.step_ledger()[-1]["hbm"]["account"]
    assert (account["span"], account["request"]) == (
        "stats_sync", "mfc:actor@0:train_step")
    assert sum(account["rows"].values()) == account["peak"] == 13 * GB
    text = tracer.setup_report(stats, rows)
    assert "set-up ledger" in text  # the first table stays
    assert ("HBM ledger: peak 13.0000 GB on device 0, set inside "
            "`stats_sync` (request `mfc:actor@0:train_step`)") in text
    lines = [ln.split() for ln in text.splitlines()]
    for name, gb in (("weights", "3.0000"), ("moments", "6.0000"),
                     ("cache", "0.0000"), ("other_live", "0.1000"),
                     ("temp", "2.0000")):
        assert [name, gb, "GB"] in lines, name
    (rest,) = [ln for ln in lines if ln[:1] == ["unaccounted"]]
    assert float(rest[1]) == pytest.approx(13.0 - 9.1 - code - 2.0, abs=1e-4)
    assert "4 of 4 programs have their bytes" in text
    temps = text.split("the largest temporaries:\n")[1].splitlines()
    assert "jit(grad_step)" in temps[0] and "2000.0 MB" in temps[0]
    assert "[mfc:actor@0:train_step]" in temps[0]
    # Nothing compiles in a steady step: the executables are not read.
    reads = tracer._hbm["programs_read_s"]
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        pass
    assert "hbm/code_gb" not in tracer.close_step(2, 1.0)
    assert tracer._hbm["programs_read_s"] == reads


def test_temp_is_of_the_programs_loaded_while_the_peaks_span_raised_it(dev):
    """`sala` (PR 66): the engine's `jit(gen)` has the request's largest
    temporaries, but the peak is set after `generate` closed, by the
    reference check's `jit(_layer)`: that one's are the row."""
    loaded = []
    tracer.hbm_readers(dev, _stub_programs(loaded))
    with tracer.span("mfc:actor_gen@0:generate", cat="compute"):
        with tracer.span("generate"):
            with tracer.span("gen_dispatch", cat="compute"):
                tracer.program_event(_BACKEND, 2.0, fun_name="jit(gen)")
                loaded.append(_exe("jit_gen", 50 * 10 ** 6, 3 * GB))
            with tracer.span("gen_wait", cat="compute"):
                dev.use(9 * GB)
        tracer.program_event(_BACKEND, 1.0, fun_name="jit(_layer)")
        loaded.append(_exe("jit__layer", 366 * 10 ** 6, GB))
        dev.use(10 * GB)
        with tracer.span("fetch", cat="host"):  # any mark inside the check
            pass
        dev.use(11 * GB)
        dev.use(8 * GB)
    stats = tracer.close_step(1, 1.0)
    assert stats["hbm/temp_gb/mfc:actor_gen@0:generate"] == 3.0
    assert stats["hbm/temp_gb"] == 1.0
    assert stats["hbm/released_gb"] == 1.0  # 9 GB at the open, 8 at the close
    account = tracer.step_ledger()[-1]["hbm"]["account"]
    assert account["span"] == "mfc:actor_gen@0:generate"
    assert [(r["span"], r["to"]) for r in
            tracer.step_ledger()[-1]["hbm"]["rises"]] == [
        ("gen_wait", 9 * GB), ("mfc:actor_gen@0:generate", 10 * GB),
        ("mfc:actor_gen@0:generate", 11 * GB)]


def test_temporaries_that_lie_in_the_reserve_are_not_in_the_peak(dev):
    """`mellum2` (PR 66): the gradient program declares 5.4 GB of
    temporaries and the train step's peak stands 0.6 GB over what was in
    use before it: the row holds no more than that."""
    loaded = []
    tracer.hbm_readers(dev, _stub_programs(loaded))
    dev.use(5 * GB)
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        with tracer.span("grad_dispatch", cat="compute"):
            tracer.program_event(_BACKEND, 9.0, fun_name="jit(grad_acc_fn)")
            loaded.append(_exe("jit_grad_acc_fn", 240 * 10 ** 6, 5 * GB))
        with tracer.span("stats_reduce", cat="compute"):
            dev.use(5 * GB + 600 * 10 ** 6)
        dev.use(5 * GB)
    stats = tracer.close_step(1, 1.0)
    assert stats["hbm/temp_max_gb"] == 5.0
    assert stats["hbm/temp_gb"] == pytest.approx(0.6)
    assert stats["hbm/unaccounted_gb"] == pytest.approx(5.0 - 0.24)


def test_the_real_readers_measure_a_loaded_program():
    """`_hbm_programs` and the join, on this backend's own executables."""
    worker._install_compile_listener()
    dev = Devices()
    d = jax.devices()[0]
    worker._hbm_devices[d.id] = d
    # What other tests of this process loaded is known already: the
    # reader measures what loads from here on.
    worker._hbm_programs_seen.update(
        ((id(e), e.fingerprint), (0, []))
        for e in d.client.live_executables())
    tracer.hbm_readers(dev, worker._hbm_programs, worker._hbm_owners)

    def hbm_ledger_probe(x):
        return (x @ x.T).sum()

    with tracer.span("mfc:actor@0:inference", cat="compute"):
        jax.jit(hbm_ledger_probe)(jnp.ones((64, 32), jnp.float32))
        dev.use(GB)
    stats = tracer.close_step(1, 1.0)
    (row,) = [r for r in tracer.step_ledger()[-1]["programs"]
              if "hbm_ledger_probe" in r["fun"]]
    assert row["arg_b"] == 64 * 32 * 4 and row["out_b"] == 4
    assert row["temp_b"] >= 64 * 64 * 4 and row["code_b"] >= 0
    assert row["request"] == "mfc:actor@0:inference"
    assert stats["hbm/temp_gb/mfc:actor@0:inference"] >= 64 * 64 * 4 / GB
    assert stats["hbm/programs_read_s"] > 0
    # A second read measures nothing twice.
    assert worker._hbm_programs(0)[0] == []


# ---------------- a live profiler, a fault ----------------


def test_a_rise_is_annotated_and_a_steady_mark_is_not(dev, tmp_path):
    from tests.test_program_tracing import _FakeAnnotation, _FakeStep

    tracer.configure("t", dir=str(tmp_path), enabled=True, force=True)
    log = _FakeAnnotation.log = []
    saved, tracer._ANNOTATIONS = tracer._ANNOTATIONS, (
        _FakeAnnotation, _FakeStep)
    try:
        for _ in range(2):
            with tracer.span("mfc:actor@0:train_step", cat="compute"):
                dev.use(2 * GB)
    finally:
        tracer._ANNOTATIONS = saved
    peaks = [a for a in log if a[1] == "areal:hbm_peak"]
    assert [a[0] for a in peaks] == ["open", "close"]  # once: the rise
    kw = peaks[0][2]
    assert (kw["span"], kw["from"], kw["to"]) == (
        "mfc:actor@0:train_step", 0, 2 * GB)
    assert kw["dur_ms"] >= 0
    tracer.flush()
    _, events = tracer.read_shard(tracer.shard_path())
    (ev,) = [e for e in events if e["name"] == "hbm_peak"]
    assert ev["args"] == {
        "span": "mfc:actor@0:train_step", "from": 0, "to": 2 * GB}


def test_a_flight_dump_carries_the_marks_and_the_owners(dev, tmp_path):
    tracer.hbm_readers(dev, owners=lambda device: {"weights": 3 * GB})
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        dev.use(5 * GB)
    path = tracer.flight_dump("worker_dead", dir=str(tmp_path))
    (dump,) = tracer.read_flight_dumps(str(tmp_path))
    assert dump["path"] == path
    hbm = dump["hbm"]
    assert [m["label"] for m in hbm["marks"]] == [
        "open:mfc:actor@0:train_step", "close:mfc:actor@0:train_step"]
    assert hbm["rises"][0]["to"] == 5 * GB
    assert hbm["owners"]["weights"] == 3 * GB
    tracer._reset_for_tests()  # no reader: the dump has no such part
    tracer.flight_dump("worker_dead", dir=str(tmp_path))
    assert "hbm" not in tracer.read_flight_dumps(str(tmp_path))[0]


def test_the_walk_makes_no_view_of_a_sharded_array():
    """On the chip the views kept the four-chip cell's generator weights
    alive past `release_params()` (PR 66): a sharded array is counted by
    itself, an even share a device, or by the views it already has."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    split = jax.device_put(
        jnp.ones((8, 128), jnp.float32), NamedSharding(mesh, P("x")))
    whole = jax.device_put(
        jnp.ones((8, 128), jnp.float32), NamedSharding(mesh, P()))
    seen = jax.device_put(
        jnp.ones((8, 128), jnp.float32), NamedSharding(mesh, P("x")))
    _ = seen.addressable_shards  # as `buffers_alias` leaves the weights
    w = _Worker(actor=_Engine(weights=[split, whole], moments=[seen]))
    first = jax.devices()[0].id
    nobodys = worker._hbm_owners(first)["other_live"]  # no engine yet
    worker._hbm_workers.add(w)
    live = len(jax.live_arrays())
    try:
        on = {d.id: worker._hbm_owners(d.id) for d in jax.devices()[:5]}
    finally:
        worker._hbm_workers.discard(w)
    assert len(jax.live_arrays()) == live
    assert "addressable_shards" not in split.__dict__
    assert "addressable_shards" not in whole.__dict__
    for d in jax.devices()[:4]:
        assert on[d.id]["weights"] == 8 * 128 * 4 // 4 + 8 * 128 * 4
        assert on[d.id]["moments"] == 8 * 128 * 4 // 4
    assert on[jax.devices()[4].id]["weights"] == 0
    # The views `seen` already had are live arrays of their own: counted
    # with their owner, by their pointers, not again as `other_live`.
    assert nobodys - on[first]["other_live"] == (
        on[first]["weights"] + on[first]["moments"])


def test_a_reader_that_raises_is_dropped_and_the_span_closes(dev):
    def broken(expect):
        raise RuntimeError("no such executable")

    tracer.hbm_readers(dev, broken, lambda device: 1 / 0)
    with tracer.span("mfc:actor@0:train_step", cat="compute"):
        tracer.program_event(_BACKEND, 1.0, fun_name="jit(grad_fn)")
        dev.use(2 * GB)
    assert tracer.open_spans() == {}
    stats = tracer.close_step(1, 1.0)
    assert stats["hbm/peak_gb"] == 2.0 and stats["hbm/weights_gb"] == 0.0
    assert stats["hbm/code_gb"] == 0.0
    assert [(e["reader"], e["error"][:12]) for e in tracer.flight_events()
            if e["kind"] == "hbm_reader_failed"] == [
        ("programs", "RuntimeError"), ("owners", "ZeroDivision")]
    assert tracer._hbm["programs"] is None and tracer._hbm["owners"] is None

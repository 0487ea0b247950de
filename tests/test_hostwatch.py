"""The program's own host watch (areal_tpu/base/hostwatch.py) with the
tick and the /proc readers injected: a late wake is counted and handed
on with every thread's open spans, the kernel's counters come out as
differences per step, and a host without /proc/pressure or schedstat
simply has no such keys."""

import gc
import threading
import time

import pytest

from areal_tpu.base import hostwatch, tracer

MS = 1_000_000


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer._reset_for_tests()
    yield
    tracer._reset_for_tests()


def _proc(root, tasks=None, psi=None):
    """A fake /proc: `tasks` maps a thread id to (cpu_ns, wait_ns), `psi`
    a resource to its `some total=` microseconds."""
    for tid, (cpu, wait) in (tasks or {}).items():
        d = root / "self" / "task" / str(tid)
        d.mkdir(parents=True, exist_ok=True)
        (d / "schedstat").write_text(f"{cpu} {wait} 17\n")
    for name, total in (psi or {}).items():
        d = root / "pressure"
        d.mkdir(exist_ok=True)
        (d / name).write_text(
            f"some avg10=0.00 avg60=0.00 avg300=0.00 total={total}\n"
            f"full avg10=0.00 avg60=0.00 avg300=0.00 total=1\n"
        )
    return str(root)


def _watch(proc, on_pause=None):
    w = hostwatch.HostWatch(on_pause=on_pause, proc=proc, start=False)
    return w


@pytest.mark.parametrize(
    "woke_after_ms,late_ms",
    [(20, 0), (119, 0), (120.0, 0), (121, 101), (520, 500)],
    ids=["on-time", "99ms-late", "100ms-late", "101ms-late", "half-a-second"],
)
def test_a_wake_more_than_100ms_past_due_is_a_pause(
        tmp_path, woke_after_ms, late_ms):
    pauses = []
    w = _watch(_proc(tmp_path), lambda *a: pauses.append(a))
    try:
        t0 = 5_000 * MS
        w.tick(t0, t0 + int(woke_after_ms * MS), 7 * MS)
        rec = w.take()
    finally:
        w.stop()
    assert rec["late_s"] == pytest.approx(late_ms / 1e3)
    assert rec["late_max_s"] == pytest.approx(late_ms / 1e3)
    if late_ms:
        assert pauses == [
            (t0 + 20 * MS, t0 + int(woke_after_ms * MS), 7 * MS)
        ]
    else:
        assert pauses == []


def test_late_seconds_add_up_and_reset_with_each_take(tmp_path):
    w = _watch(_proc(tmp_path))
    try:
        w.tick(0, 220 * MS)
        w.tick(220 * MS, 240 * MS)
        w.tick(240 * MS, 660 * MS)
        first, second = w.take(), w.take()
    finally:
        w.stop()
    assert first["late_s"] == pytest.approx(0.6)
    assert first["late_max_s"] == pytest.approx(0.4)
    assert second["late_s"] == second["late_max_s"] == 0.0


def test_a_failing_pause_hook_never_takes_the_ticker_down(tmp_path):
    def boom(due, woke, cpu):
        raise RuntimeError("hook")

    w = _watch(_proc(tmp_path), boom)
    try:
        w.tick(0, 500 * MS)
        assert w.take()["late_s"] == pytest.approx(0.48)
    finally:
        w.stop()


def test_counters_are_differences_per_step(tmp_path):
    proc = _proc(
        tmp_path, tasks={11: (4_000_000_000, 1_000_000_000),
                         12: (1_000_000_000, 0)},
        psi={"cpu": 7_000_000, "memory": 0, "io": 50_000},
    )
    w = _watch(proc)
    try:
        _proc(tmp_path, tasks={11: (4_500_000_000, 1_250_000_000),
                               12: (1_000_000_000, 50_000_000),
                               13: (100_000_000, 0)},
              psi={"cpu": 7_300_000, "memory": 0, "io": 50_000})
        first = w.take()
        second = w.take()  # nothing moved since
    finally:
        w.stop()
    assert first["cpu_s"] == pytest.approx(0.6)
    assert first["runq_wait_s"] == pytest.approx(0.3)
    assert first["psi_cpu_s"] == pytest.approx(0.3)
    assert first["psi_mem_s"] == first["psi_io_s"] == 0.0
    for key in ("cpu_s", "runq_wait_s", "psi_cpu_s", "psi_io_s"):
        assert second[key] == 0.0, key
    # rusage is the real process's: differences, so small and not negative.
    for key in ("invol_switches", "major_faults", "minor_faults"):
        assert 0 <= second[key] < 1e6, key
    assert 0 <= first["read_s"] < 1.0


def test_a_thread_that_ended_never_makes_a_counter_negative(tmp_path):
    proc = _proc(tmp_path, tasks={1: (10**9, 10**9), 2: (5 * 10**9, 10**9)})
    w = _watch(proc)
    try:
        (tmp_path / "self" / "task" / "2" / "schedstat").unlink()
        rec = w.take()
    finally:
        w.stop()
    assert rec["cpu_s"] == 0.0 and rec["runq_wait_s"] == 0.0


@pytest.mark.parametrize(
    "tasks,psi,absent",
    [
        ({1: (1, 1)}, None, ("psi_cpu_s", "psi_mem_s", "psi_io_s")),
        (None, {"cpu": 1}, ("cpu_s", "runq_wait_s", "psi_mem_s", "psi_io_s")),
        (None, None, ("cpu_s", "runq_wait_s", "psi_cpu_s", "psi_mem_s",
                      "psi_io_s")),
    ],
    ids=["no-pressure", "no-schedstat", "no-proc"],
)
def test_keys_are_absent_where_proc_has_no_such_file(
        tmp_path, tasks, psi, absent):
    w = _watch(_proc(tmp_path, tasks=tasks, psi=psi))
    try:
        rec = w.take()
    finally:
        w.stop()
    for key in absent:
        assert key not in rec, key
    for key in ("late_s", "late_max_s", "gc_s", "gc_max_s", "gc_gen2",
                "read_s"):
        assert key in rec, key
    if psi:
        assert rec["psi_cpu_s"] == 0.0
    if tasks:
        assert rec["cpu_s"] == rec["runq_wait_s"] == 0.0


def test_collector_seconds_and_full_collections_are_counted(tmp_path):
    w = _watch(_proc(tmp_path))
    try:
        gc.collect(0)
        gc.collect(2)
        gc.collect(2)
        rec = w.take()
        assert rec["gc_gen2"] == 2.0
        assert rec["gc_s"] >= rec["gc_max_s"] > 0
        assert w.take()["gc_gen2"] == 0.0
    finally:
        w.stop()
    assert w._on_gc not in gc.callbacks


def test_the_ticker_thread_runs_on_the_injected_clock(tmp_path):
    """The thread itself: three sleeps on a fake clock, the second one
    400 ms long."""
    now = [0]
    naps = iter([20, 420, 20])
    go, done = threading.Event(), threading.Event()
    pauses = []

    def sleep(seconds):
        assert seconds == hostwatch.TICK_S
        go.wait(5)
        try:
            now[0] += next(naps) * MS
        except StopIteration:
            w.stop()
            done.set()

    w = hostwatch.HostWatch(
        on_pause=lambda *a: pauses.append(a), proc=_proc(tmp_path),
        clock_ns=lambda: now[0], cpu_ns=lambda: now[0] // 4, sleep=sleep,
    )
    go.set()
    assert done.wait(5)
    rec = w.take()
    assert rec["late_s"] == pytest.approx(0.4)
    # The process used a quarter of a CPU on this clock: 105 ms of the
    # 420 ms between the two wakes, 115 ms since the watch began.
    assert pauses == [(40 * MS, 440 * MS, 105 * MS)]
    assert rec["proc_cpu_s"] == pytest.approx(0.115)


def test_a_late_tick_yields_a_host_pause_event_with_another_threads_spans(
        tmp_path):
    """Through the tracer's own hook: the flight event carries the span
    stack open on the other thread and every thread's innermost frame;
    with tracing on the pause is also a span in the ring, on the shards'
    clock."""
    tracer.configure(role="master", dir=str(tmp_path), enabled=True,
                     force=True)
    inside, release = threading.Event(), threading.Event()

    def work():
        with tracer.span("mfc:actor@0:train_step", cat="compute"):
            with tracer.span("stats_sync", cat="compute"):
                inside.set()
                release.wait(5)

    t = threading.Thread(target=work, name="mfc-thread")
    t.start()
    assert inside.wait(5)
    w = _watch(_proc(tmp_path / "proc"), tracer._on_host_pause)
    try:
        w.tick(1_000 * MS, 1_270 * MS, 3 * MS)
    finally:
        w.stop()
        release.set()
        t.join()
    (ev,) = [e for e in tracer.flight_events() if e["kind"] == "host_pause"]
    assert ev["late_ms"] == 250.0 and ev["cpu_ms"] == 3.0
    assert ev["stacks"] == {
        "mfc-thread": ["mfc:actor@0:train_step", "stats_sync"]
    }
    # The innermost frame lies in the library (the Event's wait); the
    # nearest caller of ours is named after it.
    inner, _, ours = ev["frames"]["mfc-thread"].partition(" < ")
    assert inner.startswith("python3") and inner.endswith(" wait")
    assert "test_hostwatch.py" in ours and ours.endswith(" work")
    # The thread that ticked (here this one) is not asked where it stands.
    assert threading.current_thread().name not in ev["frames"]
    _, events = tracer.read_shard(tracer.flush())
    (span,) = [e for e in events if e["name"] == "host_pause"]
    assert span["ts"] == 1_020_000 and span["dur"] == 250_000
    assert span["args"] == {"late_ms": 250.0}


def test_configure_starts_one_watch_per_process(tmp_path):
    tracer.configure(role="master", dir=str(tmp_path), enabled=False,
                     force=True)
    first = tracer._watch
    tracer.configure(role="worker", dir=str(tmp_path), enabled=False,
                     force=True)
    assert tracer._watch is first is not None
    time.sleep(3 * hostwatch.TICK_S)  # an earlier test's ticker ends
    names = [t.name for t in threading.enumerate()]
    assert names.count("areal-hostwatch") == 1
    assert set(tracer.host_take()) >= {"host/late_s", "host/gc_s"}


@pytest.mark.parametrize(
    "role,own", [("master", False), ("worker", True)],
    ids=["under-the-masters-roof", "process-of-its-own"],
)
def test_a_worker_reports_the_host_only_from_a_process_of_its_own(
        tmp_path, role, own):
    """In the master's process the master's step close reports the one
    host watch they share; a worker in a process of its own puts
    `host/<key>` into its MFC reply (the master prefixes the node) and
    closes its own ledger when the master clears its caches."""
    from areal_tpu.system.worker import ModelWorker

    tracer.configure(role=role, dir=str(tmp_path), enabled=False, force=True)
    record = ModelWorker._own_host_record()
    assert (set(record) >= {"host/late_s", "host/gc_s"}) == own
    if not own:
        assert record == {}
    worker = ModelWorker.__new__(ModelWorker)
    worker.data_cache = {"a": 1, "b": 2}
    with tracer.span("mfc:actor@0:train_step"):
        pass
    assert worker._handle_clear_cache({"keep_ids": ["a"], "step": 7}) == {}
    assert worker.data_cache == {"a": 1}
    closed = tracer.step_ledger()
    assert [c["step"] for c in closed] == ([7] if own else [])

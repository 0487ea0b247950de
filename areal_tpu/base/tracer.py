"""Distributed span tracer: Chrome/Perfetto timelines for every process.

Capability intent (no direct reference counterpart — realhf exposes only
the master's flat per-step perf log, master_worker.py:434-473): make the
*shape* of a step visible.  Each process (master, model workers,
gen_server, reward service) records spans into lock-free per-thread ring
buffers and flushes them to a per-process ``trace_<role>_<rank>.jsonl``
shard; :func:`merge_shards` aligns the shards' monotonic clocks via a
(monotonic, epoch) pair stamped in each shard's meta line and emits a
single Perfetto-loadable ``trace.json`` — one track per process, one
thread lane per tid, counter tracks for sampled gauges.

Design constraints:
- Every span has a duration, with no switch: ``span()`` reads the
  monotonic clock on entry and exit, keeps the per-thread stack of open
  spans and adds ``(n, total_ns, self_ns)`` under its name to the
  process's *step ledger*, whether or not ``AREAL_TRACE`` is set.  The
  ring entry, the shard and the event dict stay behind the switch.  A
  span with tracing off costs 2.5 microseconds on the chip machine's
  host (PERF.md section 6, PR 36; 1.5 on the same host before it read
  a clock): half a millisecond of a step of 200 spans.
- One clock with the device: every span also opens a
  ``jax.profiler.TraceAnnotation("areal:<name>")`` (the master's step a
  ``StepTraceAnnotation``), whether or not ``AREAL_TRACE`` is set.
  ``TraceMe`` checks for a live profiler session in C++ and is inert
  without one, so ANY session — an xprof capture, ``AREAL_DUMP_TRACE``,
  the benchmark's ``--trace 1`` — carries the program's spans on its
  ``/host:CPU`` plane, on the device planes' clock.  ``jax`` is never
  imported from here: a process that has not loaded it has no profiler
  session to write to, and its spans skip the annotation.
- No locks on the hot path: each thread appends to its own
  ``collections.deque(maxlen=...)`` (GIL-atomic); the global registry
  lock is taken once per thread lifetime and at flush.
- Spans yield a MUTABLE args dict so callers can attach values computed
  only after the work ran (the worker fills tokens/TFLOPs/MFU once the
  analytic FLOP count exists).

Gating: ``AREAL_TRACE=1`` enables, ``AREAL_TRACE_DIR`` picks the shard
directory (the master defaults it to ``<fileroot>/logs/<exp>/<trial>/
trace`` and exports it so scheduler-spawned workers inherit the dir).

Usage::

    from areal_tpu.base import tracer
    tracer.configure(role="worker", rank=3)
    with tracer.span("mfc:actor:train_step", cat="compute") as args:
        ...
        args["tflops"] = 1.23
    tracer.counter("kv_pool", live_tokens=512, allocated_tokens=4096)
    tracer.flush()

A ring event records name, start, duration, thread, and what caused it:
``parent`` is the span open on the same thread when it began, and across
the master -> worker hop the worker's ``mfc:*`` / ``param_sync:*`` /
``fetch`` spans carry the ``step`` of the master's ``step`` span that
dispatched them (the pools stamp it into every request).

Categories drive the stall-attribution report (apps/trace_report.py):
``compute`` (device math), ``comms`` (data/param movement and the waits
on it), ``host`` (CPU-side work: data loading, grading).  Uncategorized
spans are timeline-only; uncovered step time is reported as idle.

Two planes ride on top of the span stream:

- **Causal lineage**: :func:`new_trace_id` mints a per-sample id at
  rollout dispatch; :func:`lineage` stamps ``lineage:<stage>`` instants
  (dispatch/first_token/generated/graded/admitted/trained) carrying the
  id through every process the sample touches, so ``trace_report
  --lineage`` can join merged shards into per-sample end-to-end
  timelines.  The dispatch stamp is the *root* (``root=True``);
  :func:`validate_trace` rejects child events whose trace_id never
  appears on a root.
- **Flight recorder**: an always-on bounded ring of recent structured
  events: explicit :func:`flight_event` calls (dispatch decisions,
  breaker transitions, quarantine verdicts, weight pushes), ``lineage``
  stamps, and the two kinds this module writes itself, ``host_pause``
  and ``slow_step`` (below).  Span closures do NOT go into it: 512
  entries were 2.6 steps of spans and evicted what the ring is for; the
  step ledger is the record of what the last steps spent.  It costs a
  deque append until a fault: :func:`flight_dump` writes the ring as
  ``flightrec_<role>_<rank>.json`` next to the trace shards for
  ``trace_report --flight``.

Always on, with no switch (PR 36) — what a step that ran long has to
say for itself:

- **Step ledger**: :func:`close_step` (the master calls it at the end of
  each step, a worker in a process of its own when the master clears
  its caches) moves what every thread's spans added since the last
  close into one record ``{step, wall_s, spans: {name: (n, total_s,
  self_s)}, host}``; the last LEDGER_STEPS records stay in memory
  (:func:`step_ledger`).  Self time is duration minus what child spans
  on the same thread cover.
- **Host watch** (``base/hostwatch.py``, started by :func:`configure`,
  one per process): per step, seconds a 20 ms ticker woke > 100 ms late,
  the collector's seconds, run-queue wait against CPU seconds over all
  threads, involuntary switches, page faults and the host's pressure
  totals.  A late wake writes a ``host_pause`` flight event carrying
  ``late_ms``, the span stack open on every thread and every thread's
  innermost Python frame, a ``host_pause`` span into the ring (tracing
  on) and an ``areal:host_pause`` annotation for a live profiler.
- **Slow step**: a step whose wall lies over the median of the last
  <= 8 by max(0.1 s, 3%) gets a ``slow_step`` flight event naming every
  span whose self seconds grew by > 10 ms over that span's own median,
  with the host record beside it; :func:`close_step` returns the step
  stats ``host/<key>`` and ``time/slow_excess_s``.
- **Set-up and program ledger** (PR 51) -- what the time before the
  first timed step is made of.  :func:`setup_span` times a phase of the
  build (``setup:build`` around a runner's construction, inside it
  ``setup:worker``, ``setup:mesh``, ``setup:weights``, ``setup:engine``,
  ``setup:datasets``, ``setup:master``): host seconds, with no added
  synchronisation, so device work a phase dispatched lands where the
  host next waits.  :func:`program_event` is the callback of the
  process's one ``jax.monitoring`` registration (the worker installs it;
  this module is handed the events and never imports jax): one row a
  compiled or loaded program, ``{fun, trace_s, lower_s, compile_s,
  cache_load_s, hit, written, span}``, joined on the compiling thread.
  The rows since the last close are the step record's ``programs``; the
  FIRST :func:`close_step` of a process also returns ``setup/<key>``
  (:func:`setup_take`), the totals from process start, and never again.
  Under a live profiler each phase writes an ``areal:compile``
  annotation at its end carrying ``dur_ms``, ``phase`` and ``fun``.
- **HBM ledger** (PR 66) -- what the device's peak is made of and which
  span set it.  The worker hands over three readers of the device
  (:func:`hbm_readers`; nothing on a backend without ``memory_stats()``).
  :func:`hbm_mark` reads the counters of every local device at the open
  and the close of every request and phase (``setup:*``, ``mfc:*``,
  ``param_sync:*``, ``fetch``, ``clear_cache``) and of the engines'
  spans that end on a wait (``generate``, ``gen_wait``, ``chunk_wait``,
  ``stats_reduce``, ``stats_sync``), chosen by name in ``_hbm_kind``: no
  wait on the device is added.  A rise of the peak between two marks
  belongs to the innermost span open over it (``between_requests``
  outside any) and, under a live profiler, writes an ``areal:hbm_peak``
  annotation.  The step record gains ``hbm: {in_use, peak, device, rises,
  marks, mark_s}``, the program rows ``code_b``, ``temp_b``, ``arg_b``,
  ``out_b``, ``alias_b`` and ``request``; :func:`close_step` returns
  ``hbm/<key>`` (:func:`hbm_take`), from the FIRST close also the
  account of the peak -- owners, code, temporaries and
  ``hbm/unaccounted_gb``, the remainder -- which :func:`setup_report`
  prints.
"""

import atexit
import collections
import json
import os
import statistics
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from areal_tpu.base import hostwatch

# The tracer's import on its own clock: what `setup/to_import_s` ends at,
# and the process's start itself where /proc cannot be read.
_T_IMPORT_NS = time.monotonic_ns()

# Per-thread ring capacity.  A step emits O(100) events per process;
# 65536 absorbs many steps between flushes before dropping the oldest.
_RING_CAP = 65536

# Flight-recorder ring: process-wide, always on.  Appends are GIL-atomic
# (no lock); 512 recent events is several seconds of fleet activity —
# enough context around a fault instant without unbounded memory.
_FLIGHT_CAP = 512

# Program ledger: rows kept between two closes (a process that never
# closes a step keeps the newest), and a thread's ended compile phases
# not yet under an enclosing one (a gradient program's trace has some
# hundreds of nested jit traces as direct children).
_PROGRAM_CAP = 4096
_PHASE_CAP = 4096

# Step ledger: closed steps kept in memory, and what makes a step slow:
# its wall over the median of the last SLOW_WINDOW closed steps (at least
# SLOW_MIN_HISTORY of them) by max(SLOW_ABS_S, SLOW_REL of that median).
# Dense steps repeat to 0.01-0.1% and a MoE cell drifts 0.8% a step
# downward, so neither is flagged; a 0.13 s pause in a 2.6 s step is.
LEDGER_STEPS = 64
SLOW_WINDOW = 8
SLOW_MIN_HISTORY = 3
SLOW_ABS_S = 0.1
SLOW_REL = 0.03
GROWN_SELF_S = 0.01  # a span "grew": self seconds this far over its median
DUMP_EVERY_STEPS = 50  # flight_dump("slow_step") at most this often

_lock = threading.Lock()
_buffers: List[collections.deque] = []  # every thread's ring, for flush
_flight: collections.deque = collections.deque(maxlen=_FLIGHT_CAP)
_tls = threading.local()
_steps: collections.deque = collections.deque(maxlen=LEDGER_STEPS)
_watch: Optional[hostwatch.HostWatch] = None
_ledger_state: Dict[str, Any] = {"t_close_ns": None, "dump_step": None}

_state: Dict[str, Any] = {
    "enabled": False,
    "configured": False,
    "role": None,
    "rank": 0,
    "dir": None,
    "path": None,
    "file": None,
    "meta_written": False,
}


def _env_enabled() -> bool:
    return os.environ.get("AREAL_TRACE", "0") not in ("", "0")


def enabled() -> bool:
    return _state["enabled"]


def configure(
    role: str,
    rank: int = 0,
    dir: Optional[str] = None,
    enabled: Optional[bool] = None,
    force: bool = False,
) -> bool:
    """Set this process's trace identity and shard location.

    First configure wins (a library re-configuring must not steal the
    process's shard) unless ``force=True`` — tests use force to switch
    shards mid-process.  ``enabled=None`` reads AREAL_TRACE; an explicit
    bool overrides the env (tests, check_trace).  Returns the resulting
    enabled state."""
    with _lock:
        if _state["configured"] and not force:
            return _state["enabled"]
        if enabled is None:
            enabled = _env_enabled()
        if force:
            _close_file_locked()
            _state["meta_written"] = False
        _state["enabled"] = bool(enabled)
        _state["configured"] = True
        _state["role"] = str(role)
        _state["rank"] = int(rank)
        d = dir or os.environ.get("AREAL_TRACE_DIR")
        if d is None and enabled:
            import tempfile

            d = os.path.join(tempfile.gettempdir(), "areal_tpu_trace")
        _state["dir"] = d
        _state["path"] = (
            os.path.join(d, f"trace_{role}_{rank}.jsonl") if d else None
        )
        _start_watch_locked()
        return _state["enabled"]


def default_dir(fileroot: str, experiment: str, trial: str) -> Optional[str]:
    """Resolve (and export) the trial's trace dir: AREAL_TRACE_DIR if the
    operator set one, else ``<fileroot>/logs/<exp>/<trial>/trace``.  The
    master calls this BEFORE workers start so scheduler-spawned processes
    inherit one shared dir via the environment.  No-op when disabled."""
    if not _env_enabled() and not _state["enabled"]:
        return None
    d = os.environ.get("AREAL_TRACE_DIR")
    if not d:
        d = os.path.join(fileroot, "logs", experiment, trial, "trace")
        os.environ["AREAL_TRACE_DIR"] = d
    return d


def shard_path() -> Optional[str]:
    return _state["path"]


# ---------------- hot path ----------------


def _buf() -> collections.deque:
    b = getattr(_tls, "buf", None)
    if b is None:
        b = collections.deque(maxlen=_RING_CAP)
        _tls.buf = b
        with _lock:
            _buffers.append(b)
    return b


_ANNOTATIONS = None  # (TraceAnnotation, StepTraceAnnotation) once jax is loaded


def _annotations():
    """jax.profiler's annotation classes, or None while this process has
    not imported jax (never imported from here: the launcher, the reward
    service and the verifier pool run without it)."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return None
        _ANNOTATIONS = (
            profiler.TraceAnnotation, profiler.StepTraceAnnotation
        )
    return _ANNOTATIONS


def _annotate(name: str, args: Dict):
    """The profiler-side half of a span: inert (a C++ bool check) unless
    a profiler session is live in this process."""
    classes = _annotations()
    return classes and classes[0]("areal:" + name, **args)


class _ThreadState:
    """One thread's open spans, what its closed spans added to the step
    ledger since the last close_step, and the program it is compiling."""

    __slots__ = ("thread", "stack", "ledger", "compiles", "phases", "pending")

    def __init__(self):
        self.thread = threading.current_thread()
        self.stack: list = []
        self.ledger: Dict[str, list] = {}  # name -> [n, total_ns, self_ns]
        # Since the last take_compiles(): programs, backend seconds, cache
        # read seconds, trace seconds, lowering seconds.
        self.compiles = [0.0] * 5
        # Compile phases that ended on this thread, (start_ns, seconds),
        # under no later one: a phase that encloses them takes them out.
        self.phases: collections.deque = collections.deque(maxlen=_PHASE_CAP)
        # Of the program not yet through its backend phase:
        # [trace_s, lower_s, cache read seconds, hit, written].
        self.pending = [0.0, 0.0, 0.0, False, False]


_threads: Dict[int, _ThreadState] = {}  # by thread ident, for the watch


def _thread_state() -> _ThreadState:
    try:
        return _tls.ts
    except AttributeError:
        ts = _tls.ts = _ThreadState()
        with _lock:
            _threads[threading.get_ident()] = ts
        return ts


_NOT_OPEN = float("inf")


class _Span:
    """Always timed: clock reads, the thread's stack and the step ledger
    with or without AREAL_TRACE; the event dict, the ring and the shard
    only with it."""

    __slots__ = (
        "name", "cat", "args", "t0", "ann", "parent", "child_ns", "self_ns",
        "ts", "hbm", "mark",
    )

    def __init__(self, name: str, cat: Optional[str], args: Dict, ann):
        self.name = name
        self.cat = cat
        self.args = args
        self.ann = ann
        self.child_ns = 0
        self.self_ns = 0
        self.t0 = _NOT_OPEN  # another thread's mark may look before entry
        # HBM ledger: 0 unmarked, else _HBM_WAIT or _HBM_REQUEST; `mark`
        # is the one taken at the close (None where nothing reads).
        self.hbm = _hbm["reader"] is not None and _hbm_kind(name)
        self.mark = None

    def __enter__(self) -> Dict:
        ts = self.ts = _thread_state()
        st = ts.stack
        self.parent = st[-1] if st else None
        st.append(self)
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.monotonic_ns()
        if self.hbm:
            _hbm_open(self)
        return self.args

    def __exit__(self, *exc) -> bool:
        if self.hbm:
            self.mark = hbm_mark("close:" + self.name)
        t1 = time.monotonic_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        ts = self.ts
        st = ts.stack
        if st and st[-1] is self:
            st.pop()
        elif self in st:  # coroutines on one thread close out of order
            st.remove(self)
        dur = t1 - self.t0
        parent = self.parent
        if parent is not None:
            parent.child_ns += dur
        # Coroutines interleaved on one thread overlap without nesting:
        # their "children" can cover more than the span itself.
        self.self_ns = own = max(dur - self.child_ns, 0)
        row = ts.ledger.get(self.name)
        if row is None:
            ts.ledger[self.name] = [1, dur, own]
        else:
            row[0] += 1
            row[1] += dur
            row[2] += own
        if _state["enabled"]:
            ev = {
                "ph": "X",
                "name": self.name,
                "ts": self.t0 // 1000,
                "dur": max(dur // 1000, 1),
                "tid": threading.get_ident(),
                # The caller's own dict, also when still empty: values
                # written after the block (the MFC's token counts) reach
                # the shard.
                "args": self.args,
            }
            if self.cat:
                ev["cat"] = self.cat
            if parent is not None:
                ev["parent"] = parent.name
            _buf().append(ev)
        return False


def span(name: str, cat: Optional[str] = None, **args) -> _Span:
    """A timed span.  After the block, ``self_ns`` on the returned object
    is its duration minus what child spans on the same thread covered."""
    return _Span(name, cat, args, _annotate(name, args))


def step_span(step: int) -> Any:
    """The master's per-step span: ``span("step", step=n)`` whose
    annotation is a ``StepTraceAnnotation``, so xprof's step views see the
    training steps."""
    classes = _annotations()
    ann = classes and classes[1]("areal:step", step_num=int(step))
    return _Span("step", None, {"step": int(step)}, ann)


def counter(name: str, **values) -> None:
    """Sampled gauge: each kwarg becomes one series on the counter track
    (Perfetto ph="C")."""
    if not _state["enabled"]:
        return
    _buf().append(
        {
            "ph": "C",
            "name": name,
            "ts": time.monotonic_ns() // 1000,
            "args": values,
        }
    )


def complete(
    name: str,
    start_ns: int,
    end_ns: Optional[int] = None,
    cat: Optional[str] = None,
    **args,
) -> None:
    """Emit a span with an explicit start (for request lifetimes measured
    across threads, e.g. gen_server enqueue -> retire)."""
    if not _state["enabled"]:
        return
    if end_ns is None:
        end_ns = time.monotonic_ns()
    ev = {
        "ph": "X",
        "name": name,
        "ts": start_ns // 1000,
        "dur": max((end_ns - start_ns) // 1000, 1),
        "tid": threading.get_ident(),
    }
    if cat:
        ev["cat"] = cat
    if args:
        ev["args"] = args
    st = _thread_state().stack
    if st:
        ev["parent"] = st[-1].name
    _buf().append(ev)


# ---------------- causal lineage ----------------


def new_trace_id() -> str:
    """Mint a per-sample lineage id (rollout dispatch is the root)."""
    import uuid

    return "tr-" + uuid.uuid4().hex[:16]


def lineage(stage: str, trace_id: str, root: bool = False, **args) -> None:
    """Stamp one lineage stage for ``trace_id`` in this process.

    Emits a ``lineage:<stage>`` instant into the trace stream (when
    enabled) so ``trace_report --lineage`` can join merged shards into a
    per-sample timeline, AND always records the stamp in the flight ring
    — a fault dump shows the victim's recent per-sample activity even
    with AREAL_TRACE=0.  ``root=True`` marks the minting stage
    (dispatch); every other stamp must share a root's trace_id or
    validate_trace flags it as an orphan."""
    if not trace_id:
        return
    if _state["enabled"]:
        a = {"trace_id": trace_id, "stage": stage}
        if root:
            a["root"] = True
        a.update(args)
        _buf().append(
            {
                "ph": "i",
                "name": f"lineage:{stage}",
                "cat": "lineage",
                "ts": time.monotonic_ns() // 1000,
                "tid": threading.get_ident(),
                "s": "t",
                "args": a,
            }
        )
    fe = {
        "t_us": int(time.time() * 1e6),
        "kind": "lineage",
        "stage": stage,
        "trace_id": trace_id,
    }
    fe.update(args)
    _flight.append(fe)


# ---------------- flight recorder ----------------


def flight_event(kind: str, **fields) -> None:
    """Record one structured event in the always-on flight ring (dispatch
    decisions, breaker transitions, quarantine verdicts, weight pushes).
    Costs one deque append; nothing is written until flight_dump()."""
    fe = {"t_us": int(time.time() * 1e6), "kind": kind}
    fe.update(fields)
    _flight.append(fe)


def flight_events() -> List[Dict[str, Any]]:
    """Snapshot the flight ring (oldest first)."""
    return list(_flight)


def flight_dump(
    reason: str,
    role: Optional[str] = None,
    rank: Optional[int] = None,
    dir: Optional[str] = None,
) -> Optional[str]:
    """Dump the flight ring as ``flightrec_<role>_<rank>.json`` next to
    the trace shards.  Called from fault paths (worker death, quarantine
    escalation, checksum-rejected push, chaos kill).  role/rank default
    to the tracer identity; dir falls back to the configured trace dir
    then AREAL_TRACE_DIR.  Returns the path, or None when no dump
    location is known."""
    d = dir or _state["dir"] or os.environ.get("AREAL_TRACE_DIR")
    if not d:
        return None
    role = role if role is not None else (_state["role"] or "proc")
    rank = rank if rank is not None else _state["rank"]
    path = os.path.join(d, f"flightrec_{role}_{rank}.json")
    doc = {
        "role": str(role),
        "rank": int(rank),
        "pid": os.getpid(),
        "reason": str(reason),
        "t_dump_us": int(time.time() * 1e6),
        "events": list(_flight),
    }
    hbm = _hbm_snapshot()
    if hbm is not None:
        doc["hbm"] = hbm
    try:
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, default=_json_default)
    except OSError:
        return None
    return path


def read_flight_dumps(trace_dir: str) -> List[Dict[str, Any]]:
    """Load every ``flightrec_*.json`` in ``trace_dir`` (unparseable or
    torn dumps are skipped)."""
    import glob

    dumps = []
    for path in sorted(
        glob.glob(os.path.join(trace_dir, "flightrec_*.json"))
    ):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("events"), list):
            doc["path"] = path
            dumps.append(doc)
    return dumps


# ---------------- step ledger, host watch, slow steps ----------------


def _start_watch_locked() -> None:
    global _watch
    if _watch is None:
        _watch = hostwatch.HostWatch(on_pause=_on_host_pause)
        _ledger_state["t_close_ns"] = time.monotonic_ns()


def role() -> Optional[str]:
    """The identity this process was configured with (first one wins):
    a worker whose process answers "master" shares the master's ledger
    and host watch."""
    return _state["role"]


def open_spans() -> Dict[str, List[str]]:
    """The names of the spans open on every thread, outermost first."""
    out = {}
    for ts in list(_threads.values()):
        names = [sp.name for sp in list(ts.stack)]
        if names:
            out[ts.thread.name] = names
    return out


_LIBRARY = os.sep + "lib" + os.sep + "python"  # stdlib and site-packages


def _frame_label(frame) -> str:
    code = frame.f_code
    where = os.sep.join(code.co_filename.split(os.sep)[-2:])
    return f"{where}:{frame.f_lineno} {code.co_name}"


def _thread_frames() -> Dict[str, str]:
    """Every Python thread's innermost frame, as "file:line function";
    where that lies in a library, also (after " < ") the nearest caller
    that does not."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    out = {}
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        label = _frame_label(frame)
        caller = frame
        while caller is not None and _LIBRARY in caller.f_code.co_filename:
            caller = caller.f_back
        if caller is not None and caller is not frame:
            label += " < " + _frame_label(caller)
        out[names.get(ident, str(ident))] = label
    return out


def _on_host_pause(due_ns: int, woke_ns: int, cpu_ns: int = 0) -> None:
    """The watch's ticker woke late: say where every thread stood, and
    how much CPU the process used while it slept (``cpu_ms``: none means
    the process was not scheduled, the usual rate or more that threads of
    ours ran and the ticker could not).  Runs on the ticker's thread, at
    the wake."""
    late_ms = round((woke_ns - due_ns) / 1e6, 3)
    ann = _annotate("host_pause", {"late_ms": late_ms})
    if ann:
        # The pause is [wake - late_ms, wake] on the profiler's clock.
        with ann:
            pass
    flight_event(
        "host_pause", late_ms=late_ms, cpu_ms=round(cpu_ns / 1e6, 3),
        stacks=open_spans(), frames=_thread_frames(),
    )
    complete("host_pause", due_ns, woke_ns, cat="host", late_ms=late_ms)


def host_take() -> Dict[str, float]:
    """The host watch's record since the last take, as ``host/<key>``
    stats (a worker in a process of its own adds them to its reply)."""
    if _watch is None:
        return {}
    return {f"host/{k}": v for k, v in _watch.take().items()}


def close_step(step: int, wall_s: Optional[float] = None) -> Dict[str, float]:
    """Close the step ledger: everything the process's spans added since
    the last close becomes one record (``step_ledger()`` keeps the last
    LEDGER_STEPS), the host watch hands over its record, and a step that
    ran long writes its ``slow_step`` flight event.  ``wall_s`` defaults
    to the seconds since the last close.  Returns the step stats:
    ``host/<key>``, ``time/slow_excess_s`` (0 for a step not flagged),
    ``hbm/<key>`` where a device reader was handed over (:func:`hbm_take`)
    and, from the process's first close alone, ``setup/<key>``
    (:func:`setup_take`)."""
    now = time.monotonic_ns()
    if wall_s is None:
        wall_s = (now - (_ledger_state["t_close_ns"] or now)) / 1e9
    _ledger_state["t_close_ns"] = now
    spans: Dict[str, list] = {}
    with _lock:
        states = list(_threads.items())
    for ident, ts in states:
        closed, ts.ledger = ts.ledger, {}
        for name, (n, total, own) in list(closed.items()):
            row = spans.setdefault(name, [0, 0, 0])
            row[0] += n
            row[1] += total
            row[2] += own
        if not closed and not ts.stack and not ts.thread.is_alive():
            with _lock:
                _threads.pop(ident, None)
    host = _watch.take() if _watch is not None else {}
    hbm = _hbm_close()  # before the rows go: its mark may join them bytes
    with _lock:
        programs = list(_programs)
        _programs.clear()
    record = {
        "step": int(step),
        "t_us": int(time.time() * 1e6),  # the flight events' clock
        "wall_s": float(wall_s),
        "spans": {
            name: (n, total / 1e9, own / 1e9)
            for name, (n, total, own) in spans.items()
        },
        "host": host,
        "programs": programs,
    }
    if hbm is not None:
        record["hbm"] = hbm
    excess = _judge_step(record)
    _steps.append(record)
    stats = {f"host/{k}": v for k, v in host.items()}
    stats["time/slow_excess_s"] = excess
    stats.update(setup_take())
    stats.update(hbm_take())
    return stats


def _judge_step(record: Dict[str, Any]) -> float:
    """Seconds this step's wall lies over its neighbours' median, or 0.0
    where that is not enough to call it slow; a slow step gets its flight
    event and, at most every DUMP_EVERY_STEPS, a dump."""
    history = list(_steps)[-SLOW_WINDOW:]
    if len(history) < SLOW_MIN_HISTORY:
        return 0.0
    median = statistics.median(h["wall_s"] for h in history)
    excess = record["wall_s"] - median
    if excess < max(SLOW_ABS_S, SLOW_REL * median):
        return 0.0
    grown = []
    for name, (n, _, own) in record["spans"].items():
        usual = statistics.median(
            h["spans"].get(name, (0, 0.0, 0.0))[2] for h in history
        )
        if own - usual > GROWN_SELF_S:
            grown.append({
                "name": name, "self_s": round(own, 6),
                "median_self_s": round(usual, 6), "n": n,
            })
    grown.sort(key=lambda g: g["median_self_s"] - g["self_s"])
    flight_event(
        "slow_step", step=record["step"], wall_s=round(record["wall_s"], 6),
        median_s=round(median, 6), excess_s=round(excess, 6),
        host=record["host"], spans=grown,
    )
    last = _ledger_state["dump_step"]
    if last is None or record["step"] - last >= DUMP_EVERY_STEPS:
        if flight_dump("slow_step") is not None:
            _ledger_state["dump_step"] = record["step"]
    return excess


def step_ledger() -> List[Dict[str, Any]]:
    """The last LEDGER_STEPS closed steps, oldest first: ``step``, ``t_us``
    (epoch microseconds at the close), ``wall_s``, ``spans`` (name -> (n,
    total_s, self_s)), ``host``, ``programs`` (the rows of the programs
    compiled or loaded since the close before: :func:`program_event`) and,
    where a device reader was handed over, ``hbm`` (:func:`hbm_readers`)."""
    return list(_steps)


# ---------------- set-up and program ledger ----------------

# jax.monitoring's duration events of a program's way to the device, by
# the phase each closes; all but the cache's carry `fun_name`.  The plain
# event is fired when a compiled program is written to the persistent
# cache: a program the cache should have served and did not.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"

_programs: collections.deque = collections.deque(maxlen=_PROGRAM_CAP)


def _fresh_setup() -> Dict[str, Any]:
    return {
        "t_run_ns": None,  # the first setup span entered
        "phase_ns": {},  # "setup:<phase>" -> nanoseconds, all threads
        "taken": False,
        # From process start, over every thread (program_event).
        "totals": {
            "programs": 0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_load_s": 0.0, "cache_hits": 0, "cache_misses": 0,
            "load_max_s": 0.0,
        },
    }


_setup = _fresh_setup()


class _SetupSpan(_Span):
    """A phase of the build: a span whose seconds are also kept for the
    process's ``setup/*`` stats, whichever close comes first."""

    __slots__ = ()

    def __enter__(self) -> Dict:
        args = super().__enter__()
        if _setup["t_run_ns"] is None:
            _setup["t_run_ns"] = self.t0
        return args

    def __exit__(self, *exc) -> bool:
        dur = time.monotonic_ns() - self.t0
        with _lock:
            phases = _setup["phase_ns"]
            phases[self.name] = phases.get(self.name, 0) + dur
        return super().__exit__(*exc)


def setup_span(phase: str, **args) -> _Span:
    """``span("setup:<phase>", cat="host")`` around a phase of the build.
    What the host spent there and no more: nothing waits for the device,
    so work a phase dispatched is paid where the host next waits.  The
    first one a process enters ends ``setup/to_run_s``."""
    name = "setup:" + phase
    return _SetupSpan(name, "host", args, _annotate(name, args))


def _process_start_ns() -> int:
    """The process's start on the monotonic clock: the kernel's start
    time of ``/proc/self/stat`` (clock ticks since boot) against the boot
    clock, or this module's import where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # Past the command's closing parenthesis the state is field
            # 3 of the file and the start time field 22.
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_s = (
            time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return _T_IMPORT_NS
    return min(time.monotonic_ns() - int(age_s * 1e9), _T_IMPORT_NS)


def program_event(
    event: str, duration: Optional[float] = None, **kw
) -> None:
    """The callback of the process's one ``jax.monitoring`` registration
    (``system/worker.py`` installs it, for duration events and for plain
    ones).  It runs on the thread that compiles, at the END of a phase:
    trace, lowering, then the backend phase with the persistent cache's
    read inside it.  A phase's seconds are its own: what phases that
    ended inside it took (a nested jit's trace, an eager operation
    compiled while tracing) is taken out.  The backend phase closes the
    program's row."""
    ts = _thread_state()
    phase = _PHASES.get(event)
    if phase is None:
        if event == _CACHE_WRITE_EVENT:
            ts.pending[4] = True
        return
    now = time.monotonic_ns()
    start = now - int(duration * 1e9)
    fun = str(kw.get("fun_name", ""))
    pending, counts = ts.pending, ts.compiles
    if phase == "load":  # inside the backend phase, which fires next
        pending[2] += duration
        pending[3] = True
        counts[2] += duration
    else:
        own = duration
        ended = ts.phases
        while ended and ended[-1][0] >= start:
            own -= ended.pop()[1]
        ended.append((start, duration))
        own = max(own, 0.0)
        if phase == "trace":
            pending[0] += own
            counts[3] += own
        elif phase == "lower":
            pending[1] += own
            counts[4] += own
        else:
            counts[0] += 1
            counts[1] += duration
            _close_program(ts, fun, duration)
    # The listener cannot open an annotation around a phase that has
    # ended: like `host_pause`, this one marks [end - dur_ms, end] on a
    # live profiler's clock.
    ann = _annotate(
        "compile",
        {"dur_ms": round(duration * 1e3, 3), "phase": phase, "fun": fun},
    )
    if ann:
        with ann:
            pass
    complete(
        "compile" if phase in ("compile", "load") else "compile:" + phase,
        start, now, cat="host", event=event.rsplit("/", 1)[-1], fun=fun,
        phase=phase,
    )


def _close_program(ts: _ThreadState, fun: str, backend_s: float) -> None:
    trace_s, lower_s, load_s, hit, written = ts.pending
    ts.pending = [0.0, 0.0, 0.0, False, False]
    row = {
        "fun": fun,
        "trace_s": trace_s,
        "lower_s": lower_s,
        # The backend phase less the cache's read: the compilation (and
        # the write) of a program the cache did not serve, the key's
        # hash of one it did.
        "compile_s": max(backend_s - load_s, 0.0),
        "cache_load_s": load_s,
        "hit": hit,
        "written": written,
        "span": ts.stack[-1].name if ts.stack else "",
        # The request or phase that ran it, and when the row closed on
        # the spans' clock (the HBM ledger's joins).
        "request": _request_of(ts.stack[-1] if ts.stack else None),
        "t_ns": time.monotonic_ns(),
    }
    with _lock:
        _programs.append(row)
        totals = _setup["totals"]
        totals["programs"] += 1
        totals["trace_s"] += trace_s
        totals["lower_s"] += lower_s
        if hit:
            totals["cache_hits"] += 1
            totals["cache_load_s"] += backend_s
            totals["load_max_s"] = max(totals["load_max_s"], backend_s)
        else:
            totals["compile_s"] += backend_s
            totals["cache_misses"] += written


def take_compiles() -> Dict[str, float]:
    """This thread's compile counters since the last call, as the
    ``perf/*`` keys an MFC returns: programs compiled or loaded, the
    seconds of their backend phase, the part of those spent reading the
    persistent cache, and the seconds of tracing and of lowering."""
    ts = _thread_state()
    counts, ts.compiles = ts.compiles, [0.0] * 5
    return dict(zip(
        ("perf/compiles", "perf/compile_s", "perf/cache_load_s",
         "perf/trace_s", "perf/lower_s"),
        counts,
    ))


def setup_take() -> Dict[str, float]:
    """``setup/<key>`` stats, ONCE a process (and only of a process that
    built something under :func:`setup_span`): seconds from the process's
    start to this module's import (``to_import_s``) and to the first
    set-up span (``to_run_s``), the seconds of the ``build``, ``weights``
    and ``engine`` phases, and the program ledger's totals from process
    start over every thread -- ``programs``, ``trace_s``, ``lower_s``,
    ``compile_s`` (the backend phase of programs the cache did not
    serve), ``cache_load_s`` (of those it did: key, read and all),
    ``cache_hits``, ``cache_misses`` (compiled AND written: the cache
    should have held them), ``load_max_s``."""
    with _lock:
        if _setup["taken"] or _setup["t_run_ns"] is None:
            return {}
        _setup["taken"] = True
        phase_ns = dict(_setup["phase_ns"])
        totals = dict(_setup["totals"])
    start = _process_start_ns()
    out = {
        "setup/to_import_s": (_T_IMPORT_NS - start) / 1e9,
        "setup/to_run_s": (_setup["t_run_ns"] - start) / 1e9,
        "setup/build_s": phase_ns.get("setup:build", 0) / 1e9,
        "setup/weights_s": phase_ns.get("setup:weights", 0) / 1e9,
        "setup/engines_s": phase_ns.get("setup:engine", 0) / 1e9,
    }
    out.update({f"setup/{k}": float(v) for k, v in totals.items()})
    return out


def program_table(rows: List[Dict[str, Any]], n: int = 10) -> str:
    """The ``n`` rows that took longest, one a line."""
    def seconds(r):
        return r["trace_s"] + r["lower_s"] + r["compile_s"] + r["cache_load_s"]

    return "\n".join(
        f"  {seconds(r):7.2f}s  trace {r['trace_s']:.2f} lower "
        f"{r['lower_s']:.2f} compile {r['compile_s']:.2f} load "
        f"{r['cache_load_s']:.2f}  {'hit ' if r['hit'] else 'miss'}  "
        f"{r['fun']}  [{r['span']}]"
        for r in sorted(rows, key=seconds, reverse=True)[:n]
    )


def setup_report(stats: Dict[str, float], rows: List[Dict[str, Any]]) -> str:
    """What a log says of a set-up: the ``setup/<key>`` of ``stats``, the
    rows that took longest and, where the cache served some programs and
    not others, the longest of those it had not kept; then, where the
    process read a device, what its peak is made of (:func:`hbm_report`)."""
    totals = {k: round(v, 3) for k, v in stats.items() if "setup/" in k}
    text = (
        f"set-up ledger: {totals}\nthe longest of {len(rows)} programs "
        f"(seconds; hit or miss of the cache; the span open on the "
        f"compiling thread):\n" + program_table(rows)
    )
    written = [r for r in rows if r["written"]]
    if written and any(r["hit"] for r in rows):
        text += (
            f"\n{len(written)} programs were compiled and written, so the "
            f"cache had not kept them; the longest:\n"
            + program_table(written)
        )
    account = _hbm["account"]
    if account is not None:  # the process has closed a step on a device
        text += "\n" + hbm_report(account, rows)
    return text


# ---------------- HBM ledger ----------------

# Spans whose OPEN and CLOSE take a mark, by name: a request or a phase
# of the build (what rises inside one and under no marked child is its
# own, as self seconds are), and the engines' spans that end on a wait
# the code makes anyway (the bytes a program took are then the device's).
_HBM_REQUEST_PREFIXES = ("setup:", "mfc:", "param_sync:")
_HBM_REQUEST_NAMES = frozenset(("fetch", "clear_cache"))
_HBM_WAIT_NAMES = frozenset(
    ("generate", "gen_wait", "chunk_wait", "stats_reduce", "stats_sync")
)
_HBM_WAIT, _HBM_REQUEST = 1, 2
BETWEEN_REQUESTS = "between_requests"  # the idle gaps' name for no span

_MARK_CAP = 4096  # marks kept; a serving step takes some hundreds
_RISE_CAP = 256  # rises kept between two closes
_OWNER_ROWS = ("weights", "moments", "cache", "other_live")
_BYTE_COLUMNS = ("code_b", "temp_b", "arg_b", "out_b", "alias_b")

_marks: collections.deque = collections.deque(maxlen=_MARK_CAP)


def _fresh_hbm() -> Dict[str, Any]:
    return {
        # Handed over by the worker (hbm_readers); None: nothing to read.
        "reader": None, "programs": None, "owners": None,
        "last": None,  # the newest mark
        "rises": collections.deque(maxlen=_RISE_CAP),  # since the last close
        # Marks since the last close and what they took (the programs'
        # read, where a mark makes one, is counted by itself).
        "n": 0, "ns": 0,
        "before_step": None,  # the peak at the first request's open
        "programs_seen": 0,  # _setup's program count at the last read
        "programs_read_s": 0.0,
        "code": {},  # device -> code bytes of the programs loaded there
        "account": None,  # made at the process's first close
        "pending": {},  # hbm/<key> stats not yet taken
    }


_hbm = _fresh_hbm()


def _hbm_kind(name: str) -> int:
    if name in _HBM_WAIT_NAMES:
        return _HBM_WAIT
    if name in _HBM_REQUEST_NAMES or name.startswith(_HBM_REQUEST_PREFIXES):
        return _HBM_REQUEST
    return 0


def _request_of(sp: Optional[_Span]) -> str:
    """The innermost request or phase from ``sp`` outwards."""
    while sp is not None:
        if _hbm_kind(sp.name) == _HBM_REQUEST:
            return sp.name
        sp = sp.parent
    return BETWEEN_REQUESTS


def hbm_readers(stats, programs=None, owners=None) -> None:
    """Hand the HBM ledger its readers of the device (``system/worker.py``
    does, once its first mesh stands; this module never imports jax).

    ``stats()`` -> ``{device id: device.memory_stats()}`` over every local
    device of the process's meshes (``{}`` before there is one), or None
    on a backend that keeps no such counters -- it is then never asked
    again.  ``programs(expect)`` -> ``(rows, code)``: one row ``{name,
    code_b, temp_b, arg_b, out_b, alias_b}`` an executable loaded since
    the last call, oldest first (``name`` may be None where there are
    ``expect`` of them), and ``{device id: code bytes of everything
    loaded there}``.  ``owners(device id)`` -> ``{owner: bytes}`` of what the
    engines keep on that device between calls (``weights``, ``moments``,
    ``cache``) and ``other_live``, every buffer counted once."""
    _hbm.update(reader=stats, programs=programs, owners=owners)


def _hbm_read(which: str, *args):
    """Ask one of the worker's readers.  One that raises is not asked
    again and says so in the flight ring: the ledger's marks sit inside
    the span machinery, which a reading of the device must never fail."""
    try:
        return _hbm[which](*args)
    except Exception as e:
        _hbm[which] = None
        flight_event("hbm_reader_failed", reader=which, error=repr(e))
        return None


def hbm_mark(label: str, opening: Optional[_Span] = None):
    """Read the device and append ``{label, t_ns, device, peak, in_use,
    limit}`` to the process's marks: ``t_ns`` on the spans' clock,
    ``peak`` the largest ``peak_bytes_in_use`` over the local devices and
    ``device`` the one that holds it, ``in_use`` and ``limit`` of the
    device fullest NOW (``reserved``, ``peak_reserved`` and
    ``largest_alloc`` of the peak's device where the runtime gives them:
    the TPU runtime keeps a program's temporaries in a reserve that
    ``bytes_in_use`` does not count).  One ``memory_stats()`` call a
    local device and no wait on any: ``_Span`` calls this at the open and
    the close of the spans ``_hbm_kind`` names, ``close_step`` at a close.

    A ``peak`` above the mark before is a RISE, owned by the innermost
    span open over the whole interval between the two marks: on this
    thread first (``opening``, the span this mark opens, left out), else
    the newest marked span of another thread, else ``between_requests``.
    It goes to the step record's ``rises`` and, under a live profiler, an
    ``areal:hbm_peak`` annotation marks the interval's end.  Returns the
    mark; None where there is nothing to read."""
    reader = _hbm["reader"]
    if reader is None:
        return None
    t0 = time.monotonic_ns()
    devices = _hbm_read("reader")
    if devices is None:
        _hbm["reader"] = None
        return None
    if not devices:
        return None
    top = max(devices, key=lambda d: devices[d]["peak_bytes_in_use"])
    full = max(devices, key=lambda d: devices[d]["bytes_in_use"])
    now = time.monotonic_ns()
    mark = {
        "label": label, "t_ns": now, "device": top,
        "peak": devices[top]["peak_bytes_in_use"],
        "in_use": devices[full]["bytes_in_use"],
        "limit": devices[full].get("bytes_limit", 0),
    }
    for key, name in (("reserved", "bytes_reserved"),
                      ("peak_reserved", "peak_bytes_reserved"),
                      ("largest_alloc", "largest_alloc_size")):
        if name in devices[top]:
            mark[key] = devices[top][name]
    ts = _thread_state()
    with _lock:
        last, _hbm["last"] = _hbm["last"], mark
        _marks.append(mark)
        rise = None
        if mark["peak"] > (last["peak"] if last is not None else 0):
            since = last["t_ns"] if last is not None else now
            owner = _span_open_since(ts, since, opening)
            rise = {
                "span": owner.name if owner else BETWEEN_REQUESTS,
                "request": _request_of(owner),
                "from": last["peak"] if last is not None else 0,
                "to": mark["peak"], "device": top,
                "in_use_before": last["in_use"] if last is not None else 0,
                "since_ns": since, "t_ns": now,
            }
            _hbm["rises"].append(rise)
        read = _setup["totals"]["programs"] != _hbm["programs_seen"]
        _hbm["n"] += 1
        _hbm["ns"] += time.monotonic_ns() - t0
    if rise is not None:
        grown = {"span": rise["span"], "from": rise["from"], "to": rise["to"]}
        # Like `host_pause`: the interval [end - dur_ms, end] has ended.
        ann = _annotate(
            "hbm_peak", dict(grown, dur_ms=round((now - since) / 1e6, 3))
        )
        if ann:
            with ann:
                pass
        complete("hbm_peak", since, now, cat="host", **grown)
    if read:
        _read_programs()
    return mark


def _span_open_since(
    ts: _ThreadState, since_ns: int, opening: Optional[_Span]
) -> Optional[_Span]:
    """The innermost span open since ``since_ns`` or before: of this
    thread's, else the latest-opened MARKED span of another thread's."""
    for sp in reversed(ts.stack):
        if sp is not opening and sp.t0 <= since_ns:
            return sp
    best = None
    for other in list(_threads.values()):
        if other is ts:
            continue
        for sp in reversed(list(other.stack)):
            if sp.hbm and sp.t0 <= since_ns:
                if best is None or sp.t0 > best.t0:
                    best = sp
                break
    return best


def _hbm_open(sp: _Span) -> None:
    mark = hbm_mark("open:" + sp.name, opening=sp)
    if (
        mark is not None and _hbm["before_step"] is None
        and sp.hbm == _HBM_REQUEST and not sp.name.startswith("setup:")
    ):
        _hbm["before_step"] = mark["peak"]


def _alnum(name: str) -> str:
    return "".join(c for c in name if c.isalnum())


def _read_programs() -> None:
    """Join the executables loaded since the last read to the program
    ledger's rows of the same interval: the rows gain ``code_b``,
    ``temp_b``, ``arg_b``, ``out_b`` and ``alias_b``.  In order of
    loading, which on the chip is the rows' order name for name (PERF.md
    section 6, PR 66); where the two counts differ the reader is asked
    for the modules' names (``jit(f)`` is ``jit_f`` there: 4-28 ms a
    program to read back) and the join is on them.  Called from a mark at
    which the process's program count has moved, so never where nothing
    compiles."""
    reader = _hbm["programs"]
    with _lock:
        n = _setup["totals"]["programs"]
        new = n - _hbm["programs_seen"]
        _hbm["programs_seen"] = n
        rows = list(_programs)[-new:] if new > 0 else []
    if reader is None:
        return
    t0 = time.monotonic()
    loaded, code = _hbm_read("programs", len(rows)) or ([], {})
    if len(loaded) == len(rows):  # as a rule: one executable a row
        for row, exe in zip(rows, loaded):
            row.update({k: exe[k] for k in _BYTE_COLUMNS})
    else:
        left = [(_alnum(exe["name"]), exe) for exe in loaded]
        for row in rows:
            key = _alnum(row["fun"])
            for i, (name, exe) in enumerate(left):
                if name == key:
                    row.update({k: exe[k] for k in _BYTE_COLUMNS})
                    del left[i]
                    break
    with _lock:
        _hbm["code"] = code
        _hbm["programs_read_s"] += time.monotonic() - t0


def _hbm_close() -> Optional[Dict[str, Any]]:
    """The step record's ``hbm``: the closing mark's ``in_use``, ``peak``
    and ``device``, the ``rises`` since the close before (none in a steady
    step), the marks taken and their seconds; ``owners`` (the owners'
    reader: a walk over the live arrays) from the first close and from a
    step whose peak rose, and from the first close the ``account``.  The
    ``hbm/<key>`` stats wait for :func:`hbm_take`."""
    mark = hbm_mark("close_step")
    if mark is None:
        return None
    with _lock:
        rises = list(_hbm["rises"])
        _hbm["rises"].clear()
        n, ns = _hbm["n"], _hbm["ns"]
        _hbm["n"] = _hbm["ns"] = 0
        first = _hbm["account"] is None
    record = {
        "in_use": mark["in_use"], "peak": mark["peak"],
        "device": mark["device"], "rises": rises, "marks": n,
        "mark_s": ns / 1e9,
    }
    stats = {
        "hbm/in_use_gb": mark["in_use"] / 1e9,
        "hbm/peak_gb": mark["peak"] / 1e9,
        "hbm/peak_rise_gb": sum(r["to"] - r["from"] for r in rises) / 1e9,
        "hbm/marks": float(n),
        "hbm/mark_s": ns / 1e9,
    }
    for key in ("reserved", "peak_reserved"):
        if key in mark:
            record[key] = mark[key]
            stats[f"hbm/{key}_gb"] = mark[key] / 1e9
    if first or rises:
        t0 = time.monotonic()
        record["owners"] = _read_owners(mark["device"])
        record["owners_read_s"] = time.monotonic() - t0
    if first:
        record["account"] = _hbm["account"] = _account(
            mark, record["owners"], rises
        )
        stats.update(_account_stats(_hbm["account"]))
        stats["hbm/owners_read_s"] = record["owners_read_s"]
    with _lock:
        _hbm["pending"] = stats
    return record


def _read_owners(device) -> Dict[str, int]:
    owners = dict.fromkeys(_OWNER_ROWS, 0)
    if _hbm["owners"] is not None:
        owners.update(_hbm_read("owners", device) or {})
    return owners


def _account(
    mark: Dict[str, Any], owners: Dict[str, int], rises: List[Dict[str, Any]]
) -> Dict[str, Any]:
    """What the process's peak is made of, as far as the program can say:
    the span that set it (the newest run of one span's rises is "the
    peak's interval"), then ``rows``: the owners' bytes at this close,
    ``code`` (the programs loaded on the peak's device), ``temp`` (the
    largest temporaries among the programs loaded inside the peak's
    interval -- in a first step a program is loaded where it first runs
    -- else among those the peak's request ran, and no more than the peak
    stands over what was in use when the interval began) and
    ``unaccounted``, the
    remainder, whatever its sign; beside them ``released`` (what was in
    use when the peak's interval began and is gone at this close) and the
    runtime's ``reserved`` bytes, which the peak does not hold."""
    with _lock:
        programs = list(_programs)
        code = _hbm["code"].get(mark["device"], 0)
    temps: Dict[str, int] = {}
    for row in programs:
        if "temp_b" in row:
            temps[row["request"]] = max(
                temps.get(row["request"], 0), row["temp_b"]
            )
    run: List[Dict[str, Any]] = []
    for r in reversed(rises):
        if run and (r["span"], r["request"]) != (
            run[0]["span"], run[0]["request"]
        ):
            break
        run.insert(0, r)
    span, request, since, until, before = (
        (run[0]["span"], run[0]["request"], run[0]["since_ns"],
         run[-1]["t_ns"], run[0]["in_use_before"])
        if run else (BETWEEN_REQUESTS, BETWEEN_REQUESTS, 0, mark["t_ns"], 0)
    )
    during = [
        row["temp_b"] for row in programs
        if "temp_b" in row and since <= row["t_ns"] <= until
    ]
    rows = dict(owners)
    rows["code"] = code
    # (no more of them than the peak stands over what was in use when its
    # interval began: temporaries in the runtime's reserve are not in it)
    rows["temp"] = min(
        max(during) if during else temps.get(request, 0),
        max(mark["peak"] - before, 0),
    )
    rows["unaccounted"] = mark["peak"] - sum(rows.values())
    return {
        "peak": mark["peak"], "device": mark["device"],
        "span": span, "request": request,
        "t_s": (until - _process_start_ns()) / 1e9,
        "before_step": _hbm["before_step"] or 0,
        "rows": rows, "temps": temps,
        "released": max(before - mark["in_use"], 0),
        "reserved": mark.get("peak_reserved", mark.get("reserved")),
        "programs_read_s": _hbm["programs_read_s"],
    }


def _account_stats(account: Dict[str, Any]) -> Dict[str, float]:
    """The first close's own ``hbm/<key>``: ``peak_before_step_gb``,
    ``peak_step1_gb``, a ``<row>_gb`` each row of the account,
    ``released_gb``, ``temp_max_gb`` and ``temp_gb/<request>`` (the
    largest temporaries of all programs and of each request's),
    ``programs_read_s`` (and ``owners_read_s``: what the two readers cost
    the set-up)."""
    out = {
        "hbm/released_gb": account["released"] / 1e9,
        "hbm/peak_before_step_gb": account["before_step"] / 1e9,
        "hbm/peak_step1_gb": account["peak"] / 1e9,
        "hbm/temp_max_gb": max(account["temps"].values(), default=0) / 1e9,
        "hbm/programs_read_s": account["programs_read_s"],
    }
    for name, b in account["rows"].items():
        out[f"hbm/{name}_gb"] = b / 1e9
    for request, b in account["temps"].items():
        out[f"hbm/temp_gb/{request}"] = b / 1e9
    return out


def hbm_take() -> Dict[str, float]:
    """``hbm/<key>`` stats of the last close, once: ``in_use_gb``,
    ``peak_gb``, ``peak_rise_gb`` (this step's: a timed step that raises
    the process's peak is a finding), ``marks`` and ``mark_s`` (what the
    ledger cost the step) and, after the process's FIRST close alone,
    :func:`_account_stats`.  ``close_step`` returns them; a worker in a
    process of its own adds them to its next reply.  Empty where there
    is nothing to read (a CPU backend)."""
    with _lock:
        out, _hbm["pending"] = _hbm["pending"], {}
    return out


def hbm_marks() -> List[Dict[str, Any]]:
    """The last marks, oldest first."""
    return list(_marks)


def _hbm_snapshot() -> Optional[Dict[str, Any]]:
    """For a flight dump: the newest marks, the rises not yet closed and
    who owns what on the peak's device now."""
    last = _hbm["last"]
    if last is None:
        return None
    return {
        "marks": list(_marks)[-64:], "rises": list(_hbm["rises"]),
        "owners": _read_owners(last["device"]),
    }


def byte_table(rows: List[Dict[str, Any]], column: str, n: int = 10) -> str:
    """The ``n`` rows with the most bytes in ``column``, one a line."""
    have = [r for r in rows if column in r]
    return "\n".join(
        "  " + " ".join(
            f"{c[:-2]} {r[c] / 1e6:9.1f} MB" for c in _BYTE_COLUMNS
        ) + f"  {r['fun']}  [{r['request']}]"
        for r in sorted(have, key=lambda r: r[column], reverse=True)[:n]
    )


def hbm_report(account: Dict[str, Any], rows: List[Dict[str, Any]]) -> str:
    """What a log says of the process's peak: the account's rows, the
    remainder among them, and the programs with the largest temporaries
    and the most code."""
    gb = 1e9
    text = (
        f"HBM ledger: peak {account['peak'] / gb:.4f} GB on device "
        f"{account['device']}, set inside `{account['span']}` (request "
        f"`{account['request']}`) {account['t_s']:.1f} s after the "
        f"process's start; {account['before_step'] / gb:.4f} GB before the "
        f"first request\n"
        + "\n".join(
            f"  {name:<12s}{b / gb:9.4f} GB"
            for name, b in account["rows"].items()
        )
        + f"\n  (of the remainder {account['released'] / gb:.4f} GB were in "
        f"use when the peak's interval began and are released since"
    )
    if account["reserved"] is not None:
        text += (
            f"; the runtime's reserve, which `peak_bytes_in_use` does not "
            f"hold, stood at {account['reserved'] / gb:.4f} GB at most"
        )
    text += ")"
    joined = [r for r in rows if "temp_b" in r]
    text += (
        f"\n{len(joined)} of {len(rows)} programs have their bytes (read in "
        f"{account['programs_read_s']:.3f} s); the largest temporaries:\n"
        + byte_table(rows, "temp_b")
        + "\nthe most code:\n" + byte_table(rows, "code_b")
    )
    return text


# ---------------- flush / shard IO ----------------


def _json_default(o):
    try:
        return float(o)
    except Exception:
        return str(o)


def _close_file_locked() -> None:
    f = _state["file"]
    if f is not None:
        try:
            f.close()
        except Exception:
            pass
        _state["file"] = None


def flush() -> Optional[str]:
    """Drain every thread's ring into this process's shard file.  Safe to
    call from any thread; returns the shard path (None when disabled or
    unconfigured)."""
    if not _state["enabled"]:
        return None
    with _lock:
        path = _state["path"]
        if path is None:
            return None
        if _state["file"] is None:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            _state["file"] = open(path, "a")
        f = _state["file"]
        if not _state["meta_written"]:
            # Paired clocks let the exporter shift this shard's monotonic
            # timestamps onto the shared epoch timeline.
            f.write(
                json.dumps(
                    {
                        "kind": "meta",
                        "role": _state["role"],
                        "rank": _state["rank"],
                        "pid": os.getpid(),
                        "mono_us": time.monotonic_ns() // 1000,
                        "epoch_us": int(time.time() * 1e6),
                    }
                )
                + "\n"
            )
            _state["meta_written"] = True
        for b in _buffers:
            while True:
                try:
                    ev = b.popleft()
                except IndexError:
                    break
                f.write(json.dumps(ev, default=_json_default) + "\n")
        f.flush()
        return path


def _reset_for_tests() -> None:
    """Disable tracing, stop the host watch and drop all buffered
    events, closed steps and identity (test isolation; not part of the
    public surface)."""
    global _watch
    with _lock:
        if _watch is not None:
            _watch.stop()
            _watch = None
        _close_file_locked()
        _state.update(
            enabled=False,
            configured=False,
            role=None,
            rank=0,
            dir=None,
            path=None,
            meta_written=False,
        )
        for b in _buffers:
            b.clear()
        _flight.clear()
        _steps.clear()
        _ledger_state.update(t_close_ns=None, dump_step=None)
        _programs.clear()
        _setup.update(_fresh_setup())
        _marks.clear()
        _hbm.update(_fresh_hbm())
        for ts in _threads.values():
            ts.stack.clear()
            ts.ledger.clear()
            ts.phases.clear()
            ts.compiles = [0.0] * 5
            ts.pending = [0.0, 0.0, 0.0, False, False]


atexit.register(flush)


# ---------------- exporter ----------------


def read_shard(path: str):
    """-> (meta dict or None, [event dicts])."""
    meta = None
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue  # torn tail line from a killed process
            if row.get("kind") == "meta":
                if meta is None:
                    meta = row
                continue
            events.append(row)
    return meta, events


def merge_shards(
    trace_dir: str, out_path: Optional[str] = None
) -> Dict[str, Any]:
    """Merge every ``trace_*.jsonl`` shard in ``trace_dir`` into one
    Chrome/Perfetto trace object (and write it to ``out_path`` when
    given).  Per shard: timestamps shift from its monotonic clock onto
    the epoch timeline (meta's paired clocks), events get the shard's
    pid, and a process_name metadata event labels the track
    ``<role>_<rank>``."""
    import glob

    shards = sorted(glob.glob(os.path.join(trace_dir, "trace_*.jsonl")))
    events: List[Dict[str, Any]] = []
    synthetic_pid = 1 << 20  # shards missing a meta line (crashed early)
    used_pids: set = set()
    for path in shards:
        meta, evs = read_shard(path)
        if not evs:
            continue
        if meta is not None:
            pid = int(meta["pid"])
            shift = int(meta["epoch_us"]) - int(meta["mono_us"])
            label = f"{meta['role']}_{meta['rank']}"
        else:
            pid = synthetic_pid
            synthetic_pid += 1
            shift = 0
            label = os.path.basename(path)[len("trace_"):-len(".jsonl")]
        # One track per shard: two shards can share an OS pid (a process
        # re-configured into a new role, or pid recycling across hosts).
        if pid in used_pids:
            pid = synthetic_pid
            synthetic_pid += 1
        used_pids.add(pid)
        events.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "ts": 0,
                "args": {"name": label},
            }
        )
        for ev in evs:
            ev = dict(ev)
            ev["pid"] = pid
            ev["ts"] = int(ev.get("ts", 0)) + shift
            ev.setdefault("tid", 0)
            events.append(ev)
    # Normalize onto a zero-based timeline (Perfetto renders epoch-µs
    # offsets fine, but small numbers keep the JSON and UI readable).
    real = [e for e in events if e["ph"] != "M"]
    if real:
        t0 = min(e["ts"] for e in real)
        for e in real:
            e["ts"] -= t0
    trace = {"traceEvents": events, "displayTimeUnit": "ms"}
    if out_path:
        with open(out_path, "w") as f:
            json.dump(trace, f, default=_json_default)
    return trace


def validate_trace(trace: Dict[str, Any]) -> List[str]:
    """Schema check for the merged trace (shared by tests and
    scripts/check_trace.py).  Returns a list of problems; empty = valid."""
    errors: List[str] = []
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    if not any(e.get("ph") == "X" for e in evs):
        errors.append("no complete ('X') span events")
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as e:
        errors.append(f"not JSON-serializable: {e!r}")
    for i, e in enumerate(evs):
        ph = e.get("ph")
        if ph not in ("X", "C", "M", "i"):
            errors.append(f"event {i}: unknown ph {ph!r}")
            continue
        if not isinstance(e.get("name"), str):
            errors.append(f"event {i}: missing name")
        for field in ("ts", "pid", "tid"):
            if not isinstance(e.get(field), int):
                errors.append(f"event {i} ({e.get('name')}): bad {field}")
        if ph == "X" and not (
            isinstance(e.get("dur"), int) and e["dur"] >= 0
        ):
            errors.append(f"event {i} ({e.get('name')}): bad dur")
        if ph == "C" and not isinstance(e.get("args"), dict):
            errors.append(f"event {i} ({e.get('name')}): counter sans args")
        if len(errors) > 20:
            errors.append("... (truncated)")
            break
    if len(errors) <= 20:
        errors.extend(_validate_lineage(evs))
    return errors


def _validate_lineage(evs: List[Dict[str, Any]]) -> List[str]:
    """Lineage frame checks: every ``lineage:*`` event carries string
    trace_id/stage args, and any event stamped with a trace_id (lineage
    instants and request spans alike) must share a trace_id that appears
    on a root (``root=True``) lineage event somewhere in the merged
    trace — an orphan child means a broken propagation path."""
    errors: List[str] = []
    roots = set()
    stamped = []  # (index, event, trace_id)
    for i, e in enumerate(evs):
        args = e.get("args")
        if not isinstance(args, dict):
            continue
        tid = args.get("trace_id")
        name = e.get("name")
        is_lineage = isinstance(name, str) and name.startswith("lineage:")
        if is_lineage:
            if not isinstance(tid, str) or not tid:
                errors.append(f"event {i} ({name}): lineage sans trace_id")
                continue
            if not isinstance(args.get("stage"), str):
                errors.append(f"event {i} ({name}): lineage sans stage")
            if args.get("root"):
                roots.add(tid)
        if isinstance(tid, str) and tid:
            stamped.append((i, name, tid))
    for i, name, tid in stamped:
        if tid not in roots:
            errors.append(
                f"event {i} ({name}): orphan trace_id {tid!r} "
                f"(no root lineage event)"
            )
        if len(errors) > 20:
            errors.append("... (truncated)")
            break
    return errors

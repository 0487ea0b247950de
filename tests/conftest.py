"""Test harness: fake 8-device CPU cluster.

Mirrors the reference's CPU/multi-process testing strategy
(realhf/base/testing.py: LocalMultiProcessTest with gloo) the JAX way — a
single process sees 8 virtual CPU devices via
--xla_force_host_platform_device_count, so every sharding/mesh code path is
exercised without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# The toy programs are compiled to be run once or twice, and no number is
# ever claimed on the CPU: LLVM at -O1, not XLA's default -O2 (side by side
# on four cores each from empty compile caches, PR 71: 7-11% fewer CPU
# seconds for `test_delta_chunk_kernel.py` and `test_granite_hybrid.py`).
# The benchmark's rehearsals drop XLA_FLAGS.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=1"
os.environ["XLA_FLAGS"] = flags.strip()

# Persistent XLA compilation cache: the suite compiles hundreds of tiny
# CPU programs and recompilation dominates wall-clock on small CI hosts;
# worker subprocesses (apps/worker.py) enable the same directory, so they
# reuse the parent's compiles and repeat runs start warm.
from areal_tpu.base import compilation_cache

compilation_cache.enable()

import collections
import contextlib
import faulthandler
import signal
import sys
import tempfile
import textwrap
import threading
import time

import numpy as np
import pytest

# No case may take longer: about twice the slowest legitimate one from an
# empty compile cache (the CPU rehearsal of sala-docrl8-longctx to its
# window, 206 s under six workers).  A case that needs more is made
# shorter, not excused: there is no marker and no table of exceptions.
CASE_CEILING_S = 420.0


@contextlib.contextmanager
def case_ceiling(seconds):
    """Fail what runs inside once it has taken `seconds`, with the stack of
    every thread in the failure's text — a wait that cannot end then costs
    its own case its ceiling and names the frame, where it used to cost
    the run its limit and name nothing.  The alarm is raised in the main
    thread, where pytest (and each xdist worker) runs its cases, at the
    next bytecode or interrupted system call."""

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(
            f"still running after its ceiling of {seconds} s; every "
            f"thread's stack at that moment:\n{stacks}",
            pytrace=False,
        )

    handler = signal.signal(signal.SIGALRM, on_alarm)
    around, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        # Hand back the ceiling this one was nested in, if any.
        signal.setitimer(signal.ITIMER_REAL, around)
        signal.signal(signal.SIGALRM, handler)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second end-to-end trials, excluded from the tier-1 "
        "`-m 'not slow'` run (scripts/check_async.py covers the async e2e)",
    )
    controller = (config.getoption("dist", "no") != "no"
                  and not hasattr(config, "workerinput"))
    if controller and not config.getoption("collectonly"):
        table = RunTable()
        config.pluginmanager.register(table, "run_table")
        signal.signal(signal.SIGTERM, table.on_sigterm)


@pytest.fixture(autouse=True)
def _case_ceiling():
    with case_ceiling(CASE_CEILING_S):
        yield


class RunTable:
    """Where a run's time went and where it stood, from the reports alone,
    on the xdist controller: every file's seconds and cases, the twenty
    longest cases and THE FILES NOT YET FINISHED — on stderr at the end of
    a run and, for a run that `timeout` cuts, from the SIGTERM handler,
    which then dies of the signal as the process did without it.  A cut
    run writes neither its junit file nor its summary: before PR 71 it left
    a row of dots, and what was still out had to be guessed."""

    LINES, WIDTH = 40, 200

    def __init__(self):
        self.collected = collections.Counter()  # file -> cases collected
        self.seconds = collections.Counter()  # file -> seconds so far
        self.cases = collections.Counter()  # case -> set-up + call + teardown
        self.done = collections.Counter()  # file -> cases whose teardown is in
        self.t0 = time.monotonic()

    @staticmethod
    def _file(nodeid):
        return nodeid.split("::", 1)[0]

    def pytest_xdist_node_collection_finished(self, node, ids):
        if not self.collected:  # every worker collects the same cases
            self.collected.update(map(self._file, ids))

    def pytest_runtest_logreport(self, report):
        f = self._file(report.nodeid)
        self.seconds[f] += report.duration
        self.cases[report.nodeid] += report.duration
        if report.when == "teardown":
            self.done[f] += 1

    def lines(self):
        def short(name):
            return name.removeprefix("tests/").replace(".py", "", 1)

        def packed(title, words, room):
            text = textwrap.wrap(
                "  ".join(words) or "none", self.WIDTH,
                break_long_words=False, break_on_hyphens=False)
            if len(text) > room:
                text[room - 1:] = [f"... and {len(text) - room + 1} more lines"]
            return [title] + ["  " + t for t in text]

        out = [
            f"[conftest] run table after {time.monotonic() - self.t0:.0f} s: "
            f"{sum(self.done.values())} of {sum(self.collected.values())} "
            f"cases in, {sum(self.seconds.values()):.0f} case-seconds"]
        open_files = [
            f"{short(f)} {self.done[f]}/{n}"
            for f, n in self.collected.items() if self.done[f] < n]
        out += packed("files not yet finished (cases in / collected):",
                      open_files, 8)
        out += packed(
            "the twenty longest cases (s):",
            [f"{short(c)} {s:.0f}" for c, s in self.cases.most_common(20)], 12)
        out += packed(
            "every file's seconds/cases:",
            [f"{short(f)} {s:.0f}/{self.done[f]}"
             for f, s in self.seconds.most_common()],
            self.LINES - len(out) - 1)
        return out

    def say(self):
        print("\n" + "\n".join(self.lines()), file=sys.stderr, flush=True)

    def pytest_terminal_summary(self):
        self.say()

    def on_sigterm(self, signum, frame):
        self.say()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)


def _children_of(pid):
    """(pid, command line) of every live child of `pid`, from /proc."""
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # "pid (comm) state ppid ...": comm may hold spaces.
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
            if int(ppid) != pid or state == "Z":
                continue
            with open(f"/proc/{entry}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except (OSError, ValueError):
            continue  # gone between the listing and the read
        # multiprocessing's own tracker ends with its parent: it reads
        # a pipe that the parent's exit closes.
        if "multiprocessing.resource_tracker" not in cmd:
            out.append((int(entry), cmd))
    return out


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session):
    """Name on stderr what the last case left behind in this process (a
    worker's or the controller's), and kill the children among it.

    A child that outlives its worker holds the run's output pipe, and the
    driver's `| tee` then stays open after pytest has exited 0: the run is
    booked at its time limit with every case passed (PR 61's run; a forked
    grading process did it in PR 62's reproduction).  Nothing has a use
    for a case's child once the session is over, so it is killed here, by
    name, where the log shows it.  A thread that is not a daemon keeps the
    interpreter from exiting and cannot be killed: it is named.  The
    process itself is left to end as pytest ends it: the junit file and
    the exit code are still to come."""
    from areal_tpu.interfaces import math_sympy

    math_sympy._kill_executor()  # its own exit hook would: not a leftover
    me = os.getpid()

    def leftovers():
        return _children_of(me), [
            t for t in threading.enumerate()
            if t is not threading.main_thread() and not t.daemon
        ]

    # What was killed or told to stop a moment ago takes that moment to go
    # (the grading pool's worker and its manager thread, just above).
    deadline = time.monotonic() + 2.0
    children, threads = leftovers()
    while (children or threads) and time.monotonic() < deadline:
        time.sleep(0.05)
        children, threads = leftovers()
    left = []
    for pid, cmd in children:
        left.append(f"child {pid} (killed): {cmd[:200]}")
        with contextlib.suppress(ProcessLookupError):
            os.kill(pid, signal.SIGKILL)
    left += [f"thread {t.name!r}" for t in threads]
    if left:
        print(
            f"\n[conftest] pid {me} ends its session with "
            + "; ".join(left),
            file=sys.stderr,
            flush=True,
        )


@pytest.fixture(autouse=True)
def _fresh_name_resolve():
    from areal_tpu.base import name_resolve

    name_resolve.set_default(name_resolve.MemoryNameResolveRepository())
    yield
    name_resolve.reset()


@pytest.fixture(scope="session")
def v5e_chips():
    """The devices of a described v5e host to compile for (libtpu is
    installed here; no chip is attached).  Built inside the fixture, never
    at import: only a worker that runs such a case loads the TPU's
    library."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture
def rng():
    return np.random.default_rng(42)

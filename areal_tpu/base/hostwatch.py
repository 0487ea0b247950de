"""The program's own host watch: what the host did to a step.

One per process, started by ``tracer.configure``; standard library only.
``take()`` returns what happened since the last ``take()`` (the master
calls it through ``tracer.close_step`` once a step), as a flat dict of
floats whose keys are simply absent where this host has no such file:

- ``late_s``, ``late_max_s``: a daemon thread that sleeps TICK at a time
  woke more than LATE past its due time: the process was not scheduled,
  or another thread kept the interpreter lock.  The same rule as the
  benchmark's own ``HostWatch`` (``benchmark/run.py``), so the two can be
  held against each other.  Each late wake is also handed to
  ``on_pause(due_ns, woke_ns, cpu_ns)`` at once (the tracer writes the
  ``host_pause`` flight event there, with every thread's open spans);
  ``cpu_ns`` is the CPU time the whole process used between the two
  wakes: about none means nothing of ours ran (the process was not
  scheduled), as much as usual or more means threads of ours ran while
  the ticker could not (the interpreter lock).
- ``gc_s``, ``gc_max_s``, ``gc_gen2``: seconds inside the cyclic
  collector (``gc.callbacks``) and how many of its runs were full ones.
- ``runq_wait_s``, ``cpu_s``: thread-seconds runnable but not running,
  and on a CPU, summed over ``/proc/self/task/*/schedstat``.  A pause
  with ``runq_wait_s`` beside it is the host not scheduling us; one with
  ``cpu_s`` and no wait is a thread of ours holding the lock.
- ``invol_switches``, ``major_faults``, ``minor_faults``
  (``resource.getrusage``) and ``psi_cpu_s``, ``psi_mem_s``, ``psi_io_s``
  (the ``some total=`` microseconds of ``/proc/pressure/*``): the host's
  neighbours.
- ``proc_cpu_s``: CPU seconds of the whole process (``process_time``),
  the yardstick for a pause's ``cpu_ms`` where schedstat is absent.
- ``read_s``: what this very reading cost.

The counters are differences between two ``take()`` calls; the reads
happen there, once a step, never on a span's path.
"""

import gc
import os
import threading
import time
from typing import Callable, Dict, Optional

try:
    import resource
except ImportError:  # not a POSIX host: no rusage keys
    resource = None

TICK_S = 0.02  # the ticker's sleep
LATE_S = 0.1  # a wake this much past due is a pause

_PSI = (("psi_cpu_s", "cpu"), ("psi_mem_s", "memory"), ("psi_io_s", "io"))


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


class HostWatch:
    def __init__(
        self,
        on_pause: Optional[Callable[[int, int, int], None]] = None,
        proc: str = "/proc",
        clock_ns: Callable[[], int] = time.monotonic_ns,
        cpu_ns: Callable[[], int] = time.process_time_ns,
        sleep: Callable[[float], None] = time.sleep,
        start: bool = True,
    ):
        self._on_pause = on_pause
        self._clock_ns = clock_ns
        self._cpu_ns = cpu_ns
        self._sleep = sleep
        self._stopped = False
        self._late_ns = self._late_max_ns = 0
        self._gc_ns = self._gc_max_ns = self._gc_gen2 = 0
        self._gc_t0: Optional[int] = None
        # Which of the kernel's files this host has is asked once: on a
        # sandboxed kernel a failed open costs as much as a read, and a
        # process of a few hundred threads paid 6-7 ms a step for
        # schedstat files that were not there (chip runs, PR 36).
        self._tasks = os.path.join(proc, "self", "task")
        try:
            probe = os.listdir(self._tasks)[:1]
        except OSError:
            probe = []
        if not probe or _read(
            os.path.join(self._tasks, probe[0], "schedstat")
        ) is None:
            self._tasks = None
        self._psi = tuple(
            (key, path) for key, path in (
                (key, os.path.join(proc, "pressure", name))
                for key, name in _PSI
            ) if _read(path) is not None
        )
        self._last = self._counters()
        gc.callbacks.append(self._on_gc)
        if start:
            threading.Thread(
                target=self._run, name="areal-hostwatch", daemon=True
            ).start()

    def stop(self) -> None:
        self._stopped = True
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    # -- the ticker ---------------------------------------------------------
    def _run(self) -> None:
        last, cpu_last = self._clock_ns(), self._cpu_ns()
        while not self._stopped:
            self._sleep(TICK_S)
            now, cpu = self._clock_ns(), self._cpu_ns()
            self.tick(last, now, cpu - cpu_last)
            last, cpu_last = now, cpu

    def tick(self, last_ns: int, now_ns: int, cpu_ns: int = 0) -> None:
        """One wake of the ticker: asleep since ``last_ns``, awake at
        ``now_ns``, the process having used ``cpu_ns`` of CPU between."""
        due_ns = last_ns + int(TICK_S * 1e9)
        late_ns = now_ns - due_ns
        if late_ns <= LATE_S * 1e9:
            return
        self._late_ns += late_ns
        self._late_max_ns = max(self._late_max_ns, late_ns)
        if self._on_pause is not None:
            try:
                self._on_pause(due_ns, now_ns, cpu_ns)
            except Exception:  # the watch must never take the process down
                pass

    # -- the collector ------------------------------------------------------
    def _on_gc(self, phase: str, info: Dict) -> None:
        now = self._clock_ns()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            took = now - self._gc_t0
            self._gc_t0 = None
            self._gc_ns += took
            self._gc_max_ns = max(self._gc_max_ns, took)
            if info.get("generation") == 2:
                self._gc_gen2 += 1

    # -- the kernel's counters ----------------------------------------------
    def _counters(self) -> Dict[str, float]:
        """Running totals, in the units of the record; absent where the
        host has no such file."""
        out: Dict[str, float] = {"proc_cpu_s": self._cpu_ns() / 1e9}
        if self._tasks is not None:
            try:
                tids = os.listdir(self._tasks)
            except OSError:
                tids = []
            cpu_ns = wait_ns = 0
            for tid in tids:
                text = _read(os.path.join(self._tasks, tid, "schedstat"))
                fields = text.split() if text else ()
                if len(fields) >= 2:
                    cpu_ns += int(fields[0])
                    wait_ns += int(fields[1])
            out["cpu_s"] = cpu_ns / 1e9
            out["runq_wait_s"] = wait_ns / 1e9
        if resource is not None:
            ru = resource.getrusage(resource.RUSAGE_SELF)
            out["invol_switches"] = float(ru.ru_nivcsw)
            out["major_faults"] = float(ru.ru_majflt)
            out["minor_faults"] = float(ru.ru_minflt)
        for key, path in self._psi:
            for line in (_read(path) or "").splitlines():
                if line.startswith("some") and "total=" in line:
                    out[key] = int(line.rsplit("total=", 1)[1]) / 1e6
        return out

    # -- the record -----------------------------------------------------------
    def take(self) -> Dict[str, float]:
        """What happened since the last call."""
        t0 = self._clock_ns()
        now = self._counters()
        out = {
            "late_s": self._late_ns / 1e9,
            "late_max_s": self._late_max_ns / 1e9,
            "gc_s": self._gc_ns / 1e9,
            "gc_max_s": self._gc_max_ns / 1e9,
            "gc_gen2": float(self._gc_gen2),
        }
        self._late_ns = self._late_max_ns = 0
        self._gc_ns = self._gc_max_ns = self._gc_gen2 = 0
        for key, total in now.items():
            # A thread that ended took its totals with it: never negative.
            out[key] = max(total - self._last.get(key, total), 0.0)
        self._last = now
        out["read_s"] = (self._clock_ns() - t0) / 1e9
        return out

"""Fault injection for chaos-proving the rollout fleet.

A ``FaultInjector`` holds a parsed fault spec and applies it at named
injection points inside a serving process (the gen server wires it into
its request handling and health route).  Specs are env-gated so a chaos
harness can break a *real* server binary without test-only code paths::

    AREAL_FAULTS="kill@t=5s"            # die 5s after arming
    AREAL_FAULTS="hang@p=0.1"           # hang 10% of requests
    AREAL_FAULTS="slow@ms=500"          # add 500ms to every request
    AREAL_FAULTS="slow@ms=50&p=0.5, error@p=0.05"   # combined

Grammar (commas or whitespace separate faults; ``&`` separates params)::

    SPEC  := FAULT ((","|WS) FAULT)*
    FAULT := KIND ["@" PARAM ("&" PARAM)*]
    PARAM := KEY "=" VALUE
    KIND  := kill | hang | slow | error | nan | corrupt_push

Params: ``t`` (arm delay; plain seconds, or with an ``s``/``ms``
suffix), ``p`` (per-call probability, default 1), ``ms`` (added latency
for ``slow``), ``point`` (restrict to one injection point, e.g.
``generate`` or ``health``; default all points), ``skip`` (ignore the
first N matching calls — call-count scoping that, unlike ``t=``, is
deterministic regardless of timing), ``times`` (fire at most N times,
0 = unlimited).  ``hang@point=mfc_train_step&skip=2&times=1`` hangs
exactly the third train MFC, once.

Semantics at a ``fire(point)`` call site:

- ``slow``  — sleep ``ms`` before proceeding (p-gated);
- ``error`` — raise :class:`FaultError` (p-gated), which the server
  surfaces to the client as an ordinary request failure;
- ``hang``  — block (p-gated) until :meth:`FaultInjector.release` or the
  ``hang_max_s`` safety cap, simulating a hung server;
- ``nan`` / ``corrupt_push`` — PASSIVE numerical-corruption kinds for
  the integrity guard plane: ``fire`` never applies them; the host asks
  :meth:`FaultInjector.poison` at a named data boundary (the train
  engine at ``train_grads``, the gen server at ``weight_push``) and
  poisons its own payload when a spec is due —
  ``nan@point=train_grads&skip=2&times=1`` NaN-poisons exactly the
  third accumulated gradient;
- ``kill``  — a POINT-SCOPED kill fires inline via
  :meth:`kill_point` (the host checks it at a named spot — e.g. between
  a checkpoint stage and its flip — and exits itself, simulating a
  crash at exactly that boundary); a point-less kill never fires inline
  — the host polls :meth:`kill_due` (the gen server arms a timer thread
  that calls its own ``close()``), simulating preemption of the whole
  server.

Deterministic by default: the probability stream is seeded from
``AREAL_FAULTS_SEED`` (default 0) so a chaos leg replays identically.
Stdlib-only and jax-free, like the rest of ``base/``.
"""

import dataclasses
import os
import random
import re
import threading
import time
from typing import Callable, List, Optional, Sequence

from areal_tpu.base import logging

logger = logging.getLogger("faults")

KINDS = ("kill", "hang", "slow", "error", "nan", "corrupt_push")
# Kinds `fire` never applies: kills are polled/point-checked by the host;
# poison kinds are fetched via `poison` at data boundaries.
PASSIVE_KINDS = ("kill", "nan", "corrupt_push")
POISON_KINDS = ("nan", "corrupt_push")

ENV_SPEC = "AREAL_FAULTS"
ENV_SEED = "AREAL_FAULTS_SEED"


class FaultError(RuntimeError):
    """Raised at an injection point by an ``error`` fault (and by a
    ``hang`` that hit its safety cap)."""


_DURATION_RE = re.compile(r"^(?P<num>[0-9]*\.?[0-9]+)(?P<unit>ms|s)?$")


def _parse_duration_s(text: str) -> float:
    m = _DURATION_RE.match(text.strip())
    if not m:
        raise ValueError(f"unparseable duration {text!r} (want e.g. 5s, 500ms, 2.5)")
    v = float(m.group("num"))
    return v / 1000.0 if m.group("unit") == "ms" else v


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    kind: str  # kill | hang | slow | error
    arm_after_s: float = 0.0  # t= — spec is inert before this elapses
    prob: float = 1.0  # p= — per-call firing probability
    latency_s: float = 0.0  # ms= — added latency for `slow`
    point: str = ""  # restrict to one injection point ("" = all)
    skip: int = 0  # skip= — ignore the first N matching calls
    times: int = 0  # times= — fire at most N times (0 = unlimited)

    def matches(self, point: str, elapsed_s: float) -> bool:
        if elapsed_s < self.arm_after_s:
            return False
        return not self.point or self.point == point


def parse_faults(text: str) -> List[FaultSpec]:
    """Parse a fault-spec string, validating the FULL grammar eagerly —
    every error names the offending clause, so a typo'd chaos run fails
    loudly at configure time (``from_env``) instead of silently
    injecting nothing or blowing up at injection time in a hot path."""
    specs: List[FaultSpec] = []
    for raw in re.split(r"[,\s]+", text.strip()):
        if not raw:
            continue
        kind, _, params = raw.partition("@")
        if kind not in KINDS:
            raise ValueError(
                f"bad fault clause {raw!r}: unknown kind {kind!r} "
                f"(one of {KINDS})"
            )
        kw = dict(kind=kind)
        for param in params.split("&") if params else ():
            key, sep, val = param.partition("=")
            if not sep:
                raise ValueError(
                    f"bad fault clause {raw!r}: malformed param {param!r} "
                    "(want KEY=VALUE)"
                )
            try:
                if key == "t":
                    kw["arm_after_s"] = _parse_duration_s(val)
                elif key == "p":
                    kw["prob"] = float(val)
                    if not 0.0 <= kw["prob"] <= 1.0:
                        raise ValueError(
                            f"probability {val!r} out of [0, 1]"
                        )
                elif key == "ms":
                    kw["latency_s"] = float(val) / 1000.0
                elif key == "point":
                    kw["point"] = val
                elif key in ("skip", "times"):
                    kw[key] = int(val)
                    if kw[key] < 0:
                        raise ValueError(f"{key} must be >= 0, got {val!r}")
                else:
                    raise ValueError(
                        f"unknown param {key!r} "
                        "(one of t, p, ms, point, skip, times)"
                    )
            except ValueError as e:
                if raw in str(e):
                    raise
                raise ValueError(f"bad fault clause {raw!r}: {e}") from None
        if kind != "slow" and kw.get("latency_s"):
            raise ValueError(
                f"bad fault clause {raw!r}: ms= only applies to slow"
            )
        if kind in POISON_KINDS and not kw.get("point"):
            raise ValueError(
                f"bad fault clause {raw!r}: {kind} needs point= (a data "
                "boundary the host polls via poison(), e.g. "
                "point=train_grads or point=weight_push)"
            )
        specs.append(FaultSpec(**kw))
    if not specs:
        raise ValueError(f"empty fault spec {text!r}")
    return specs


class FaultInjector:
    """Applies a list of :class:`FaultSpec` at named injection points.

    Thread-safe: ``fire`` is called from server request threads; the
    kill clock and the hang release event are shared state.
    """

    def __init__(
        self,
        specs: Sequence[FaultSpec],
        seed: Optional[int] = None,
        hang_max_s: float = 300.0,
        on_fire: Optional[Callable[[str], None]] = None,
    ):
        self.specs = list(specs)
        if seed is None:
            seed = int(os.environ.get(ENV_SEED, "0"))
        self._rng = random.Random(seed)
        self._rng_lock = threading.Lock()
        self.hang_max_s = hang_max_s
        # Observability hook: the host (gen server) counts fired faults
        # per kind into its metrics registry.
        self.on_fire = on_fire
        self._released = threading.Event()
        self._t0 = time.monotonic()
        self.fired = {k: 0 for k in KINDS}
        self._kill_reported = False
        # spec index -> how many calls have matched it (skip/times
        # scoping); guarded by _rng_lock (both sit on the same
        # per-injection-point slow path).
        self._match_counts = {}

    @classmethod
    def parse(cls, text: str, **kw) -> "FaultInjector":
        return cls(parse_faults(text), **kw)

    @classmethod
    def from_env(cls, environ=None, **kw) -> Optional["FaultInjector"]:
        """Injector from ``AREAL_FAULTS``, or None when unset/empty."""
        spec = (environ or os.environ).get(ENV_SPEC, "").strip()
        return cls.parse(spec, **kw) if spec else None

    # ---------------- clocks / gates ----------------

    def elapsed_s(self) -> float:
        return time.monotonic() - self._t0

    def _chance(self, p: float) -> bool:
        if p >= 1.0:
            return True
        with self._rng_lock:
            return self._rng.random() < p

    def _record(self, kind: str) -> None:
        self.fired[kind] += 1
        if self.on_fire is not None:
            self.on_fire(kind)

    def _count_gate(self, idx: int, spec: FaultSpec) -> bool:
        """Advance the spec's matching-call counter and apply skip/times:
        the spec is eligible on call numbers (skip, skip + times]."""
        with self._rng_lock:
            n = self._match_counts[idx] = self._match_counts.get(idx, 0) + 1
        if n <= spec.skip:
            return False
        if spec.times and n > spec.skip + spec.times:
            return False
        return True

    # ---------------- the injection points ----------------

    @property
    def kill_spec(self) -> Optional[FaultSpec]:
        # Point-scoped kills fire inline via kill_point, never from the
        # host's poll/timer path.
        for s in self.specs:
            if s.kind == "kill" and not s.point:
                return s
        return None

    def kill_due(self) -> bool:
        """True once a ``kill`` fault's arm delay has elapsed.  The host
        polls this (or sleeps until ``kill_spec.arm_after_s``) and tears
        itself down — the injector never exits the process itself."""
        s = self.kill_spec
        due = s is not None and self.elapsed_s() >= s.arm_after_s
        if due and not self._kill_reported:
            self._kill_reported = True
            self._record("kill")
        return due

    def fire(self, point: str) -> None:
        """Apply every armed fault matching ``point``.  May sleep
        (``slow``), block (``hang``), or raise :class:`FaultError`
        (``error``); returns normally when nothing fires."""
        elapsed = self.elapsed_s()
        for i, s in enumerate(self.specs):
            if s.kind in PASSIVE_KINDS or not s.matches(point, elapsed):
                continue
            if not self._count_gate(i, s):
                continue
            if not self._chance(s.prob):
                continue
            if s.kind == "slow":
                self._record("slow")
                time.sleep(s.latency_s)
            elif s.kind == "hang":
                self._record("hang")
                logger.warning(f"FAULT hang at point {point!r}")
                if not self._released.wait(timeout=self.hang_max_s):
                    raise FaultError(
                        f"hang fault at {point!r} exceeded the "
                        f"{self.hang_max_s}s safety cap"
                    )
                raise FaultError(f"hang fault at {point!r} released")
            elif s.kind == "error":
                self._record("error")
                raise FaultError(f"injected error at {point!r}")

    def kill_point(self, point: str) -> bool:
        """True when a point-scoped ``kill`` fault matches this call
        (skip/times accounted).  The HOST exits itself on True (e.g.
        ``os._exit``) — the injector only renders the verdict, so a test
        harness can also call this to assert the trigger."""
        elapsed = self.elapsed_s()
        for i, s in enumerate(self.specs):
            if s.kind != "kill" or not s.point:
                continue
            if not s.matches(point, elapsed):
                continue
            if not self._count_gate(i, s):
                continue
            if not self._chance(s.prob):
                continue
            self._record("kill")
            logger.warning(f"FAULT kill at point {point!r}")
            return True
        return False

    def poison(self, point: str) -> Optional[str]:
        """Kind of the first due poison fault (``nan``/``corrupt_push``)
        at this data boundary, or None.  Like :meth:`kill_point`, the
        injector only renders the verdict — the HOST corrupts its own
        payload (NaN-scale the grad sum, perturb the pushed params), so
        chaos runs exercise the real detection path with no test-only
        code in it."""
        elapsed = self.elapsed_s()
        for i, s in enumerate(self.specs):
            if s.kind not in POISON_KINDS:
                continue
            if not s.matches(point, elapsed):
                continue
            if not self._count_gate(i, s):
                continue
            if not self._chance(s.prob):
                continue
            self._record(s.kind)
            logger.warning(f"FAULT {s.kind} at point {point!r}")
            return s.kind
        return None

    def release(self) -> None:
        """Unblock every in-flight ``hang`` (host teardown calls this so
        hung request threads fail fast instead of leaking)."""
        self._released.set()

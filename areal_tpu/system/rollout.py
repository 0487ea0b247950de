"""Rollout controller: the actor plane of asynchronous RL.

Continuously pulls prompts from a data stream and fans them out to
generation servers (reference: AReaL's rollout worker +
`GenerationServer` pairing, realhf/system/rollout_worker.py; the
Podracer "actor plane", arxiv 2104.06272):

- **Queue-depth-aware load balancing**: each dispatch picks the server
  whose enriched ``/health`` reports the least load (collector queue
  depth + live decode slots) plus the controller's own
  not-yet-acknowledged dispatches to it — the cached health signal is
  refreshed at a bounded rate so balancing never becomes a health-poll
  storm, and the fleet is polled *concurrently* with a per-server
  timeout so one hung server cannot stall everyone's refresh.
- **Version stamping**: every trajectory records the weight version it
  STARTED sampling under (``version_start``, the head version) and the
  one it finished under — bounded-staleness admission in the
  ``ReplayBuffer`` keys on the head version.
- **Backpressure**: when the replay buffer cannot accept (at capacity),
  the controller stops pulling prompts instead of overrunning the
  buffer and evicting samples the trainer never saw.
- **Bounded fan-out**: a controller-level semaphore caps in-flight
  dispatches, on top of each client's per-loop ``agenerate`` bound.

Elastic-fleet hardening (the RLAX / Podracer preemptible-pool posture,
PAPERS.md arxiv 2512.06392 / 2104.06272):

- **Dynamic membership**: with a ``discovery`` callable (normally
  :func:`areal_tpu.system.fleet.fleet_discovery` over the
  ``names.gen_servers`` keepalive subtree) the controller diffs the
  announced fleet at every health refresh — joins get a client and
  start taking dispatches within one refresh interval; leaves are
  *drained* (no new dispatches; in-flight work runs to completion)
  and reaped once idle.  Statically-passed clients are never drained
  by discovery.
- **Hardened dispatch**: each ``agenerate`` runs under an optional
  deadline (``dispatch_timeout_s``); a failed or timed-out dispatch is
  re-sent — with exponential backoff — to a *different* server
  (excluding every server observed failing this prompt), up to
  ``max_dispatch_retries`` times before the prompt is counted
  ``failed``.  No prompt is ever silently dropped.
- **Circuit breaking**: each server carries a
  :class:`~areal_tpu.system.fleet.CircuitBreaker`; dispatch failures
  AND failed health polls count toward opening it, the half-open probe
  rides the next health poll, and only closed breakers take regular
  dispatches.

The ``cursor`` (prompts consumed from the stream) is persisted in
``RecoverInfo`` so a recovered trial resumes the stream where it
stopped instead of re-sampling consumed prompts; ``membership_epoch``
rides along so fleet churn is observable across restarts.
"""

import asyncio
import dataclasses
import inspect
import time
from typing import (
    Any,
    Callable,
    Dict,
    FrozenSet,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
)

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.base import logging, metrics, tracer
from areal_tpu.system.fleet import CircuitBreaker
from areal_tpu.system.replay import ReplayBuffer, Trajectory

logger = logging.getLogger("rollout")


@dataclasses.dataclass
class RolloutStat:
    """Reference: AReaL's RolloutStat (submitted/accepted/running)."""

    submitted: int = 0
    completed: int = 0
    accepted: int = 0
    rejected: int = 0
    failed: int = 0
    redispatched: int = 0
    in_flight: int = 0
    backpressure_waits: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ServerState:
    """One fleet member as the controller sees it."""

    sid: str
    client: Any  # LLMAPIClient / ZMQGenClient-compatible
    breaker: CircuitBreaker
    # False for clients passed at construction (never drained by
    # discovery); True for discovery-announced members.
    dynamic: bool = False
    health: Dict = dataclasses.field(default_factory=dict)
    # Explicit flag — NOT a sentinel queue depth — so an unreachable
    # server can never leak bogus numbers into version_lag or autosize.
    healthy: bool = False
    # Dispatches sent but not yet completed — the live correction on
    # top of the (staler) polled queue depth.
    local_load: int = 0
    # Draining: takes no new dispatches; in-flight work completes, then
    # the membership sync reaps the entry.
    draining: bool = False


def _normalize_prompt(item, cursor: int):
    """Accept (qid, prompt_ids) pairs, {"qid", "prompt_ids"} dicts, or
    bare token lists; returns ``(qid, prompt_ids, task)``.

    Auto-assigned qids are replay dedup keys, so they must stay unique
    across everything one trial can feed through the controller.  A
    bare ``prompt{cursor}`` collides the moment two task streams share
    a controller, or a cycled dataset rewinds its cursor — so an item
    carrying task metadata (the mixture scheduler stamps ``task`` /
    ``epoch`` / per-task ``index`` on every draw) gets a namespaced
    ``{task}:e{epoch}:p{index}`` qid instead: unique per task, per
    dataset pass, per sample, and stable across recover fast-forwards.
    Plain single-stream items keep the historical ``prompt{cursor}``."""
    if isinstance(item, dict):
        task = str(item.get("task", "") or "")
        ids = list(map(int, item["prompt_ids"]))
        qid = item.get("qid")
        if qid is not None:
            return str(qid), ids, task
        if task or "epoch" in item:
            epoch = int(item.get("epoch", 0) or 0)
            index = int(item.get("index", cursor))
            return f"{task or 'task'}:e{epoch}:p{index}", ids, task
        return f"prompt{cursor}", ids, task
    if (
        isinstance(item, (tuple, list))
        and len(item) == 2
        and isinstance(item[0], str)
    ):
        return item[0], list(map(int, item[1])), ""
    return f"prompt{cursor}", [int(t) for t in item], ""


class RolloutController:
    """Pumps a prompt stream through an elastic gen-server fleet into a
    ReplayBuffer."""

    def __init__(
        self,
        clients: Sequence[Any] = (),  # static members (never drained)
        replay: ReplayBuffer = None,
        gconfig: GenerationHyperparameters = None,
        seed: Optional[int] = None,
        max_concurrency: int = 0,  # 0 = sum of client capacities
        health_refresh_s: float = 0.5,
        backpressure_poll_s: float = 0.05,
        autosize_inflight: bool = True,
        discovery: Optional[Callable[[], Dict[str, Any]]] = None,
        dispatch_timeout_s: float = 0.0,  # 0 = no per-dispatch deadline
        max_dispatch_retries: int = 2,
        retry_backoff_s: float = 0.05,  # doubles per retry, capped at 2s
        health_poll_timeout_s: float = 2.0,
        breaker_threshold: int = 3,
        breaker_cooldown_s: float = 5.0,
        # Agent-serving episodes: when set, each prompt becomes a
        # multi-turn episode instead of a single generate —
        # ``episode_runner(client, qid, prompt_ids)`` drives the full
        # tool-use loop against that server (system/episode.py's
        # ``make_episode_runner``) and returns an Episode, which lands
        # in replay as ONE trajectory with version-stamped turns.  The
        # runner is synchronous (it blocks on each turn); dispatches run
        # it on a worker thread, so deadline/retry/breaker semantics
        # apply to the whole episode.
        episode_runner: Optional[Callable[[Any, str, List[int]], Any]] = None,
        # Versioned parameter store (system/paramstore.py).  When set,
        # the controller maintains the store's refcounts from what it
        # already observes: each health poll pins the server's reported
        # serving version under ``server:{sid}`` (exclusive — the pin
        # FOLLOWS the server as it upgrades), each dispatch pins the
        # trainer version under ``dispatch:{qid}`` until the prompt
        # terminates, and a fleet reap releases every pin the departed
        # server held.  Net effect: a version is retired only when no
        # live server serves it and no in-flight prompt was dispatched
        # against it — the refcount lifecycle that lets a
        # breaker-open/mid-episode laggard still pull head-1.
        paramstore: Optional[Any] = None,
        # Task-mixture curriculum (data/mixture.py).  When set, run()
        # defaults its prompt source to the mixture stream, the
        # mixture's per-task cursors ride in state_dict()["mixture"]
        # (an old record holding only the scalar cursor is backfilled
        # by replaying the deterministic schedule), and every dispatch
        # is task-stamped through lineage and the trajectory.
        mixture: Optional[Any] = None,
    ):
        if not clients and discovery is None:
            raise ValueError(
                "rollout controller needs at least one client or a "
                "fleet-discovery callable"
            )
        if replay is None or gconfig is None:
            raise ValueError("rollout controller needs replay and gconfig")
        self.replay = replay
        self.gconfig = gconfig
        self.seed = seed
        self.health_refresh_s = health_refresh_s
        self.backpressure_poll_s = backpressure_poll_s
        # When True, each health poll resizes the client's agenerate
        # bound to the server-reported decode capacity; False keeps the
        # client's own max_inflight (e.g. to oversubscribe the collector
        # queue on purpose).
        self.autosize_inflight = autosize_inflight
        self.discovery = discovery
        self.dispatch_timeout_s = dispatch_timeout_s
        self.max_dispatch_retries = max_dispatch_retries
        self.retry_backoff_s = retry_backoff_s
        self.health_poll_timeout_s = health_poll_timeout_s
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        self.episode_runner = episode_runner
        self.paramstore = paramstore
        self.mixture = mixture
        # Lineage: pass trace_id through to the runner only when its
        # signature can take it — external runners predating the causal
        # lineage plane keep working unchanged.
        self._runner_takes_trace = False
        if episode_runner is not None:
            try:
                sig = inspect.signature(episode_runner)
                self._runner_takes_trace = "trace_id" in sig.parameters or any(
                    p.kind == inspect.Parameter.VAR_KEYWORD
                    for p in sig.parameters.values()
                )
            except (TypeError, ValueError):
                pass
        self.stat = RolloutStat()
        # Prompts consumed from the data stream since trial start
        # (persisted via state_dict -> RecoverInfo).
        self.cursor = 0
        # Bumps on every membership change (join/leave/reap) — persisted
        # so fleet churn is observable across recoveries.
        self.membership_epoch = 0
        self._skip_on_run = 0
        self._stop = False
        self._servers: List[ServerState] = []
        self._by_sid: Dict[str, ServerState] = {}
        for i, c in enumerate(clients):
            self._add_server(f"static{i}", c, dynamic=False)
        self._health_ts = 0.0
        self._refresh_lock: Optional[asyncio.Lock] = None
        cap = max_concurrency or sum(
            max(1, int(getattr(c, "max_inflight", 1))) for c in clients
        ) or 16
        self._sem = asyncio.Semaphore(cap)
        self.max_concurrency = cap
        reg = metrics.default_registry()
        self._m_in_flight = reg.gauge(
            "areal_rollout_in_flight", "dispatches awaiting a response"
        )
        self._m_backpressure = reg.counter(
            "areal_rollout_backpressure_total",
            "waits because the replay buffer could not accept",
        )
        self._m_dispatched = reg.counter(
            "areal_rollout_dispatched_total",
            "prompt dispatches, by terminal status",
            ("status",),
        )
        self._m_version_lag = reg.gauge(
            "areal_rollout_version_lag",
            "trainer weight version minus the dispatched server's "
            "serving version, at dispatch time",
        )
        self._m_redispatch = reg.counter(
            "areal_rollout_redispatch_total",
            "prompts re-sent to a different server after a dispatch "
            "failure, by failure reason",
            ("reason",),
        )
        self._m_breaker_open = reg.gauge(
            "areal_rollout_breaker_open",
            "servers whose circuit breaker is currently open",
        )
        self._m_breaker_trans = reg.counter(
            "areal_rollout_breaker_transitions_total",
            "circuit-breaker state transitions, by target state",
            ("state",),
        )
        self._m_servers = reg.gauge(
            "areal_rollout_servers",
            "non-draining fleet members known to the controller",
        )

    # ---------------- fleet membership ----------------

    @property
    def clients(self) -> List[Any]:
        """All known clients (compat shim for pre-elastic callers)."""
        return [s.client for s in self._servers]

    @property
    def servers(self) -> List[ServerState]:
        return list(self._servers)

    def server(self, sid: str) -> Optional[ServerState]:
        return self._by_sid.get(sid)

    def _make_breaker(self) -> CircuitBreaker:
        def on_transition(state: str) -> None:
            self._m_breaker_trans.labels(state).inc()
            self._m_breaker_open.set(
                sum(
                    1
                    for s in self._servers
                    if s.breaker.state == CircuitBreaker.OPEN
                )
            )
            tracer.flight_event("breaker", state=state)

        return CircuitBreaker(
            threshold=self.breaker_threshold,
            cooldown_s=self.breaker_cooldown_s,
            on_transition=on_transition,
        )

    def _add_server(self, sid: str, client: Any, dynamic: bool) -> ServerState:
        st = ServerState(
            sid=sid, client=client, breaker=self._make_breaker(),
            dynamic=dynamic,
        )
        self._servers.append(st)
        self._by_sid[sid] = st
        return st

    def _sync_membership(self, mapping: Dict[str, Any]) -> None:
        """Diff the announced fleet against the known set: add joins,
        drain leaves (dynamic members only), reap drained-and-idle."""
        changed = False
        for sid, target in mapping.items():
            st = self._by_sid.get(sid)
            if st is None:
                if isinstance(target, str):
                    from areal_tpu.system.gen_server import make_gen_client

                    client = make_gen_client(target)
                else:  # tests may announce ready-made client objects
                    client = target
                self._add_server(sid, client, dynamic=True)
                changed = True
                logger.info(f"fleet join: {sid}")
            elif st.draining:
                # Re-announced while draining: welcome back.
                st.draining = False
                changed = True
                logger.info(f"fleet re-join: {sid}")
        for st in self._servers:
            if st.dynamic and not st.draining and st.sid not in mapping:
                st.draining = True
                changed = True
                logger.info(
                    f"fleet leave: {st.sid} draining "
                    f"({st.local_load} in flight)"
                )
        for st in [
            s for s in self._servers if s.draining and s.local_load == 0
        ]:
            self._servers.remove(st)
            del self._by_sid[st.sid]
            changed = True
            logger.info(f"fleet reap: {st.sid}")
            if self.paramstore is not None:
                # A dead/drained server no longer holds its version
                # alive (TTL expiry in the store covers the crash case
                # where no reap is ever observed).
                try:
                    self.paramstore.release_holder(f"server:{st.sid}")
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            close = getattr(st.client, "close", None)
            if st.dynamic and callable(close):
                try:
                    close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
        if changed:
            self.membership_epoch += 1
        self._m_servers.set(
            sum(1 for s in self._servers if not s.draining)
        )

    def drain(self, sid: str) -> None:
        """Stop dispatching to `sid`; in-flight work completes."""
        st = self._by_sid.get(sid)
        if st is not None:
            st.draining = True

    # ---------------- recover ----------------

    def state_dict(self) -> Dict[str, Any]:
        sd = {
            "cursor": self.cursor,
            "stat": self.stat.as_dict(),
            "membership_epoch": self.membership_epoch,
        }
        if self.mixture is not None:
            sd["mixture"] = self.mixture.state_dict()
        return sd

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self.cursor = int(sd.get("cursor", 0))
        self.membership_epoch = int(sd.get("membership_epoch", 0))
        st = sd.get("stat", {})
        for k, v in st.items():
            if hasattr(self.stat, k) and k != "in_flight":
                setattr(self.stat, k, int(v))
        self.stat.in_flight = 0
        if self.mixture is not None:
            ms = sd.get("mixture")
            if ms:
                # Per-task cursors restore exactly; the stream resumes
                # itself, so run() has nothing to skip.
                self.mixture.load_state_dict(ms)
            else:
                # Old-pickle backfill: the record predates the mixture
                # and only holds the scalar draw count — replaying that
                # many draws of the deterministic schedule reconstructs
                # the identical per-task positions.
                self.mixture.fast_forward(self.cursor)
            self._skip_on_run = 0
            return
        # On the next run(), fast-forward the (restarted) prompt stream
        # past everything the pre-restart trial already consumed.
        self._skip_on_run = self.cursor

    def stop(self) -> None:
        self._stop = True

    # ---------------- health / load balancing ----------------

    async def _poll_one(self, st: ServerState) -> None:
        """One server's health poll, breaker-aware.  Open breakers are
        not polled until their cooldown elapses; the poll that follows
        IS the half-open probe."""
        br = st.breaker
        if br.state == CircuitBreaker.OPEN:
            if not br.probe_due():
                st.health = {}
                st.healthy = False
                return
            br.begin_probe()
        try:
            h = await asyncio.wait_for(
                asyncio.to_thread(st.client.health),
                timeout=self.health_poll_timeout_s,
            )
        except Exception as e:  # noqa: BLE001 — deprioritize, don't die
            logger.warning(f"health poll failed for {st.sid}: {e!r}")
            st.health = {}
            st.healthy = False
            # Failed polls count toward the breaker too, so a server
            # that dies between dispatches still trips it open.
            br.record_failure()
            return
        st.health = h
        st.healthy = True
        br.record_success()
        if self.paramstore is not None and h.get("version") is not None:
            # Exclusive pin: the holder tracks the server's CURRENT
            # serving version, releasing its previous pin as it
            # upgrades.  A laggard (breaker-open during a push) keeps
            # head-1 alive in the store until it catches up or is
            # reaped.
            try:
                self.paramstore.pin(
                    int(h["version"]), f"server:{st.sid}", exclusive=True
                )
            except Exception:  # noqa: BLE001 — accounting, not dispatch
                pass
        cap = int(h.get("capacity", 0))
        if cap > 0 and self.autosize_inflight:
            # Size each client's agenerate bound to what its server can
            # actually co-decode.
            st.client.max_inflight = max(cap, 1)

    async def _refresh_health(self) -> None:
        if self.discovery is not None:
            try:
                mapping = await asyncio.to_thread(self.discovery)
            except Exception as e:  # noqa: BLE001 — keep the last view
                logger.warning(f"fleet discovery failed: {e!r}")
            else:
                self._sync_membership(dict(mapping))
        # Concurrent, individually-timed polls: one hung server costs
        # health_poll_timeout_s, not the whole fleet's refresh.
        await asyncio.gather(
            *(self._poll_one(s) for s in self._servers if not s.draining)
        )

    async def _maybe_refresh(self) -> None:
        if self._refresh_lock is None:
            self._refresh_lock = asyncio.Lock()
        async with self._refresh_lock:
            if time.monotonic() - self._health_ts < self.health_refresh_s:
                return
            await self._refresh_health()
            self._health_ts = time.monotonic()

    def _load_score(self, st: ServerState) -> float:
        h = st.health
        return (
            float(h.get("queue_depth", 0))
            + float(h.get("live_slots", 0))
            + st.local_load
        )

    def _eligible(self, exclude: FrozenSet[str]) -> List[ServerState]:
        return [
            s
            for s in self._servers
            if not s.draining
            and s.healthy
            and s.breaker.allow_dispatch()
            and s.sid not in exclude
        ]

    async def _choose_client(
        self, exclude: FrozenSet[str] = frozenset()
    ) -> Optional[ServerState]:
        """Least-loaded dispatchable server, preferring ones not in
        `exclude` (servers observed failing THIS prompt); waits through
        refreshes when nothing is dispatchable.  None only on stop()."""
        while not self._stop:
            await self._maybe_refresh()
            eligible = self._eligible(exclude) or self._eligible(frozenset())
            if eligible:
                return min(eligible, key=self._load_score)
            await asyncio.sleep(min(self.health_refresh_s, 0.1))
        return None

    # ---------------- the pump ----------------

    async def run(
        self,
        prompt_source: Optional[Iterable] = None,
        max_prompts: Optional[int] = None,
    ) -> RolloutStat:
        """Pump prompts until the source is exhausted, `max_prompts` are
        dispatched, or stop() — then await all in-flight dispatches.
        With no explicit source, the configured task-mixture stream is
        pumped (infinite — bound it with ``max_prompts``)."""
        if prompt_source is None:
            prompt_source = self.mixture
        if prompt_source is None:
            raise ValueError(
                "run() needs a prompt source (or a configured mixture)"
            )
        it: Iterator = iter(prompt_source)
        while self._skip_on_run > 0:
            if next(it, None) is None:
                break
            self._skip_on_run -= 1
        tasks: "set[asyncio.Task]" = set()
        dispatched = 0
        while not self._stop and (
            max_prompts is None or dispatched < max_prompts
        ):
            # Backpressure: a full buffer means the trainer is behind —
            # pulling more prompts would only evict unconsumed samples.
            while not self.replay.can_accept() and not self._stop:
                self.stat.backpressure_waits += 1
                self._m_backpressure.inc()
                tracer.counter(
                    "rollout_controller",
                    in_flight=self.stat.in_flight,
                    backpressured=1,
                )
                await asyncio.sleep(self.backpressure_poll_s)
            if self._stop:
                break
            item = next(it, None)
            if item is None:
                break
            qid, prompt_ids, task = _normalize_prompt(item, self.cursor)
            self.cursor += 1
            dispatched += 1
            t = asyncio.create_task(self._dispatch(qid, prompt_ids, task))
            tasks.add(t)
            t.add_done_callback(tasks.discard)
            # Yield so dispatches start promptly even on a fast source.
            await asyncio.sleep(0)
        if tasks:
            await asyncio.gather(*tasks)
        return self.stat

    async def completed_groups(
        self,
        n_groups: Optional[int] = None,
        timeout_per_group: Optional[float] = None,
        poll_s: float = 0.2,
    ):
        """Async iterator over retired GRPO groups, in retirement order.

        The streaming complement of ``replay.get_batch(batch_size)``:
        instead of parking until a whole stamped batch is resident, the
        consumer receives each finished group (one accepted Trajectory =
        one prompt's ``gconfig.n`` responses) as soon as the buffer
        retires it, stamped with ``retired_version`` for per-group
        staleness attribution.  This is the handoff the
        pipeline-overlapped trainer builds on: ref/reward inference for
        group *k* proceeds while groups *k+1..* are still decoding.

        Blocking waits run in a worker thread in short ``poll_s`` slices
        so ``stop()`` is honored promptly (the iterator then ends);
        ``timeout_per_group`` bounds how long any single group may take
        to retire (TimeoutError).  Yields forever when ``n_groups`` is
        None — pair with ``stop()`` or an explicit count.
        """
        yielded = 0
        while not self._stop and (n_groups is None or yielded < n_groups):
            deadline = (
                None
                if timeout_per_group is None
                else time.monotonic() + timeout_per_group
            )
            while True:
                if self._stop:
                    return
                wait = poll_s
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"completed_groups: waited {timeout_per_group}s "
                            "for the next admissible group"
                        )
                    wait = min(wait, remaining)
                try:
                    batch = await asyncio.to_thread(
                        self.replay.get_batch, 1, wait
                    )
                except TimeoutError:
                    continue  # poll slice expired; re-check stop/deadline
                break
            yield batch[0]
            yielded += 1

    async def _generate_with_retries(
        self, qid: str, prompt_ids: List[int], trace_id: str = ""
    ):
        """Dispatch with deadline + bounded redispatch.  Each failure
        excludes the observed-failing server for this prompt, records a
        breaker failure, and backs off exponentially; returns the output
        or None once every attempt is exhausted (or on stop())."""
        exclude: set = set()
        backoff = self.retry_backoff_s
        attempts = 1 + max(0, self.max_dispatch_retries)
        for attempt in range(attempts):
            srv = await self._choose_client(frozenset(exclude))
            if srv is None:  # stopped while waiting for a server
                return None
            srv.local_load += 1
            srv_version = srv.health.get("version")
            if srv_version is not None:
                # Dispatch-time lag between the trainer head and the
                # chosen server's serving weights — a persistently
                # positive gauge means weight sync is falling behind.
                self._m_version_lag.set(self.replay.version - int(srv_version))
            tracer.flight_event(
                "dispatch",
                trace_id=trace_id,
                qid=qid,
                sid=srv.sid,
                attempt=attempt,
            )
            err = reason = None
            try:
                if self.episode_runner is not None:
                    if self._runner_takes_trace:
                        coro = asyncio.to_thread(
                            self.episode_runner,
                            srv.client,
                            qid,
                            prompt_ids,
                            trace_id=trace_id or None,
                        )
                    else:
                        coro = asyncio.to_thread(
                            self.episode_runner, srv.client, qid, prompt_ids
                        )
                else:
                    coro = srv.client.agenerate(
                        APIGenerateInput(
                            qid=qid,
                            prompt_ids=prompt_ids,
                            gconfig=self.gconfig,
                            seed=self.seed,
                            trace_id=trace_id or None,
                        )
                    )
                if self.dispatch_timeout_s > 0:
                    out = await asyncio.wait_for(
                        coro, timeout=self.dispatch_timeout_s
                    )
                else:
                    out = await coro
            except asyncio.TimeoutError:
                err, reason = (
                    f"deadline ({self.dispatch_timeout_s}s) expired",
                    "timeout",
                )
            except Exception as e:  # noqa: BLE001 — one prompt, not the pump
                err, reason = repr(e), "error"
            finally:
                srv.local_load -= 1
            if err is None:
                srv.breaker.record_success()
                return out
            srv.breaker.record_failure()
            exclude.add(srv.sid)
            last = attempt == attempts - 1
            logger.warning(
                f"dispatch {qid} -> {srv.sid} failed ({err}); "
                + ("giving up" if last else "re-dispatching")
            )
            if not last:
                self.stat.redispatched += 1
                self._m_redispatch.labels(reason).inc()
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 2.0)
        return None

    async def _dispatch(
        self, qid: str, prompt_ids: List[int], task: str = ""
    ) -> None:
        # Lineage root: every prompt's causal timeline starts here.  The
        # trace_id rides the request (HTTP header / ZMQ frame) through
        # gen server, grader, replay admission, and train consumption;
        # the task stamp lets trace_report attribute e2e latency per
        # task stream.
        trace_id = tracer.new_trace_id()
        t_dispatch = time.monotonic()
        tracer.lineage(
            "dispatch",
            trace_id,
            root=True,
            qid=qid,
            prompt_len=len(prompt_ids),
            trainer_version=self.replay.version,
            **({"task": task} if task else {}),
        )
        async with self._sem:
            self.stat.submitted += 1
            self.stat.in_flight += 1
            self._m_in_flight.set(self.stat.in_flight)
            tracer.counter(
                "rollout_controller",
                in_flight=self.stat.in_flight,
                backpressured=0,
            )
            # In-flight pin: the version this prompt was dispatched
            # against stays resident in the store until the prompt
            # terminates, so a server finishing a long episode can
            # still be repaired to that version if it lags.
            if self.paramstore is not None:
                try:
                    self.paramstore.pin(
                        self.replay.version,
                        f"dispatch:{qid}",
                        exclusive=False,
                    )
                except Exception:  # noqa: BLE001 — accounting only
                    pass
            try:
                out = await self._generate_with_retries(
                    qid, prompt_ids, trace_id
                )
            finally:
                self.stat.in_flight -= 1
                self._m_in_flight.set(self.stat.in_flight)
                if self.paramstore is not None:
                    try:
                        self.paramstore.release_holder(f"dispatch:{qid}")
                    except Exception:  # noqa: BLE001 — accounting only
                        pass
            if out is None:
                # Exhausted every retry: the prompt is explicitly failed
                # — visible in stat/metrics — never silently dropped.
                self.stat.failed += 1
                self._m_dispatched.labels("failed").inc()
                tracer.lineage("failed", trace_id, qid=qid, error="exhausted")
                return
            self.stat.completed += 1
        if self.episode_runner is not None:
            # One Episode -> ONE trajectory: version-stamped turns ride
            # in traj.data["episode"]; tool tokens carry zero logprobs.
            traj = out.to_trajectory(qid, birth_time=time.time())
        else:
            traj = Trajectory(
                qid=out.qid,
                prompt_ids=list(out.prompt_ids),
                output_ids=out.output_ids,
                output_logprobs=out.output_logprobs,
                no_eos=out.no_eos,
                version_start=out.version_start,
                version_end=out.version,
            )
        traj.trace_id = trace_id
        traj.t_dispatch = t_dispatch
        traj.task = task
        # Lossless backpressure on the put side too: a completed response
        # holds until the trainer drains a slot rather than evicting an
        # unconsumed sample.  Too-stale responses fall through to put()
        # and are rejected — waiting would not freshen them.
        while (
            not self._stop
            and len(self.replay) >= self.replay.capacity
            and self.replay.version - traj.version_start
            <= self.replay.max_head_offpolicyness
        ):
            self.stat.backpressure_waits += 1
            self._m_backpressure.inc()
            await asyncio.sleep(self.backpressure_poll_s)
        if self.replay.put(traj):
            self.stat.accepted += 1
            self._m_dispatched.labels("accepted").inc()
        else:
            self.stat.rejected += 1
            self._m_dispatched.labels("rejected").inc()

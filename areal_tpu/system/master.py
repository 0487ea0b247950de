"""Master worker: drives the DFG, epoch/step accounting, save/eval cadence,
recover checkpoints.

Capability parity: realhf/system/master_worker.py + function_executor.py —
per train step, an asyncio gather runs one coroutine per MFC plus a data
loader; each MFC coroutine blocks on buffer readiness, dispatches the call
to the worker hosting its model, and amends the buffer with the outputs.
"""

import asyncio
import collections
import contextvars
import dataclasses
import inspect
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from areal_tpu.api.config import ModelInterfaceType
from areal_tpu.api.dfg import DFG, MFCDef, OffloadHook, ParamReallocHook
from areal_tpu.base import (
    faults,
    integrity,
    logging,
    metrics,
    recover,
    timeutil,
    tracer,
)
from areal_tpu.base.monitor import StatsLogger
from areal_tpu.base.stats import merge_stats
from areal_tpu.system.buffer import SequenceBuffer
from areal_tpu.system.replay import ReplayBuffer, Trajectory

logger = logging.getLogger("master")

# True within the async-rollout prefetch task (and its children); hooks use
# it to avoid self-awaiting the prefetch.
_IN_PREFETCH: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "areal_in_prefetch", default=False
)


class WorkerDeadError(RuntimeError):
    """A worker missed its MFC deadline with a dead heartbeat: its
    in-flight requests are failed with this so the master can abort the
    step and recover instead of hanging (see ZMQWorkerPool.request)."""

    def __init__(self, worker_id: int, reason: str):
        super().__init__(f"worker {worker_id} dead: {reason}")
        self.worker_id = worker_id
        self.reason = reason


class PoolClosedError(RuntimeError):
    """The pool was closed with requests still in flight; awaiters get
    this instead of hanging on futures nobody will ever resolve."""


def pool_metrics():
    """The worker-liveness counters, shared by every WorkerPool
    implementation (one registration site; the registry is get-or-create
    so repeated calls return the same metrics)."""
    reg = metrics.default_registry()
    return (
        reg.counter(
            "areal_master_worker_dead_total",
            "workers declared dead (deadline expired, heartbeat stale)",
        ),
        reg.counter(
            "areal_master_mfc_timeout_total",
            "MFC requests whose deadline expired (slow or dead)",
        ),
        reg.counter(
            "areal_master_orphan_replies_total",
            "late/unmatched worker replies dropped by the master",
            ("reason",),
        ),
    )


_TIMEOUT_UNSET = object()


class WorkerPool:
    """Transport abstraction: request(worker_id, payload) -> response."""

    # Per-request deadline default; None = wait forever (seed behavior).
    mfc_timeout_s: Optional[float] = None
    # The master's current step: stamped into every request so the
    # worker's spans name the `step` span that dispatched them.
    step: Optional[int] = None

    def stamp(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        if self.step is None or "step" in payload:
            return payload
        return {**payload, "step": self.step}

    async def request(
        self,
        worker_id: int,
        payload: Dict[str, Any],
        timeout: Any = _TIMEOUT_UNSET,
    ) -> Dict:
        raise NotImplementedError

    @property
    def n_workers(self) -> int:
        raise NotImplementedError

    @property
    def dead_workers(self) -> set:
        return set()

    async def wait_workers(self, timeout: float = 300.0):
        """Block until every worker is reachable (no-op in-process)."""


class InProcessPool(WorkerPool):
    """All workers live in this process (single-host trials and the
    reference-style in-process system tests, tests/experiments/utils.py)."""

    def __init__(self, workers, mfc_timeout_s: Optional[float] = None):
        self.workers = list(workers)
        self.mfc_timeout_s = mfc_timeout_s
        self._dead: set = set()
        self._m_worker_dead, self._m_mfc_timeout, _ = pool_metrics()

    async def request(
        self,
        worker_id: int,
        payload: Dict[str, Any],
        timeout: Any = _TIMEOUT_UNSET,
    ) -> Dict:
        if timeout is _TIMEOUT_UNSET:
            timeout = self.mfc_timeout_s
        if worker_id in self._dead:
            raise WorkerDeadError(
                worker_id, "worker previously declared dead"
            )
        coro = asyncio.to_thread(
            self.workers[worker_id].handle_request, self.stamp(payload)
        )
        if timeout is None:
            return await coro
        # No heartbeat lane in-process (a handler thread cannot beat for
        # itself), so deadline expiry alone is the death verdict.  The
        # expired to_thread keeps running in the default executor — the
        # caller (or a chaos harness) must release any injected hang.
        try:
            return await asyncio.wait_for(coro, timeout)
        except asyncio.TimeoutError:
            self._m_mfc_timeout.inc()
            self._m_worker_dead.inc()
            self._dead.add(worker_id)
            raise WorkerDeadError(
                worker_id,
                f"no reply to {payload.get('type')} within {timeout}s",
            ) from None

    def revive(self, worker_id: int):
        """Un-declare a death (the in-process analogue of a relaunch)."""
        self._dead.discard(worker_id)

    @property
    def dead_workers(self) -> set:
        return set(self._dead)

    @property
    def n_workers(self) -> int:
        return len(self.workers)


@dataclasses.dataclass
class ExperimentSaveEvalControl:
    """Reference: cli_args.py:605."""

    total_train_epochs: int = 1
    save_freq_steps: Optional[int] = None
    ckpt_freq_steps: Optional[int] = None
    ckpt_freq_secs: Optional[float] = None
    eval_freq_steps: Optional[int] = None
    benchmark_steps: Optional[int] = None  # stop early after N steps


class MasterWorker:
    def __init__(
        self,
        dfg: DFG,
        pool: WorkerPool,
        model_placement: Dict[str, int],  # model key -> primary worker id
        data_worker_ids: List[int],
        ctrl: ExperimentSaveEvalControl,
        fileroot: str = "/tmp/areal_tpu/trial",
        experiment_name: str = "exp",
        trial_name: str = "trial",
        # model key -> ALL worker ids forming its (possibly multi-host)
        # mesh; group[0] must be the primary.  Models absent here run on
        # their single placement worker.
        model_groups: Optional[Dict[str, List[int]]] = None,
        # model key -> worker ids each holding an INDEPENDENT replica;
        # generate/inference MFCs are token-balance-split across them (the
        # reference's DP dispatch, model_function_call.py:282-472).
        model_replicas: Optional[Dict[str, List[int]]] = None,
        # Dynamic difficulty filtering: after each step, prompts whose group
        # accuracy falls outside [min_accuracy, max_accuracy] are removed
        # from the datasets (reference: model_worker.py:574-639).
        difficulty_filter: Optional[Dict[str, float]] = None,
        # Asynchronous rollout: 1 = generate step t+1's rollouts WHILE step
        # t trains (one-step-stale behavior policy, corrected by the PPO
        # ratio).  The weight-sync hook orders itself after any in-flight
        # generation, so every rollout batch uses a single weight version.
        # Step wall-clock becomes ~max(gen, train) instead of gen + train
        # on disjoint gen/train placements.
        rollout_ahead: int = 0,
        # Asynchronous RL (reference: AReaL's bounded-staleness pipeline,
        # arxiv 2505.24298): when set, K = max_head_offpolicyness + 1
        # rollout batches stay outstanding, each stamped with the trainer
        # version at generation start, and the trainer consumes them
        # through a staleness-bounded ReplayBuffer.  0 degrades to the
        # synchronous ordering (one batch generated and consumed inside
        # each step).  Mutually exclusive with rollout_ahead.
        max_head_offpolicyness: Optional[int] = None,
        # Replay capacity in BATCHES for the async-RL pipeline (clamped
        # below to at least K so admission, not capacity, rules).
        replay_capacity: int = 4,
        # Evict SequenceBuffer entries older than this many steps (async
        # stragglers from long-dead batches); None = keep forever.
        buffer_max_age_steps: Optional[int] = None,
        # Pipeline-overlapped PPO (ROADMAP item 3; OPPO, arxiv
        # 2509.25762): stream the step's batch through the graph in
        # rollout chunks so ref/reward inference and train grad
        # accumulation run on retired chunks WHILE later chunks still
        # decode.  overlap_window bounds in-flight chunks (1 = overlap
        # off: the whole batch flows through the unchanged barrier node
        # path — bit-exact with pipeline_overlap=False);
        # pipeline_chunk_seqs sets prompts per chunk.  Mutually
        # exclusive with rollout_ahead / max_head_offpolicyness (those
        # overlap ACROSS steps; this overlaps WITHIN one on-policy
        # step).
        pipeline_overlap: bool = False,
        overlap_window: int = 2,
        pipeline_chunk_seqs: int = 1,
        # Crash-safe trainer plane: how many worker deaths the run loop
        # absorbs (abort step -> restore recover checkpoint -> resume)
        # before giving up with a structured fault report.
        max_recoveries: int = 3,
        # Optional hook called with the sorted dead worker ids before the
        # master re-waits for hellos; a launcher uses it to respawn the
        # processes (may be sync or async).  Without one the master still
        # re-waits — an externally relaunched worker re-joins by itself.
        worker_relauncher: Optional[Any] = None,
        # Numerical-integrity guard plane: a step whose merged stats carry
        # a `quarantined` flag (engine/interface anomaly sentinels tripped
        # and the weight update was discarded) extends a consecutive
        # streak; after this many in a row the master escalates to a
        # rollback onto the last manifest-valid recover checkpoint,
        # sharing the worker-death recovery budget (max_recoveries).
        # 0 disables escalation (quarantined steps are only counted).
        max_consecutive_quarantines: int = 3,
        # Stamp a per-leaf-norm content checksum on cross-set weight
        # pushes (param_send) so the receiver verifies the payload before
        # swapping; a corrupted push is rejected and retried once.
        weight_push_checksum: bool = True,
    ):
        self.dfg = dfg
        self.pool = pool
        self.placement = model_placement
        self.groups = {k: list(v) for k, v in (model_groups or {}).items()}
        self.replicas = {
            k: list(v) for k, v in (model_replicas or {}).items()
        }
        self.difficulty_filter = difficulty_filter
        self._filtered_ids: List[str] = []
        self.data_worker_ids = data_worker_ids
        self.ctrl = ctrl
        self.fileroot = fileroot
        self.experiment_name = experiment_name
        self.trial_name = trial_name

        self.buffer = SequenceBuffer(
            consumers={n.name: n.input_keys for n in dfg.nodes},
            max_age_steps=buffer_max_age_steps,
        )
        self.step_info = recover.StepInfo()
        self.save_ctl = timeutil.FrequencyControl(
            frequency_steps=ctrl.save_freq_steps
        )
        self.ckpt_ctl = timeutil.FrequencyControl(
            frequency_steps=ctrl.ckpt_freq_steps,
            frequency_seconds=ctrl.ckpt_freq_secs,
        )
        self.eval_ctl = timeutil.FrequencyControl(
            frequency_steps=ctrl.eval_freq_steps
        )
        self.stats_history: List[Dict[str, float]] = []
        self.stats_logger = StatsLogger(fileroot, experiment_name, trial_name)
        reg = metrics.default_registry()
        self._m_steps = reg.counter(
            "areal_master_steps_total", "train steps completed"
        )
        self._m_step_seconds = reg.histogram(
            "areal_master_step_seconds",
            "wall time per train step",
            buckets=(0.1, 0.5, 1, 2, 5, 10, 30, 60, 120, 300),
        )
        self._m_mfc_seconds = reg.gauge(
            "areal_mfc_wall_seconds",
            "last step's wall seconds, per MFC",
            ("mfc",),
        )
        self._m_mfc_mfu = reg.gauge(
            "areal_mfc_mfu_ratio",
            "last step's model FLOP utilization, per MFC",
            ("mfc",),
        )
        self._m_mfc_tflops = reg.gauge(
            "areal_mfc_tflops",
            "last step's achieved TFLOP/s, per MFC",
            ("mfc",),
        )
        # Online cost-model residual: |composed per-MFC walls - measured
        # step| / measured step (analysis/costmodel.compose_step over the
        # DFG levels).  The advisor's offline predictions inherit this
        # composition, so a drifting residual means its rankings are
        # running on stale physics (apps/metrics_report.py
        # `advisor_pred_err` SLO).
        self._m_advisor_err = reg.gauge(
            "areal_master_advisor_pred_err_ratio",
            "relative error of DFG-composed per-MFC walls vs measured "
            "step seconds, last step",
        )
        # Pipeline-overlap attribution: per-stage busy fraction of the
        # streamed step window and the idle gap between a stage's first
        # and last chunk (the bubble the overlap is meant to shrink).
        self._m_pipe_fill = reg.gauge(
            "areal_master_pipeline_fill_ratio",
            "last streamed step: stage busy seconds / step window",
            ("stage",),
        )
        self._m_pipe_bubble = reg.gauge(
            "areal_master_pipeline_bubble_seconds",
            "last streamed step: stage idle seconds inside its active span",
            ("stage",),
        )
        self._m_pipe_chunks = reg.counter(
            "areal_master_pipeline_chunks_total",
            "rollout chunks streamed through the pipelined step path",
        )
        # Crash-safe trainer plane: recoveries absorbed by the run loop,
        # committed checkpoint flips, and the freshness signal the SLO
        # watchdog derives ckpt_age from.
        self._m_recoveries = reg.counter(
            "areal_master_recoveries_total",
            "worker-death recoveries absorbed by the master run loop",
        )
        self._m_ckpt_flips = reg.counter(
            "areal_ckpt_flips_total",
            "recover checkpoints atomically committed (staged dir flipped)",
        )
        self._m_ckpt_last_success = reg.gauge(
            "areal_ckpt_last_success_timestamp_seconds",
            "unix time of the last committed recover checkpoint",
        )
        # Numerical-integrity guard plane: quarantined steps (update
        # discarded, data consumed), the live streak the escalation
        # ladder watches, and the rollbacks it triggered.
        self._m_quarantined = reg.counter(
            "areal_master_quarantined_steps_total",
            "train steps quarantined by the anomaly sentinels",
        )
        self._m_consec_quar = reg.gauge(
            "areal_master_consecutive_quarantines",
            "current run of consecutive quarantined steps",
        )
        self._m_quar_rollbacks = reg.counter(
            "areal_master_quarantine_rollbacks_total",
            "checkpoint rollbacks triggered by quarantine streaks",
        )
        self.max_recoveries = int(max_recoveries)
        self.worker_relauncher = worker_relauncher
        self._recoveries = 0
        self.max_consecutive_quarantines = int(max_consecutive_quarantines)
        self.weight_push_checksum = bool(weight_push_checksum)
        self._consecutive_quarantines = 0
        self._quarantine_ledger: List[Dict[str, Any]] = []
        # Data ids of the most recent _load_data batch — the ledger's
        # best-effort attribution of WHICH samples poisoned a quarantined
        # step (exact on the barrier/streamed paths; the async paths may
        # be one prefetch ahead).
        self._last_data_ids: List[str] = []
        # Master-side chaos points (AREAL_FAULTS): recover_stage /
        # recover_flip kill the master between a checkpoint stage and its
        # flip, proving a torn save never loses recoverability.
        self._faults = faults.FaultInjector.from_env()
        # Span tracing (AREAL_TRACE): resolve the trial's shared shard dir
        # before claiming this process's identity so in-process workers
        # and the master write one coherent shard set.
        tracer.default_dir(fileroot, experiment_name, trial_name)
        tracer.configure(role="master")
        self._steps_per_epoch: Optional[int] = None
        self._restore_pending: Optional[recover.RecoverInfo] = None
        self._train_rpcs = [
            n
            for n in dfg.nodes
            if n.interface_type == ModelInterfaceType.TRAIN_STEP
        ]
        if rollout_ahead not in (0, 1):
            raise ValueError(
                "rollout_ahead supports 0 (synchronous) or 1 (one-step "
                "overlap); deeper pipelines need the staleness-bounded "
                "async-RL mode (max_head_offpolicyness)"
            )
        self.rollout_ahead = rollout_ahead
        self._async_rl = max_head_offpolicyness is not None
        self.max_head_offpolicyness = (
            int(max_head_offpolicyness) if self._async_rl else 0
        )
        if self._async_rl:
            if rollout_ahead:
                raise ValueError(
                    "rollout_ahead and max_head_offpolicyness are mutually "
                    "exclusive (async RL subsumes the one-step overlap)"
                )
            if self.max_head_offpolicyness < 0:
                raise ValueError(
                    "max_head_offpolicyness must be >= 0, got "
                    f"{self.max_head_offpolicyness}"
                )
        self.pipeline_overlap = bool(pipeline_overlap)
        self.overlap_window = int(overlap_window)
        self.pipeline_chunk_seqs = int(pipeline_chunk_seqs)
        if self.pipeline_overlap:
            if self.overlap_window < 1:
                raise ValueError(
                    f"overlap_window must be >= 1, got {overlap_window}"
                )
            if self.pipeline_chunk_seqs < 1:
                raise ValueError(
                    "pipeline_chunk_seqs must be >= 1, got "
                    f"{pipeline_chunk_seqs}"
                )
            if rollout_ahead or self._async_rl:
                raise ValueError(
                    "pipeline_overlap is mutually exclusive with "
                    "rollout_ahead / max_head_offpolicyness: those overlap "
                    "generation ACROSS steps, pipeline overlap streams "
                    "WITHIN one on-policy step"
                )
        self._async_K = self.max_head_offpolicyness + 1
        self._replay_dropped: List[Trajectory] = []
        self.replay: Optional[ReplayBuffer] = (
            ReplayBuffer(
                capacity=max(int(replay_capacity), self._async_K),
                max_head_offpolicyness=self.max_head_offpolicyness,
                on_drop=self._replay_dropped.append,
            )
            if self._async_rl
            else None
        )
        # Completed train steps == the weight version rollout batches are
        # stamped against.
        self._trainer_version = 0
        self._ahead_queue: "collections.deque[asyncio.Task]" = (
            collections.deque()
        )
        self._batches_launched = 0
        self._batch_seq = 0
        # Serialize dataset fetches and generator occupancy across
        # concurrently-outstanding prefetch tasks (the in-process workers
        # have no internal locking; two generates on one engine would
        # race).  Created lazily — asyncio primitives want a running loop.
        self._fetch_lock: Optional[asyncio.Lock] = None
        self._gen_lock: Optional[asyncio.Lock] = None
        # Prefetchable sources: GENERATE nodes fed purely by the dataset.
        self._source_nodes = [
            n
            for n in dfg.nodes
            if n.interface_type == ModelInterfaceType.GENERATE
            and all(
                dfg.data_producers.get(k) is None for k in n.input_keys
            )
        ]
        self._ahead_task: Optional[asyncio.Task] = None
        self._total_steps: Optional[int] = None
        # Cross-worker data plane bookkeeping: which workers hold which
        # (data id, key) — the master's equivalent of the reference's
        # GlobalStorageTracker (realhf/system/redistributor.py:12).
        self._owners: Dict[str, Dict[str, set]] = {}
        # model key -> each group member's (shard_rank, n_shards) for
        # sharded data dispatch (see _shard_infos).
        self._shard_info_cache: Dict[str, List[Tuple[int, int]]] = {}
        self._xfer_id = 0
        # (sid, key, dst) -> Future resolved when the transfer lands; lets a
        # concurrent MFC needing the same copy await it instead of
        # dispatching against data still in flight.
        self._inflight: Dict[tuple, asyncio.Future] = {}
        # Per-step transfer-plane accounting (bytes/seconds per kind),
        # surfaced as transfer/* step stats — the reference's data_manager
        # redistribution timing made visible (blog/AReaL_v0_2.md:52-54).
        self._xfer_acc: Dict[str, float] = {}
        # Per-step `<role>/sync/*` of colocated param syncs, as the worker
        # reports them (bytes placed, seconds in device_put and handler).
        self._sync_acc: Dict[str, float] = {}

    # ---------------- lifecycle ----------------

    async def discover_spec(self) -> Dict[str, int]:
        sizes = await asyncio.gather(
            *[
                self.pool.request(w, {"type": "spec"})
                for w in self.data_worker_ids
            ]
        )
        steps = max(s["steps_per_epoch"] for s in sizes)
        self._steps_per_epoch = max(steps, 1)
        return {
            "dataset_size": sum(s["dataset_size"] for s in sizes),
            "steps_per_epoch": self._steps_per_epoch,
        }

    async def run(self) -> List[Dict[str, float]]:
        """Train until total_train_epochs (or benchmark_steps) complete."""
        await self.discover_spec()
        total_steps = self.ctrl.total_train_epochs * self._steps_per_epoch
        if self.ctrl.benchmark_steps is not None:
            total_steps = min(total_steps, self.ctrl.benchmark_steps)
        self._total_steps = total_steps
        logger.info(
            f"master: {total_steps} steps "
            f"({self.ctrl.total_train_epochs} epochs x {self._steps_per_epoch})"
        )
        if self._restore_pending:
            await self._restore_worker_state()
        # A step's wall for the ledger runs from close to close, so what
        # happens between two steps (checkpoints, a hook of the caller's
        # on the stats logger) lands in the step that follows it.
        t_closed = time.monotonic()
        try:
            while self.step_info.global_step < total_steps:
                t0 = time.monotonic()
                # The "step" span marks the attribution window every other
                # track is bucketed against (apps/trace_report.py).
                try:
                    # Every request of this step carries its number to
                    # the worker's spans (WorkerPool.stamp).
                    self.pool.step = self.step_info.global_step + 1
                    with tracer.step_span(self.pool.step):
                        stats = await self.execute_step()
                except WorkerDeadError as e:
                    await self._recover_from_worker_death(e)
                    continue
                now = time.monotonic()
                dt = now - t0
                stats["time/step_s"] = dt
                # host/<key> and time/slow_excess_s: what the host did to
                # this step, and whether it ran long (base/tracer.py).
                stats.update(
                    tracer.close_step(self.pool.step, now - t_closed)
                )
                t_closed = now
                if "setup/programs" in stats:  # this process's first close
                    logger.info(tracer.setup_report(
                        stats, tracer.step_ledger()[-1]["programs"]
                    ))
                self._export_step_metrics(stats, dt)
                quarantined = self._note_quarantine(stats)
                self.stats_history.append(stats)
                logger.info(
                    f"step {self.step_info.global_step + 1}/{total_steps} "
                    f"({dt:.2f}s): "
                    f"{ {k: round(v, 4) for k, v in stats.items()} }"
                )
                self.stats_logger.log(self.step_info.global_step + 1, stats)
                self.step_info = self.step_info.next(self._steps_per_epoch)
                if not quarantined:
                    await self._post_step()
                elif (
                    self.max_consecutive_quarantines > 0
                    and self._consecutive_quarantines
                    >= self.max_consecutive_quarantines
                ):
                    # A quarantined step never checkpoints (the rollback
                    # target must stay pre-anomaly); a streak at the
                    # threshold escalates to a fleet-wide rollback.
                    await self._quarantine_rollback()
                tracer.flush()
        finally:
            self.stats_logger.close()
            tracer.flush()
        return self.stats_history

    def _export_step_metrics(
        self, stats: Dict[str, float], step_seconds: float
    ) -> None:
        """Mirror the merged per-MFC perf keys (worker `_mfc_perf`, fed
        by monitor.py's analytic FLOP counters) into labeled gauges —
        the per-MFC wall/MFU view the fleet watchdog trends."""
        self._m_steps.inc()
        self._m_step_seconds.observe(step_seconds)
        suffixes = (
            ("perf/time_s", self._m_mfc_seconds),
            ("perf/mfu", self._m_mfc_mfu),
            ("perf/tflops", self._m_mfc_tflops),
        )
        for k, v in stats.items():
            for suffix, gauge in suffixes:
                if k == suffix:
                    gauge.labels("all").set(float(v))
                elif k.endswith("/" + suffix):
                    gauge.labels(k[: -(len(suffix) + 1)]).set(float(v))
        self._export_advisor_residual(stats, step_seconds)

    def _export_advisor_residual(
        self, stats: Dict[str, float], step_seconds: float
    ) -> None:
        """Compose this step's measured per-MFC walls through the DFG
        levels (the same composition apps/advisor.py predicts with) and
        publish the relative error vs the measured step."""
        from areal_tpu.analysis import costmodel

        walls: Dict[str, float] = {}
        for node in self.dfg.nodes:
            v = stats.get(f"{node.name}/perf/time_s")
            if v is None and len(self.dfg.nodes) == 1:
                v = stats.get("perf/time_s")
            if v is not None:
                walls[node.name] = float(v)
        if not walls or step_seconds <= 0:
            return
        levels = [
            [n.name for n in lvl] for lvl in self.dfg.topological_order()
        ]
        pred = costmodel.compose_step(levels, walls)
        self._m_advisor_err.set(abs(pred - step_seconds) / step_seconds)

    async def _post_step(self):
        if self.save_ctl.check():
            await self.save(kind="persistent")
        if self.ckpt_ctl.check():
            await self.save(kind="recover")
        # (eval hook: evaluation jobs are launched by the AutomaticEvaluator
        # watching the checkpoint dir; see areal_tpu/scheduler/evaluator.py)

    # ---------------- worker-death recovery ----------------

    async def _recover_from_worker_death(self, err: WorkerDeadError) -> None:
        """Absorb a WorkerDeadError surfaced by the pool: emit a
        structured fault report, abort the half-finished step (streamed
        train chunks included), wait for the worker to be relaunched, and
        roll every worker back to the last recover checkpoint.  Raises —
        so run() exits non-zero — when the recovery budget is exhausted
        or there is no checkpoint to roll back to."""
        self._recoveries += 1
        self._m_recoveries.inc()
        report = {
            "event": "worker_dead",
            "worker_id": err.worker_id,
            "reason": err.reason,
            "step": self.step_info.global_step,
            "dead_workers": sorted(self.pool.dead_workers),
            "recovery": self._recoveries,
            "max_recoveries": self.max_recoveries,
        }
        logger.error(f"FAULT_REPORT {json.dumps(report, sort_keys=True)}")
        # Flight recorder: preserve the last seconds of structured events
        # around the death — the ring is cheap to keep and priceless now.
        tracer.flight_event(
            "worker_dead",
            worker_id=err.worker_id,
            reason=err.reason,
            step=self.step_info.global_step,
        )
        tracer.flight_dump("worker_dead", role="master", rank=0)
        if self._recoveries > self.max_recoveries:
            raise RuntimeError(
                f"recovery budget exhausted ({self.max_recoveries}): "
                f"worker {err.worker_id} dead: {err.reason}"
            )
        await self._abort_step()
        if self.worker_relauncher is not None:
            ret = self.worker_relauncher(sorted(self.pool.dead_workers))
            if inspect.isawaitable(ret):
                await ret
        # A relaunched worker re-joins with a fresh hello (ZMQ pool) or a
        # revive() (in-process pool); block until the fleet is whole again
        # rather than dispatching into a hole.
        await self.pool.wait_workers()
        if not self.load_recover_info():
            raise RuntimeError(
                f"worker {err.worker_id} died before the first recover "
                "checkpoint existed; nothing to roll back to"
            )
        await self._restore_worker_state()
        logger.info(
            f"recovered from worker {err.worker_id} death; resuming at "
            f"step {self.step_info.global_step}"
        )

    # ---------------- step quarantine + escalation ----------------

    def _note_quarantine(self, stats: Dict[str, float]) -> bool:
        """Fold the step's sentinel outcome into the escalation state.

        Any MFC reporting a positive `quarantined` stat means the anomaly
        sentinels tripped and the weight update was discarded on-device
        (engines/train.py guarded apply) or never dispatched
        (interfaces/ppo.py batch sentinels): bump the streak, record the
        step + decoded verdict + offending data ids in the ledger.  A
        clean step resets the streak."""
        quarantined = any(
            k.rsplit("/", 1)[-1] == "quarantined" and v > 0
            for k, v in stats.items()
        )
        if not quarantined:
            if self._consecutive_quarantines:
                self._consecutive_quarantines = 0
                self._m_consec_quar.set(0.0)
            return False
        verdict = 0
        for k, v in stats.items():
            if k.rsplit("/", 1)[-1] == "anomaly_verdict":
                verdict |= int(v)
        self._consecutive_quarantines += 1
        self._m_quarantined.inc()
        self._m_consec_quar.set(float(self._consecutive_quarantines))
        entry = integrity.quarantine_entry(
            self.step_info.global_step, verdict, self._last_data_ids
        )
        self._quarantine_ledger.append(entry.as_dict())
        logger.warning(
            "QUARANTINE "
            + json.dumps(
                {
                    "event": "step_quarantined",
                    "step": self.step_info.global_step,
                    "verdict": verdict,
                    "kinds": list(entry.kinds),
                    "consecutive": self._consecutive_quarantines,
                    "threshold": self.max_consecutive_quarantines,
                },
                sort_keys=True,
            )
        )
        tracer.flight_event(
            "quarantine",
            step=self.step_info.global_step,
            verdict=verdict,
            consecutive=self._consecutive_quarantines,
        )
        return True

    async def _quarantine_rollback(self) -> None:
        """Escalate a quarantine streak: abort any residual step state and
        roll every worker back to the last manifest-valid recover
        checkpoint — quarantined steps never checkpoint, so that target
        predates the first anomaly of the streak.  Shares (and is bounded
        by) the worker-death recovery budget."""
        self._recoveries += 1
        self._m_recoveries.inc()
        self._m_quar_rollbacks.inc()
        report = {
            "event": "quarantine_rollback",
            "step": self.step_info.global_step,
            "consecutive_quarantines": self._consecutive_quarantines,
            "ledger_tail": self._quarantine_ledger[
                -self._consecutive_quarantines:
            ],
            "recovery": self._recoveries,
            "max_recoveries": self.max_recoveries,
        }
        logger.error(f"FAULT_REPORT {json.dumps(report, sort_keys=True)}")
        tracer.flight_event(
            "quarantine_escalation",
            step=self.step_info.global_step,
            consecutive=self._consecutive_quarantines,
        )
        tracer.flight_dump("quarantine_rollback", role="master", rank=0)
        if self._recoveries > self.max_recoveries:
            raise RuntimeError(
                f"recovery budget exhausted ({self.max_recoveries}): "
                f"{self._consecutive_quarantines} consecutive quarantined "
                "steps"
            )
        await self._abort_step()
        if not self.load_recover_info():
            raise RuntimeError(
                "quarantine streak hit before the first recover checkpoint "
                "existed; nothing to roll back to"
            )
        await self._restore_worker_state()
        # The streak is resolved by the rollback (the replayed steps get a
        # fresh verdict); load_recover_info restored the persisted count,
        # which described the saved state, not the post-rollback one.
        self._consecutive_quarantines = 0
        self._m_consec_quar.set(0.0)
        logger.info(
            "quarantine rollback complete; resuming at step "
            f"{self.step_info.global_step}"
        )

    async def _abort_step(self) -> None:
        """Flush the in-flight step after a worker death so the retried
        step starts from a clean slate: cancel prefetch tasks, drop open
        train streams on surviving workers (train_stream_* state must not
        leak into the retry), and reset the master's data-plane maps."""
        tasks = list(self._ahead_queue)
        self._ahead_queue.clear()
        if self._ahead_task is not None:
            tasks.append(self._ahead_task)
            self._ahead_task = None
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        alive = [
            w
            for w in range(self.pool.n_workers)
            if w not in self.pool.dead_workers
        ]
        await asyncio.gather(
            *[
                self.pool.request(w, {"type": "train_stream_abort"})
                for w in alive
            ],
            return_exceptions=True,
        )
        self.buffer.clear()
        for fut in self._inflight.values():
            if not fut.done():
                fut.cancel()
        self._inflight.clear()
        self._owners.clear()
        self._xfer_acc.clear()
        self._shard_info_cache.clear()

    # ---------------- one step ----------------

    async def execute_step(self) -> Dict[str, float]:
        results: Dict[str, Dict[str, float]] = {}
        # clear(), never rebind: with rollout_ahead the NEXT step's
        # prefetch transfers run concurrently and must keep landing in the
        # live dict (wall-clock attribution — a transfer counts toward the
        # step during which it actually moved bytes).
        self._xfer_acc.clear()
        self._sync_acc.clear()
        if self._async_rl and self._source_nodes:
            await self._execute_step_async_rl(results)
        elif self.rollout_ahead > 0 and self._source_nodes:
            await self._execute_step_async(results)
        elif self.pipeline_overlap and self._source_nodes:
            await self._execute_step_streamed(results)
        else:
            coros = [self._load_data()]
            for node in self.dfg.nodes:
                coros.append(self._run_mfc(node, results))
            await asyncio.gather(*coros)
        if self._ahead_task is not None:
            # Cache clearing snapshots the buffer's keep-ids: the prefetch
            # must have amended its outputs first or they'd be dropped (the
            # shipped PPO graph already serializes via the weight-sync
            # hook; this keeps arbitrary graphs safe).
            await self._ahead_task
        if self.difficulty_filter:
            await self._apply_difficulty_filter()
        await self._clear_worker_caches()
        merged: Dict[str, float] = {}
        for name, stats in results.items():
            for k, v in stats.items():
                merged[f"{name}/{k}" if len(results) > 1 else k] = v
        for k, v in self._xfer_acc.items():
            merged[f"transfer/{k}"] = v
        merged.update(self._sync_acc)
        for k, v in self.buffer.stats().items():
            merged[f"buffer/{k}"] = float(v)
        return merged

    async def _execute_step_async(self, results: Dict) -> None:
        """One step with one-step-ahead rollouts (reference capability:
        AReaL's asynchronous RL — decoupled generation overlapping
        training; our DFG equivalent of overlapping the source GENERATE
        nodes of step t+1 with the rest of step t's graph).

        Steady state per step: (a) take this step's rollouts from the
        prefetch task started last step; (b) register the NEXT batch's data
        (synchronously — cache clearing must see its ids) and launch the
        next prefetch; (c) run the rest of this step's graph concurrently
        with that prefetch.  The weight-sync hook awaits the in-flight
        generation (see _run_hook), so rollouts never mix weight versions
        and the behavior policy is exactly one step stale.

        Recover note: a crash loses the in-flight prefetch batch (its data
        cursor already advanced) — one skipped batch per recovery, the
        async-RL tradeoff."""
        if self._ahead_task is not None:
            results.update(await self._ahead_task)
            self._ahead_task = None
        else:
            # First step (or restart): no prefetch yet — run sources inline.
            await self._load_data()
            results.update(await self._prefetch_rollouts())
        nxt = self.step_info.global_step + 1
        if self._total_steps is None or nxt < self._total_steps:
            await self._load_data()
            self._ahead_task = asyncio.create_task(self._prefetch_rollouts())
        rest = [n for n in self.dfg.nodes if n not in self._source_nodes]
        await asyncio.gather(*[self._run_mfc(n, results) for n in rest])

    async def _prefetch_rollouts(self) -> Dict[str, Dict[str, float]]:
        # Mark this context (inherited by the gather children) so a hook
        # running INSIDE the prefetch never awaits the prefetch task —
        # task-identity checks can't see through gather's child tasks.
        # Token-reset matters: the first step awaits this coroutine INLINE
        # in the run-loop's own context, which must not stay marked.
        token = _IN_PREFETCH.set(True)
        try:
            results: Dict[str, Dict[str, float]] = {}
            await asyncio.gather(
                *[self._run_mfc(n, results) for n in self._source_nodes]
            )
            return results
        finally:
            _IN_PREFETCH.reset(token)

    # ---------------- asynchronous RL (staleness-bounded pipeline) ------

    def _topup_prefetch(self) -> None:
        """Keep at most K = max_head_offpolicyness + 1 rollout batches
        launched AHEAD of consumption (trainer_version counts consumed
        batches: one per step).  The n-th batch then launches no earlier
        than step n-K, stamps a head version >= n-K, and FIFO consumption
        reads it at step n-1 — staleness <= K-1 = the cap, so admission
        never rejects in steady state and K=1 degrades to the synchronous
        generate-then-train ordering.  Bounding by queue length instead
        would relaunch a full step early (the queue drains at step START,
        before this step's weight update) and stamp a version that is
        cap+1 stale at consumption."""
        limit = self._trainer_version + self._async_K
        if self._total_steps is not None:
            limit = min(limit, self._total_steps)
        while self._batches_launched < limit:
            self._batches_launched += 1
            self._ahead_queue.append(
                asyncio.create_task(self._prefetch_rollout_batch())
            )

    async def _prefetch_rollout_batch(self):
        """One stamped rollout batch: fetch a dataset batch, then run the
        source GENERATE nodes once the (serialized) generator frees up.
        The trainer version at GENERATION START is the head-version stamp
        the replay buffer's admission rule keys on — a weight sync landing
        mid-generation does not change the stamp, mirroring the gen
        server's interruptible in-memory push where the tail of a request
        decodes under newer weights than its head."""
        if self._gen_lock is None:
            self._gen_lock = asyncio.Lock()
        ids = await self._load_data()
        token = _IN_PREFETCH.set(True)
        try:
            results: Dict[str, Dict[str, float]] = {}
            async with self._gen_lock:
                v0 = self._trainer_version
                await asyncio.gather(
                    *[self._run_mfc(n, results) for n in self._source_nodes]
                )
            return results, v0, ids
        finally:
            _IN_PREFETCH.reset(token)

    async def _execute_step_async_rl(self, results: Dict) -> None:
        """One step of the replay-buffer-driven pipeline (reference:
        AReaL's asynchronous RL, arxiv 2505.24298 §4.1).

        Unlike rollout_ahead, weight syncs (the train node's realloc
        post-hook) apply WITHOUT draining the pipeline: a batch
        mid-generation keeps its head-version stamp and finishes under
        the new weights; decoupled PPO (behav_imp_weight_cap on the actor
        interface) corrects the off-policy gap admission lets through.
        With max_head_offpolicyness=0, exactly one batch is generated and
        consumed inside each step — today's synchronous ordering and
        numerics."""
        self._topup_prefetch()
        while self.replay is None or len(self.replay) == 0:
            if not self._ahead_queue:
                raise RuntimeError(
                    "async_rl: replay buffer empty with no rollout batches "
                    "in flight (admission rejected everything?)"
                )
            gen_stats, v0, ids = await self._ahead_queue.popleft()
            self._topup_prefetch()
            self._batch_seq += 1
            traj = Trajectory(
                qid=f"rollout_batch{self._batch_seq}",
                prompt_ids=[],
                output_ids=[],
                output_logprobs=[],
                no_eos=[],
                version_start=v0,
                version_end=self._trainer_version,
                data={"stats": gen_stats, "ids": ids},
            )
            if not self.replay.put(traj):
                logger.warning(
                    f"async_rl: rejected {traj.qid} (head version {v0} vs "
                    f"trainer {self._trainer_version}, cap "
                    f"{self.max_head_offpolicyness})"
                )
                await self._drop_batch_ids(ids)
                # The rejected batch will never be consumed: release its
                # launch slot so a replacement (stamped with the CURRENT
                # version) can keep the step budget whole.
                self._batches_launched -= 1
                self._topup_prefetch()
        # Resident => returns immediately; FIFO gives the oldest
        # admissible batch.
        traj = self.replay.get_batch(1, timeout=0)[0]
        await self._flush_replay_drops()
        staleness = traj.staleness(self._trainer_version)
        tracer.flight_event(
            "train_chunk",
            qid=traj.qid,
            staleness=traj.staleness(self._trainer_version),
            version=self._trainer_version,
        )
        results.update(traj.data["stats"])
        rest = [n for n in self.dfg.nodes if n not in self._source_nodes]
        await asyncio.gather(*[self._run_mfc(n, results) for n in rest])
        self._trainer_version += 1
        self.replay.set_version(self._trainer_version)
        await self._flush_replay_drops()
        wm = self.replay.watermarks()
        results["replay"] = {
            "staleness": float(staleness),
            "size": float(wm["size"]),
            "in_flight_batches": float(len(self._ahead_queue)),
            "accepted": float(wm["accepted"]),
            "rejected": float(wm["rejected"]),
            "dropped_stale": float(wm["dropped_stale"]),
            "evicted": float(wm["evicted"]),
        }

    # ---------------- pipeline-overlapped step (streamed) ----------------

    async def _execute_step_streamed(self, results: Dict) -> None:
        """One step as a group-granular dataflow (ROADMAP item 3; OPPO,
        arxiv 2509.25762; Podracer's streamed actor→learner handoff,
        arxiv 2104.06272).

        The batch is split into chunks of `pipeline_chunk_seqs` prompts.
        Each chunk flows through the graph in topological order — gen,
        then ref/reward inference, then TRAIN grad accumulation — as one
        asyncio task, with `overlap_window` chunks in flight: chunk i's
        ref/reward/train stages run while chunk i+1 is still decoding.
        Per-node asyncio locks serialize same-engine calls (the
        in-process workers have no internal locking), so the pipeline is
        a classic stage pipeline: stages overlap ACROSS chunks, never
        within one engine.  TRAIN nodes use the worker's
        mfc_stream_begin/chunk/end protocol: grads accumulate into the
        engine's donated sum per chunk and the single optimizer step
        fires after the last chunk (engines/train.py streamed entry
        point).

        overlap_window=1 is the bit-exactness gate: the whole batch runs
        through the UNCHANGED per-node `_run_mfc` path (the same code the
        barrier executor gathers), sequentially in topological order —
        identical payloads, identical numerics, while still emitting the
        `pipe:*` spans and `pipeline/*` stats for A/B attribution.

        Requires donation_safe_swap on colocated generators (validated
        in experiments/check.py): later chunks decode while earlier
        chunks accumulate grads, so the generator must not alias buffers
        the optimizer step donates.  DP replica splitting and
        shard-exact shipping fall back to primary-group broadcast here
        (chunks are small; shard planning needs whole-batch metadata).
        """
        t_step0 = time.monotonic()
        ids = await self._load_data()
        order = [n for lvl in self.dfg.topological_order() for n in lvl]
        spans: Dict[str, List[Tuple[float, float]]] = {
            n.name: [] for n in order
        }

        if self.overlap_window <= 1:
            for node in order:
                t0 = time.monotonic()
                with tracer.span(
                    f"pipe:{node.name}", stage=node.name, chunk=0,
                    n=len(ids),
                ):
                    await self._run_mfc(node, results)
                spans[node.name].append((t0, time.monotonic()))
            self._m_pipe_chunks.inc()
            self._emit_pipeline_stats(results, spans, t_step0, 1)
            return

        k = self.pipeline_chunk_seqs
        chunks = [ids[i : i + k] for i in range(0, len(ids), k)]
        sem = asyncio.Semaphore(self.overlap_window)
        locks: Dict[str, asyncio.Lock] = {
            n.name: asyncio.Lock() for n in order
        }
        started: set = set()
        node_stats: Dict[str, List[Dict]] = {n.name: [] for n in order}

        async def run_chunk(ci: int, chunk_ids: List[str]) -> None:
            async with sem:
                for node in order:
                    group = self._group(str(node.model_name))
                    is_train = (
                        node.interface_type == ModelInterfaceType.TRAIN_STEP
                    )
                    async with locks[node.name]:
                        if node.name not in started:
                            started.add(node.name)
                            for hook in node.pre_hooks:
                                await self._run_hook(hook, node, group)
                            if is_train:
                                await self._release_aliased_generators(node)
                                await asyncio.gather(
                                    *[
                                        self.pool.request(
                                            w,
                                            {
                                                "type": "mfc_stream_begin",
                                                "model_name": str(
                                                    node.model_name
                                                ),
                                                "mb_spec": node.mb_spec,
                                            },
                                        )
                                        for w in group
                                    ]
                                )
                        t0 = time.monotonic()
                        with tracer.span(
                            f"pipe:{node.name}", stage=node.name,
                            chunk=ci, n=len(chunk_ids),
                        ):
                            if is_train:
                                await asyncio.gather(
                                    *[
                                        self._ensure_data(node, chunk_ids, w)
                                        for w in group
                                    ]
                                )
                                payload = {
                                    "type": "mfc_stream_chunk",
                                    "model_name": str(node.model_name),
                                    "ids": chunk_ids,
                                    "input_keys": list(node.input_keys),
                                    "input_key_remap": dict(
                                        node.input_key_remap
                                    ),
                                    "mb_spec": node.mb_spec,
                                }
                                resps = await asyncio.gather(
                                    *[
                                        self.pool.request(w, payload)
                                        for w in group
                                    ]
                                )
                                node_stats[node.name].append(
                                    resps[0].get("stats") or {}
                                )
                            else:
                                resp = await self._dispatch_mfc(
                                    node, chunk_ids, group
                                )
                                node_stats[node.name].append(
                                    resp.get("stats") or {}
                                )
                        spans[node.name].append((t0, time.monotonic()))
            self._m_pipe_chunks.inc()

        await asyncio.gather(
            *[run_chunk(ci, c) for ci, c in enumerate(chunks)]
        )

        # Close the train streams (the one scaled optimizer step each),
        # then post-hooks in graph order — weight syncs fire exactly once
        # per step, after the full grad sum, as on the barrier path.
        for node in order:
            group = self._group(str(node.model_name))
            if node.interface_type == ModelInterfaceType.TRAIN_STEP:
                t0 = time.monotonic()
                with tracer.span(
                    f"pipe:{node.name}", stage=node.name, chunk=-1,
                    apply=True,
                ):
                    resps = await asyncio.gather(
                        *[
                            self.pool.request(
                                w,
                                {
                                    "type": "mfc_stream_end",
                                    "model_name": str(node.model_name),
                                    "mb_spec": node.mb_spec,
                                },
                            )
                            for w in group
                        ]
                    )
                spans[node.name].append((t0, time.monotonic()))
                results[node.name] = resps[0].get("stats") or {}
                replicas = self.replicas.get(str(node.model_name))
                if replicas and len(replicas) > 1:
                    await self._sync_interface_state(
                        str(node.model_name), group[0], replicas
                    )
            else:
                results[node.name] = merge_stats(node_stats[node.name])
            for hook in node.post_hooks:
                await self._run_hook(hook, node, group)

        # Streamed dispatch bypassed get_batch_for_rpc; take each node's
        # batch now (all keys are resident, so this returns immediately)
        # purely to mark consumption so the ledger can evict the step's
        # entries — otherwise the buffer grows without bound.
        for node in order:
            await self.buffer.get_batch_for_rpc(node, timeout=60)
        self._emit_pipeline_stats(results, spans, t_step0, len(chunks))

    def _emit_pipeline_stats(
        self,
        results: Dict,
        spans: Dict[str, List[Tuple[float, float]]],
        t0: float,
        n_chunks: int,
    ) -> None:
        """Fill/bubble attribution for the streamed step: per stage,
        busy = union of its chunk dispatch intervals; fill = busy /
        step window; bubble = idle gaps BETWEEN the stage's first and
        last chunk (the inter-chunk stall the overlap should shrink)."""
        window = max(time.monotonic() - t0, 1e-9)
        pipe: Dict[str, float] = {
            "n_chunks": float(n_chunks),
            "window": float(self.overlap_window),
            "step_window_s": window,
        }
        for name, ivals in spans.items():
            if not ivals:
                continue
            ivals = sorted(ivals)
            busy = 0.0
            cur_a, cur_b = ivals[0]
            for a, b in ivals[1:]:
                if a > cur_b:
                    busy += cur_b - cur_a
                    cur_a, cur_b = a, b
                else:
                    cur_b = max(cur_b, b)
            busy += cur_b - cur_a
            span = ivals[-1][1] - ivals[0][0]
            fill = busy / window
            bubble = max(span - busy, 0.0)
            pipe[f"fill_{name}"] = fill
            pipe[f"bubble_s_{name}"] = bubble
            self._m_pipe_fill.labels(name).set(fill)
            self._m_pipe_bubble.labels(name).set(bubble)
        results["pipeline"] = pipe

    async def _flush_replay_drops(self) -> None:
        """Purge the ledger entries of batches the replay buffer discarded
        (capacity eviction or aged past the cap) via its on_drop hook."""
        if not self._replay_dropped:
            return
        dropped, self._replay_dropped = self._replay_dropped, []
        for traj in dropped:
            await self._drop_batch_ids((traj.data or {}).get("ids") or [])

    async def _drop_batch_ids(self, ids: List[str]) -> None:
        if not ids:
            return
        await self.buffer.drop_ids(ids)
        for sid in ids:
            self._owners.pop(sid, None)

    async def _load_data(self) -> List[str]:
        if self._fetch_lock is None:
            self._fetch_lock = asyncio.Lock()
        ids: List[str] = []
        # The lock keeps concurrently-outstanding async-RL prefetches from
        # racing two `next()` calls on one dataloader iterator.
        async with self._fetch_lock:
            with tracer.span("load_data", cat="host"):
                resps = await asyncio.gather(
                    *[
                        self.pool.request(w, {"type": "fetch"})
                        for w in self.data_worker_ids
                    ]
                )
                for w, r in zip(self.data_worker_ids, resps):
                    meta = r["meta"]
                    self._record_owner(meta, w)
                    await self.buffer.put_batch(
                        meta, step=self.step_info.global_step
                    )
                    ids.extend(meta.ids)
            self._last_data_ids = list(ids)
        return ids

    def _record_owner(self, meta, worker: int, replace: bool = False):
        for sid in meta.ids:
            km = self._owners.setdefault(sid, {})
            for key in meta.keys:
                if replace:
                    km[key] = {worker}
                else:
                    km.setdefault(key, set()).add(worker)

    async def _ensure_data(self, node: MFCDef, ids, dst: int, keys=None):
        """Move any input (id, key) not yet resident on `dst` from an owning
        worker, as one tagged transfer per source (the data-plane pre-hook;
        reference: model_function_call data_transfer pre-hooks +
        redistributor.derive_plan).  `keys` restricts the shipped keys (the
        sharded plane ships heavy keys for a member's own rows only)."""
        plans: Dict[int, Dict[str, list]] = {}  # src -> key -> [ids]
        waits = set()
        started: list = []
        # Planning is synchronous (no awaits), so ownership marks and
        # in-flight registrations below are atomic wrt other coroutines.
        for sid in ids:
            km = self._owners.get(sid, {})
            for key in keys if keys is not None else node.input_keys:
                holders = km.get(key)
                if holders is None:
                    raise KeyError(
                        f"MFC {node.name}: no worker holds {key!r} for "
                        f"data id {sid!r}"
                    )
                if dst in holders:
                    fut = self._inflight.get((sid, key, dst))
                    if fut is not None:
                        waits.add(fut)
                    continue
                # Valid sources are settled holders (copy not in flight).
                settled = [
                    w
                    for w in holders
                    if (sid, key, w) not in self._inflight
                ]
                src = min(settled)
                plans.setdefault(src, {}).setdefault(key, []).append(sid)
                km[key].add(dst)
                fut = asyncio.get_running_loop().create_future()
                self._inflight[(sid, key, dst)] = fut
                started.append((sid, key, dst))
        err: Optional[BaseException] = None
        try:
            for src, key_ids in plans.items():
                # One transfer per (src, key-set): group ids needing the
                # same keys.
                by_ids: Dict[tuple, set] = {}
                for key, sids in key_ids.items():
                    for sid in sids:
                        by_ids.setdefault(sid, set()).add(key)
                groups: Dict[frozenset, list] = {}
                for sid, keys in by_ids.items():
                    groups.setdefault(frozenset(keys), []).append(sid)
                for keys, sids in groups.items():
                    xfer_id = self._xfer_id
                    self._xfer_id += 1
                    with tracer.span(
                        "xfer:data", cat="comms",
                        src=src, dst=dst, n=len(sids),
                        # Same label the worker stamps on the consuming
                        # compute span, so the profile store can join
                        # transfer bytes to their MFC.
                        mfc=f"{node.model_name}:{node.interface_type.value}",
                    ) as targs:
                        send_r, recv_r = await asyncio.gather(
                            self.pool.request(
                                src,
                                {
                                    "type": "data_send",
                                    "ids": sids,
                                    "keys": sorted(keys),
                                    "dst": dst,
                                    "xfer_id": xfer_id,
                                },
                            ),
                            self.pool.request(
                                dst,
                                {"type": "data_recv", "xfer_id": xfer_id},
                            ),
                        )
                        targs["bytes"] = send_r.get("bytes", 0)
                    self._acc_xfer("data", send_r, recv_r)
        except BaseException as e:  # propagate to waiters, then re-raise
            err = e
            raise
        finally:
            for tag in started:
                fut = self._inflight.pop(tag, None)
                if fut is None or fut.done():
                    continue
                if err is None:
                    fut.set_result(None)
                else:
                    fut.set_exception(
                        RuntimeError(f"transfer for {tag} failed: {err!r}")
                    )
        if waits:
            await asyncio.gather(*waits)

    def _acc_xfer(
        self,
        kind: str,
        send_r: Optional[Dict] = None,
        recv_r: Optional[Dict] = None,
        count: bool = True,
    ):
        """Fold one transfer's reply metrics into this step's accounting.
        Either side may be absent (e.g. param recvs arrive separately from
        their sends); `count` increments the per-kind transfer counter."""
        acc = self._xfer_acc
        if send_r is not None:
            acc[f"{kind}_bytes"] = (
                acc.get(f"{kind}_bytes", 0.0)
                + float(send_r.get("bytes", 0) or 0)
            )
            acc[f"{kind}_send_s"] = (
                acc.get(f"{kind}_send_s", 0.0)
                + float(send_r.get("seconds", 0.0) or 0.0)
            )
        if recv_r is not None:
            acc[f"{kind}_recv_s"] = (
                acc.get(f"{kind}_recv_s", 0.0)
                + float(recv_r.get("seconds", 0.0) or 0.0)
            )
        if count:
            acc[f"{kind}_count"] = acc.get(f"{kind}_count", 0.0) + 1.0

    def _group(self, model_key: str) -> List[int]:
        return self.groups.get(model_key, [self.placement[model_key]])

    def _hook_target_set(self, model_key: str) -> List[int]:
        """Workers that must receive a param hook for this model: every
        replica, or the SPMD group."""
        return self.replicas.get(model_key) or self._group(model_key)

    async def _run_mfc(self, node: MFCDef, results: Dict):
        batch = await self.buffer.get_batch_for_rpc(node, timeout=600)
        group = self._group(str(node.model_name))
        # Pre hooks (param sync from another model, e.g. gen <- train).
        for hook in node.pre_hooks:
            await self._run_hook(hook, node, group)
        if (
            self.rollout_ahead == 0
            and not self._async_rl
            and node.interface_type == ModelInterfaceType.TRAIN_STEP
        ):
            # Skipped in async modes: a prefetch may be mid-generation on
            # the aliased weights while this node trains.
            await self._release_aliased_generators(node)
        replicas = self.replicas.get(str(node.model_name))
        splittable = (
            replicas
            and len(replicas) > 1
            and node.interface_type
            in (ModelInterfaceType.GENERATE, ModelInterfaceType.INFERENCE)
            and len(batch.ids) >= len(replicas)
        )
        if splittable:
            stats_list = await self._run_mfc_split(node, batch, replicas)
            # Denominator-aware DP-head gather: token-weighted where the
            # shards report `<key>_denominator`, mean otherwise.
            results[node.name] = merge_stats(
                [st or {} for st in stats_list]
            )
        else:
            resp = await self._dispatch_mfc(
                node, list(batch.ids), group, meta=batch
            )
            results[node.name] = resp.get("stats") or {}
        if (
            node.interface_type == ModelInterfaceType.TRAIN_STEP
            and replicas
            and len(replicas) > 1
        ):
            # Algorithm state (e.g. value-norm moments) only advanced on the
            # training primary; broadcast it so inference-only replicas
            # denormalize with the same statistics.
            await self._sync_interface_state(
                str(node.model_name), group[0], replicas
            )
        for hook in node.post_hooks:
            await self._run_hook(hook, node, group)

    async def _sync_interface_state(
        self, model_key: str, primary: int, replicas: List[int]
    ):
        state = await self.pool.request(
            primary, {"type": "interface_state"}
        )
        sd = (state.get("states") or {}).get(model_key)
        if not sd:
            return
        await asyncio.gather(
            *[
                self.pool.request(
                    w,
                    {
                        "type": "load_interface_state",
                        "states": {model_key: sd},
                    },
                )
                for w in replicas
                if w != primary
            ]
        )

    async def _run_mfc_split(self, node: MFCDef, batch, replicas: List[int]):
        """DP dispatch: token-balance-split the batch over independent
        replicas, run the sub-calls concurrently, gather their outputs
        (reference: FFD split + DP-head gather,
        model_function_call.py:282)."""
        from areal_tpu.base.datapack import partition_balanced

        key = next(iter(set(node.input_keys) & set(batch.keys)), None)
        if key is None:
            key = next(iter(batch.keys))
        sizes = [int(sum(s)) for s in batch.seqlens[key]]
        bins = partition_balanced(sizes, len(replicas))
        parts = [
            [batch.ids[i] for i in bin_idx]
            for bin_idx in bins
        ]
        resps = await asyncio.gather(
            *[
                self._dispatch_mfc(node, ids, [w])
                for ids, w in zip(parts, replicas)
                if ids
            ]
        )
        return [r.get("stats") for r in resps]

    async def _shard_infos(
        self, node: MFCDef, group: List[int]
    ) -> Optional[List[Tuple[int, int]]]:
        """Each member's (shard_rank, n_shards) for this model's batch
        rows, cached per model key.  None when sharded shipping cannot
        apply (any member wants the full batch, or members disagree on
        the shard count)."""
        key = str(node.model_name)
        infos = self._shard_info_cache.get(key)
        if infos is None:
            resps = await asyncio.gather(
                *[
                    self.pool.request(
                        w, {"type": "shard_info", "model_name": key}
                    )
                    for w in group
                ]
            )
            infos = [(int(r["rank"]), int(r["n"])) for r in resps]
            self._shard_info_cache[key] = infos
        ns = {n for _, n in infos}
        if len(ns) != 1:
            return None  # members disagree: fall back to full broadcast
        n = ns.pop()
        if n <= 1 or {r for r, _ in infos} != set(range(n)):
            return None  # unsharded, or some shard block has no receiver
        return infos

    async def _dispatch_mfc(
        self, node: MFCDef, ids: List[str], group: List[int], meta=None
    ) -> Dict:
        # Data-plane pre-hook.  Default: every group member executes the
        # MFC SPMD-symmetrically and receives the full input batch.  When
        # the node declares shard_keys AND the members' meshes split the
        # batch axis across processes, those keys are shipped
        # SHARD-EXACTLY: each member gets only the rows its own devices
        # consume (the packer derives the global row layout from metadata
        # alone; see packing.split_sharded / pack_sample shard_blocks).
        # Reference: data_manager.py:144-416 shard-exact redistribution.
        shard_keys = set(node.shard_keys) & set(node.input_keys)
        bcast_keys = set(node.input_keys) - shard_keys
        plan = None
        if meta is not None and shard_keys and len(group) > 1:
            infos = await self._shard_infos(node, group)
            if infos is not None:
                n = infos[0][1]
                sizes = [
                    int(sum(meta.seqlens[meta.main_key()][i]))
                    for i in range(len(ids))
                ]
                from areal_tpu.base.datapack import partition_balanced

                blocks = partition_balanced(sizes, n)
                plan = {"blocks": blocks, "infos": infos, "n": n}
        if plan is None:
            await asyncio.gather(
                *[self._ensure_data(node, ids, w) for w in group]
            )
        else:
            coros = []
            for w, (rank, _) in zip(group, plan["infos"]):
                mine = [ids[i] for i in plan["blocks"][rank]]
                if mine:
                    coros.append(
                        self._ensure_data(node, mine, w, keys=shard_keys)
                    )
                if bcast_keys:
                    coros.append(
                        self._ensure_data(node, ids, w, keys=bcast_keys)
                    )
            await asyncio.gather(*coros)
        payload = {
            "type": "mfc",
            "model_name": str(node.model_name),
            "interface_type": node.interface_type.value,
            "ids": ids,
            "input_keys": list(node.input_keys),
            "input_key_remap": dict(node.input_key_remap),
            "output_key_remap": dict(node.output_key_remap),
            "mb_spec": node.mb_spec,
        }
        if plan is not None:
            shard_of = {}
            for s, block in enumerate(plan["blocks"]):
                for i in block:
                    shard_of[ids[i]] = [s, plan["n"]]
            payload["shard_of"] = shard_of
            payload["shard_meta"] = meta.select_keys(
                set(node.input_keys) & meta.keys
            )
        # Dispatch wait: uncategorized on purpose — the master is parked
        # on worker compute here, which the worker tracks attribute.
        with tracer.span(
            f"mfc:{node.name}", model=str(node.model_name), n=len(ids)
        ):
            resps = await asyncio.gather(
                *[self.pool.request(w, payload) for w in group]
            )
        resp = resps[0]  # group[0] is the primary
        if resp.get("meta") is not None:
            # Every member computed (and cached) the full outputs; the
            # primary's copy is authoritative, the rest are extra sources.
            for i, w in enumerate(group):
                self._record_owner(resp["meta"], w, replace=(i == 0))
            await self.buffer.amend_batch(resp["meta"])
        return resp

    async def _release_aliased_generators(self, node: MFCDef):
        """Synchronous colocated trials: a generator configured with
        donation_safe_swap=False ALIASES the train master's buffers (the
        copy-free hot-swap that makes 1.5B PPO fit 16 GB); a live alias
        blocks the optimizer step's buffer donation, transiently costing
        a full extra parameter copy.  Between the last generate() and
        this train node's post-hook resync the weights are dead — tell
        the hook targets to drop them before the step.  Only full-copy
        hooks (eta>=1) qualify: an EMA target still needs its current
        params.  Workers whose engine keeps the defensive copy
        (donation_safe_swap=True, remote generators) no-op.  Replaces the
        reference's weight-refresh ordering, model_worker.py:1040-1067."""
        targets = []
        for hook in node.post_hooks:
            if isinstance(hook, ParamReallocHook) and hook.eta >= 1.0:
                t = str(hook.target)
                targets += [(t, w) for w in self._hook_target_set(t)]
        if targets:
            await asyncio.gather(
                *[
                    self.pool.request(
                        w, {"type": "release_params", "model_name": t}
                    )
                    for t, w in targets
                ]
            )

    async def _run_hook(self, hook, node: MFCDef, group: List[int]):
        if isinstance(hook, OffloadHook):
            target = str(hook.target or node.model_name)
            targets = (
                self.replicas.get(target)
                or (self._hook_target_set(target) if hook.target else group)
            )
            with tracer.span(f"offload:{target}", cat="host"):
                await asyncio.gather(
                    *[
                        self.pool.request(
                            w,
                            {"type": "offload", "model_name": target},
                        )
                        for w in targets
                    ]
                )
        elif isinstance(hook, ParamReallocHook):
            if (
                self._ahead_task is not None
                and not _IN_PREFETCH.get()
                and str(hook.target)
                in {str(n.model_name) for n in self._source_nodes}
            ):
                # Async rollout: never swap a generator's weights while its
                # prefetch is mid-flight — the sync applies between batches
                # (one-step staleness, single weight version per batch).
                await self._ahead_task
            target_group = self._hook_target_set(str(hook.target))
            if target_group == group:
                # Colocated (same member set): every process holds both
                # models; the copy/EMA is a local (or SPMD-collective-free)
                # reshard on each.
                with tracer.span(
                    f"param_sync:{hook.target}", cat="comms"
                ):
                    resps = await asyncio.gather(
                        *[
                            self.pool.request(
                                w,
                                {
                                    "type": "param_sync",
                                    "src": str(node.model_name),
                                    "dst": str(hook.target),
                                    "eta": hook.eta,
                                },
                            )
                            for w in group
                        ]
                    )
                # The primary's own account of the sync: bytes placed,
                # seconds in the device_put, seconds in the handler.
                role = str(hook.target).split("@")[0]
                for k, v in (resps[0].get("sync") or {}).items():
                    key = f"{role}/sync/{k}"
                    self._sync_acc[key] = self._sync_acc.get(key, 0.0) + v
            else:
                # Cross-set realloc over the transfer plane (reference:
                # param_realloc NCCL groups, model_worker.py:1009).  EVERY
                # src member participates in the host gather — a collective
                # when the src mesh spans processes — then the primary ships
                # one copy to each target member; sends and recvs are
                # dispatched concurrently so no side waits on the other's
                # request ordering.
                # Checksummed push with one retry: the receiver verifies
                # the per-leaf-norm checksum the sender stamped before
                # swapping; a payload corrupted in flight raises
                # WeightChecksumError (and bumps the rejection counter)
                # instead of serving poisoned weights, and the push is
                # re-dispatched once with fresh transfer ids.  The
                # sender's serialize-once cache (worker._handle_param_send)
                # makes the retry reuse the gathered host tree, checksum,
                # and wire encoding — only the corrupted-in-flight copy
                # is re-shipped, nothing is re-gathered.
                from areal_tpu.system.paramstore import M_PUSH_SECONDS

                push_t0 = time.monotonic()
                for attempt in (1, 2):
                    xfer_ids = list(
                        range(
                            self._xfer_id, self._xfer_id + len(target_group)
                        )
                    )
                    self._xfer_id += len(target_group)
                    try:
                        with tracer.span(
                            f"param_realloc:{hook.target}", cat="comms",
                            n_dst=len(target_group),
                        ) as realloc_args:
                            resps = await asyncio.gather(
                                *[
                                    self.pool.request(
                                        w,
                                        {
                                            "type": "param_send",
                                            "model_name": str(
                                                node.model_name
                                            ),
                                            "dsts": target_group,
                                            "xfer_ids": xfer_ids,
                                            "sender": i == 0,
                                            "checksum": (
                                                self.weight_push_checksum
                                            ),
                                        },
                                    )
                                    for i, w in enumerate(group)
                                ],
                                *[
                                    self.pool.request(
                                        w,
                                        {
                                            "type": "param_recv",
                                            "model_name": str(hook.target),
                                            "xfer_id": xid,
                                            "eta": hook.eta,
                                        },
                                    )
                                    for w, xid in zip(
                                        target_group, xfer_ids
                                    )
                                ],
                            )
                            realloc_args["bytes"] = sum(
                                int(r.get("bytes", 0) or 0)
                                for r in resps[: len(group)]
                            )
                        break
                    except integrity.WeightChecksumError as e:
                        if attempt >= 2:
                            raise
                        logger.warning(
                            f"weight push to {hook.target} rejected by "
                            f"receiver checksum ({e}); retrying once"
                        )
                # Same fleet signal the broadcast fabric feeds: push_p99
                # in metrics_report covers realloc and fabric pushes.
                M_PUSH_SECONDS.observe(time.monotonic() - push_t0)
                for i, send_r in enumerate(resps[: len(group)]):
                    # Only member 0 actually sends (sender=i==0); the
                    # rest reply bytes=0 and must not bump the transfer
                    # counter or param_count over-reports on multi-member
                    # source groups.
                    self._acc_xfer("param", send_r, count=(i == 0))
                for recv_r in resps[len(group):]:
                    self._acc_xfer("param", recv_r=recv_r, count=False)

    async def _apply_difficulty_filter(self):
        """Remove prompts whose group accuracy this step falls outside the
        configured band — too easy and too hard prompts give GRPO zero
        advantage (reference: model_worker.py:574-639 dataset filtering)."""
        by_worker: Dict[int, List[str]] = {}
        for sid, km in self._owners.items():
            holders = km.get("rewards")
            if holders:
                by_worker.setdefault(min(holders), []).append(sid)
        if not by_worker:
            return
        resps = await asyncio.gather(
            *[
                self.pool.request(w, {"type": "data_accuracy", "ids": ids})
                for w, ids in by_worker.items()
            ]
        )
        accs: Dict[str, float] = {}
        for r in resps:
            accs.update(r.get("accuracy") or {})
        lo = self.difficulty_filter.get("min_accuracy", 0.0)
        hi = self.difficulty_filter.get("max_accuracy", 1.0)
        drop = [sid for sid, a in accs.items() if a < lo or a > hi]
        if not drop:
            return
        resps = await asyncio.gather(
            *[
                self.pool.request(
                    w, {"type": "filter_dataset", "ids": drop}
                )
                for w in self.data_worker_ids
            ]
        )
        removed = sum(int(r.get("removed") or 0) for r in resps)
        self._filtered_ids.extend(drop)
        logger.info(
            f"difficulty filter: removed {removed} prompts "
            f"({len(drop)}/{len(accs)} flagged outside accuracy [{lo}, {hi}])"
        )

    async def _clear_worker_caches(self):
        if self._fetch_lock is None:
            self._fetch_lock = asyncio.Lock()
        async with self._fetch_lock:
            await self._clear_worker_caches_locked()

    async def _clear_worker_caches_locked(self):
        # Under _fetch_lock: an async-RL prefetch's fetch registers its ids
        # in the buffer inside the same critical section, so the keep-set
        # snapshot below can never miss data already cached on a worker.
        keep = list(self.buffer._entries.keys())
        keep_set = set(keep)
        for sid in list(self._owners):
            if sid not in keep_set:
                del self._owners[sid]
        await asyncio.gather(
            *[
                self.pool.request(
                    w, {"type": "clear_cache", "keep_ids": keep}
                )
                for w in range(self.pool.n_workers)
            ]
        )

    # ---------------- save / recover ----------------

    async def save(self, kind: str = "persistent"):
        step = self.step_info.global_step
        if kind == "recover":
            await self._save_recover(step)
            logger.info(f"saved (recover) at step {step}")
            return
        for node in self._train_rpcs:
            d = self._ckpt_dir(node, f"step_{step}")
            # All group members join (the host gather of a process-spanning
            # param tree is collective); only the jax process-0 member
            # writes files.
            await asyncio.gather(
                *[
                    self.pool.request(
                        w,
                        {
                            "type": "save",
                            "model_name": str(node.model_name),
                            "save_dir": d,
                        },
                    )
                    for w in self._group(str(node.model_name))
                ]
            )
        logger.info(f"saved ({kind}) at step {step}")

    async def _save_recover(self, step: int) -> None:
        """Atomic recover-save.  Every train node's weights + optimizer
        state stage into ``recover_checkpoint.tmp.<step>``; a fsynced
        MANIFEST.json (file inventory + model versions + self-checksum)
        makes the staged dir self-validating; only then do ALL staged
        dirs flip into place (old current rotates to ``.prev``, keep
        last-2) and recover_info.pkl is rewritten.  A crash at any point
        leaves a manifest-valid checkpoint + matching-or-older recover
        info on disk — never a torn current."""
        # Version counters for EVERY model on every worker — not just the
        # train nodes: sampling seeds derive from the generation
        # replica's counter (e.g. actor_gen@0), which a rollback must
        # rewind too or the recovered trial redraws different rollouts.
        model_versions: Dict[str, int] = {}
        for w in range(self.pool.n_workers):
            out = await self.pool.request(w, {"type": "model_versions"})
            for k, v in out["versions"].items():
                model_versions[k] = int(v)
        staged_dirs: List[Tuple[str, str]] = []
        for node in self._train_rpcs:
            key = str(node.model_name)
            base = self._ckpt_dir(node, "recover_checkpoint")
            # Leftover .tmp.<step> dirs from a save that died pre-flip.
            recover.clean_stale_stages(base)
            staged = recover.stage_dir(base, step)
            group = self._group(key)
            # All group members join (the host gather of a
            # process-spanning param tree is collective); only the jax
            # process-0 member writes files.
            await asyncio.gather(
                *[
                    self.pool.request(
                        w,
                        {
                            "type": "save",
                            "model_name": key,
                            "save_dir": staged,
                        },
                    )
                    for w in group
                ]
            )
            # Optimizer state next to the weights (Adam moments + schedule
            # position; reference: megatron.py:687-736).
            await asyncio.gather(
                *[
                    self.pool.request(
                        w,
                        {
                            "type": "save_optimizer",
                            "model_name": key,
                            "path": os.path.join(
                                staged, "optimizer_state.pkl"
                            ),
                        },
                    )
                    for w in group
                ]
            )
            recover.write_manifest(
                staged, step, {key: model_versions.get(key, 0)}
            )
            staged_dirs.append((staged, base))
        # Chaos point: a kill here (everything staged, nothing flipped)
        # must leave the previous current checkpoint untouched.
        if self._faults is not None and self._faults.kill_point(
            "recover_stage"
        ):
            os._exit(42)
        for staged, base in staged_dirs:
            recover.commit_checkpoint(staged, base)
            self._m_ckpt_flips.inc()
        self._m_ckpt_last_success.set(time.time())
        # Chaos point: a kill here (flipped, recover info still old)
        # restores older counters against newer weights — detectable via
        # the manifest step, and strictly recoverable.
        if self._faults is not None and self._faults.kill_point(
            "recover_flip"
        ):
            os._exit(42)
        # Data stream position per data worker.
        states = await asyncio.gather(
            *[
                self.pool.request(w, {"type": "data_state"})
                for w in self.data_worker_ids
            ]
        )
        # Algorithm state (e.g. value-norm moments) from every worker.
        iface_states = await asyncio.gather(
            *[
                self.pool.request(w, {"type": "interface_state"})
                for w in range(self.pool.n_workers)
            ]
        )
        info = recover.RecoverInfo(
            last_step_info=self.step_info,
            save_ctl_states={
                "save": self.save_ctl.state_dict(),
                "ckpt": self.ckpt_ctl.state_dict(),
                "eval": self.eval_ctl.state_dict(),
            },
            data_states={
                w: s["states"]
                for w, s in zip(self.data_worker_ids, states)
            },
            interface_states={
                w: s["states"]
                for w, s in enumerate(iface_states)
                if s["states"]
            },
            used_data_ids=list(self._filtered_ids),
            model_versions=model_versions,
            replay_watermarks=(
                self.replay.watermarks()
                if self.replay is not None
                else {}
            ),
            rollout_state=(
                {
                    "trainer_version": self._trainer_version,
                    "batch_seq": self._batch_seq,
                }
                if self._async_rl
                else {}
            ),
            quarantine_ledger=list(self._quarantine_ledger),
            consecutive_quarantines=self._consecutive_quarantines,
        )
        recover.dump(
            info,
            recover.recover_root(
                self.fileroot, self.experiment_name, self.trial_name
            ),
        )

    def _ckpt_dir(self, node: MFCDef, sub: str) -> str:
        return os.path.join(
            self.fileroot, "checkpoints", self.experiment_name,
            self.trial_name, str(node.model_name), sub,
        )

    def load_recover_info(self) -> bool:
        info = recover.load(
            recover.recover_root(
                self.fileroot, self.experiment_name, self.trial_name
            )
        )
        if info is None:
            return False
        self.step_info = info.last_step_info
        if "save" in info.save_ctl_states:
            self.save_ctl.load_state_dict(info.save_ctl_states["save"])
        if "ckpt" in info.save_ctl_states:
            self.ckpt_ctl.load_state_dict(info.save_ctl_states["ckpt"])
        if "eval" in info.save_ctl_states:
            self.eval_ctl.load_state_dict(info.save_ctl_states["eval"])
        # Quarantine audit trail: keep whichever ledger is longer — a
        # fresh restart adopts the persisted one; a live rollback keeps
        # the in-memory entries of the streak that triggered it (those
        # steps never checkpointed, so the persisted ledger predates
        # them).
        ledger = list(getattr(info, "quarantine_ledger", None) or [])
        if len(ledger) > len(self._quarantine_ledger):
            self._quarantine_ledger = ledger
        self._consecutive_quarantines = int(
            getattr(info, "consecutive_quarantines", 0) or 0
        )
        self._m_consec_quar.set(float(self._consecutive_quarantines))
        # Worker-side state (weights, optimizer, data cursors) is restored
        # at run() start, once the pool is serving.
        self._restore_pending = info
        logger.info(f"recovered at step {self.step_info.global_step}")
        return True

    async def _restore_worker_state(self):
        """Reload trained weights + optimizer state from the recover
        checkpoint and rewind data streams; refresh dependent models (e.g.
        the generator) by replaying each train node's realloc post-hooks."""
        info = self._restore_pending
        self._restore_pending = None
        # Model engines are about to be (re)loaded: any cached per-member
        # shard ownership may describe the pre-crash build.  Meshes don't
        # change across a same-config recover today, but a stale entry
        # here would silently mis-ship rows — refresh is one round-trip
        # per model per trial.
        self._shard_info_cache.clear()
        versions = getattr(info, "model_versions", None) or {}
        for node in self._train_rpcs:
            key = str(node.model_name)
            base = self._ckpt_dir(node, "recover_checkpoint")
            # Trust only a manifest-valid dir (current, else the kept
            # .prev) — a torn half-written tree must never be loaded.
            d = recover.latest_valid_checkpoint(base)
            if d is None:
                if os.path.isdir(base) or os.path.isdir(
                    base + recover.PREV_SUFFIX
                ):
                    raise RuntimeError(
                        f"recover checkpoint for {key!r} at {base} failed "
                        "manifest validation (and no intact .prev exists) "
                        "— refusing to restore from a torn checkpoint"
                    )
                continue
            manifest = recover.validate_manifest(d)
            if manifest["step"] != self.step_info.global_step:
                logger.warning(
                    f"checkpoint step {manifest['step']} != recover-info "
                    f"step {self.step_info.global_step} for {key!r} (crash "
                    "between flip and recover-info rewrite); restoring "
                    "anyway"
                )
            group = self._group(key)
            await asyncio.gather(
                *[
                    self.pool.request(
                        w,
                        {
                            "type": "load_model",
                            "model_name": key,
                            "ckpt_dir": d,
                            "optimizer_path": os.path.join(
                                d, "optimizer_state.pkl"
                            ),
                        },
                    )
                    for w in group
                ]
            )
            for hook in node.post_hooks:
                await self._run_hook(hook, node, group)
            logger.info(f"restored {node.model_name} from {d}")
        if versions:
            # Rewind EVERY model's version counter fleet-wide (after the
            # post-hook replay, which must not re-advance them): sampling
            # seeds derive from the generation replica's counter, so a
            # recovered trial redraws the same rollouts only if this is
            # exact.  Workers ignore keys they don't host.
            await asyncio.gather(
                *[
                    self.pool.request(
                        w,
                        {
                            "type": "set_model_versions",
                            "versions": versions,
                        },
                    )
                    for w in range(self.pool.n_workers)
                ]
            )
        # Re-apply difficulty filtering BEFORE rewinding cursors so the
        # dataset the replay walks matches the pre-crash one.
        filtered = getattr(info, "used_data_ids", None) or []
        if filtered:
            self._filtered_ids = list(filtered)
            await asyncio.gather(
                *[
                    self.pool.request(
                        w, {"type": "filter_dataset", "ids": filtered}
                    )
                    for w in self.data_worker_ids
                ]
            )
        data_states = getattr(info, "data_states", None) or {}
        await asyncio.gather(
            *[
                self.pool.request(
                    w, {"type": "load_data_state", "states": states}
                )
                for w, states in data_states.items()
            ]
        )
        iface_states = getattr(info, "interface_states", None) or {}
        await asyncio.gather(
            *[
                self.pool.request(
                    w, {"type": "load_interface_state", "states": states}
                )
                for w, states in iface_states.items()
            ]
        )
        if self._async_rl:
            # Resume admission where the crashed trial stopped: version
            # watermarks + counters from the replay buffer, the pipeline
            # cursor rewound to consumed batches (in-flight prefetches
            # died with the process — one lost batch per outstanding
            # prefetch, the async-RL recover tradeoff).
            wm = getattr(info, "replay_watermarks", None) or {}
            if wm:
                self.replay.load_watermarks(wm)
            rs = getattr(info, "rollout_state", None) or {}
            self._trainer_version = int(
                rs.get("trainer_version", self.step_info.global_step)
            )
            self._batch_seq = int(rs.get("batch_seq", 0))
            if self.replay.version < self._trainer_version:
                self.replay.set_version(self._trainer_version)
            self._batches_launched = self.step_info.global_step

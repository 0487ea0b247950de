"""Experiment launcher: master in this process, workers via the scheduler.

Capability parity: realhf/apps/main.py (`main_start` submit + wait + recover
retry loop) and system/controller.py (worker configure/start) — condensed:
the ExperimentPlan already carries fully-resolved WorkerConfigs, so
"configuring" a worker is shipping it a pickle, and the master runs in the
launcher process (the reference's separate master worker process exists to
survive launcher death under slurm; the local/TPU-pod launcher supervises
directly).

Two execution modes:
- run_experiment_inproc(plan): workers in-process (tests, single-host
  trials, and the bench path) — no subprocesses, no sockets.
- run_experiment(plan): ZMQ multi-process — one subprocess per
  WorkerConfig, file-backed name-resolve for discovery, recover retry loop
  re-submitting everything on failure (reference recover mode "auto").
  This launcher process never initializes a JAX backend: workers inherit
  JAX_PLATFORMS like any JAX program, and on a TPU host the first worker
  to start owns the chips (a second colocated worker fails on "TPU
  already in use"; disjoint gen/train meshes on one host are the
  in-process, device-offset form).
"""

import asyncio
import os
import pickle
import sys
from typing import Dict, List, Optional

from areal_tpu.base import logging, metrics, name_resolve, tracer
from areal_tpu.experiments.common import ExperimentPlan
from areal_tpu.scheduler import JobException, make_scheduler
from areal_tpu.system.master import MasterWorker
from areal_tpu.system.stream import ZMQWorkerPool

logger = logging.getLogger("main")


def _make_master(plan: ExperimentPlan, pool) -> MasterWorker:
    return MasterWorker(
        dfg=plan.dfg,
        pool=pool,
        model_placement=plan.model_placement,
        data_worker_ids=plan.data_worker_ids,
        ctrl=plan.ctrl,
        fileroot=plan.fileroot,
        experiment_name=plan.experiment_name,
        trial_name=plan.trial_name,
        model_groups=plan.model_groups,
        model_replicas=plan.model_replicas,
        difficulty_filter=plan.difficulty_filter,
        rollout_ahead=plan.rollout_ahead,
        max_recoveries=plan.max_recoveries,
    )


def run_experiment_inproc(plan: ExperimentPlan, tokenizer=None, inspect=None):
    """All workers in this process — delegates to the canonical in-process
    runner (areal_tpu/experiments/common.py run_experiment).

    `inspect(master, stage)` is the canonical runner's hook (stage "built",
    then "done"); chip_smoke.py checks weights, counters and device
    residency through it."""
    from areal_tpu.experiments.common import run_experiment as _run_inproc

    _, stats = _run_inproc(plan, tokenizer=tokenizer, inspect=inspect)
    return stats


async def _watch_jobs(sched):
    """Fail fast if any worker process dies while the master is running."""
    from areal_tpu.scheduler import JobState
    from areal_tpu.scheduler.client import read_log_tail

    while True:
        for info in sched.find_all():
            if info.state in (JobState.FAILED, JobState.CANCELLED):
                raise JobException(
                    "trial", info.name, info.host or "?", info.state
                ) from RuntimeError(
                    f"worker log tail:\n{read_log_tail(info.log_path)}"
                )
        await asyncio.sleep(1.0)


async def _run_master_zmq(plan: ExperimentPlan, n_workers: int, sched):
    pool = ZMQWorkerPool(
        plan.experiment_name,
        plan.trial_name,
        n_workers,
        mfc_timeout_s=plan.mfc_timeout_s,
        worker_heartbeat_s=plan.worker_heartbeat_s,
    )
    watchdog = asyncio.get_running_loop().create_task(_watch_jobs(sched))
    try:
        master_task = asyncio.get_running_loop().create_task(
            _drive_master(plan, pool)
        )
        done, _ = await asyncio.wait(
            {master_task, watchdog}, return_when=asyncio.FIRST_COMPLETED
        )
        if watchdog in done:  # worker died -> propagate
            master_task.cancel()
            watchdog.result()
        return master_task.result()
    finally:
        watchdog.cancel()
        pool.close()


async def _drive_master(plan: ExperimentPlan, pool: ZMQWorkerPool):
    await pool.wait_workers()
    with tracer.setup_span("build"), tracer.setup_span("master"):
        master = _make_master(plan, pool)
        # Resume step counters / freq-ctl state from a recover checkpoint
        # if one exists (written every ckpt_freq; no-op on fresh trials).
        master.load_recover_info()
    stats = await master.run()
    await pool.broadcast({"type": "exit"})
    return stats


def run_experiment(
    plan: ExperimentPlan,
    recover_retries: int = 0,
    name_resolve_root: Optional[str] = None,
    scheduler_mode: str = "local",
    worker_env: Optional[Dict[str, str]] = None,
    scheduler_kwargs: Optional[Dict] = None,
):
    """Multi-process trial: spawn workers, run the master, wait, recover."""
    root = name_resolve_root or os.path.join(
        plan.fileroot, "name_resolve", plan.experiment_name, plan.trial_name
    )
    os.makedirs(root, exist_ok=True)
    os.environ["AREAL_NAME_RESOLVE"] = "file"
    os.environ["AREAL_NAME_RESOLVE_ROOT"] = root
    name_resolve.set_default(name_resolve.FileNameResolveRepository(root))

    plan_dir = os.path.join(
        plan.fileroot, "plans", plan.experiment_name, plan.trial_name
    )
    os.makedirs(plan_dir, exist_ok=True)
    for wc in plan.worker_configs:
        with open(
            os.path.join(plan_dir, f"worker_{wc.worker_index}.pkl"), "wb"
        ) as f:
            pickle.dump(wc, f)

    last_err = None
    for attempt in range(recover_retries + 1):
        # Workers must import areal_tpu regardless of the launcher's cwd
        # (the package is not pip-installed; reference relies on install).
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        pkg_root = os.path.dirname(pkg_root)  # dir containing areal_tpu/
        pythonpath = os.environ.get("PYTHONPATH", "")
        if pkg_root not in pythonpath.split(os.pathsep):
            pythonpath = (
                f"{pkg_root}{os.pathsep}{pythonpath}" if pythonpath
                else pkg_root
            )
        env = {
            "PYTHONPATH": pythonpath,
            "AREAL_NAME_RESOLVE": "file",
            "AREAL_NAME_RESOLVE_ROOT": root,
            # Liveness lane: workers beat this often so the master's MFC
            # deadline distinguishes slow (alive, still beating) from
            # dead (no beats past the grace window).
            "AREAL_WORKER_HEARTBEAT_S": str(plan.worker_heartbeat_s),
        }
        # Trace shards from every process must land in ONE dir; the
        # explicit env dict ships it to schedulers that don't inherit
        # our environ (the master configures itself in MasterWorker).
        trace_dir = tracer.default_dir(
            plan.fileroot, plan.experiment_name, plan.trial_name
        )
        if trace_dir:
            env["AREAL_TRACE"] = os.environ.get("AREAL_TRACE", "1")
            env["AREAL_TRACE_DIR"] = trace_dir
        env.update(worker_env or {})
        sched = make_scheduler(
            scheduler_mode,
            plan.experiment_name,
            plan.trial_name,
            env=env,
            **(scheduler_kwargs or {}),
        )
        # Live metrics plane for the master (which runs in THIS process):
        # serve the default registry and announce the URL so
        # apps/metrics_report.py finds the trainer role next to the
        # workers' own servers (apps/worker.py announces those).
        metrics_server = metrics.MetricsServer(
            announce=(plan.experiment_name, plan.trial_name, "master")
        )
        sched.submit_array(
            "model_worker",
            lambda i: [
                sys.executable, "-m", "areal_tpu.apps.worker",
                "--config", plan_dir, "--index", str(i),
                "--experiment", plan.experiment_name,
                "--trial", plan.trial_name,
            ],
            count=len(plan.worker_configs),
        )
        try:
            stats = asyncio.run(
                _run_master_zmq(plan, len(plan.worker_configs), sched)
            )
            sched.wait(timeout=60.0)
            return stats
        except (JobException, RuntimeError, TimeoutError) as e:
            last_err = e
            logger.error(f"trial attempt {attempt} failed: {e!r}")
            sched.stop_all()
            if attempt >= recover_retries:
                raise
            logger.info(f"recovering (attempt {attempt + 1})...")
        finally:
            metrics_server.close()
            sched.stop_all()
    raise last_err  # pragma: no cover

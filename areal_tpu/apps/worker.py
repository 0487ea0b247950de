"""Worker-process bootstrap: load a pickled WorkerConfig, serve the stream.

Capability parity: realhf/apps/remote.py (re-register experiment from cached
config, run the worker poll loop).  Launched by the scheduler as

    python -m areal_tpu.apps.worker --config <plan_dir> --index <i> \
        --experiment <name> --trial <name>

Discovery/config env: AREAL_NAME_RESOLVE(=file) + AREAL_NAME_RESOLVE_ROOT
must point at the trial's shared store (set by apps/main.py).
"""

import argparse
import os
import pickle


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True, help="plan directory")
    p.add_argument("--index", type=int, required=True)
    p.add_argument("--experiment", required=True)
    p.add_argument("--trial", required=True)
    args = p.parse_args()

    from areal_tpu.base import (
        compilation_cache,
        logging,
        metrics,
        seeding,
        tracer,
    )

    compilation_cache.enable()
    # Shard name: trace_worker_<index>.jsonl (dir comes from
    # AREAL_TRACE_DIR, exported by the launcher when tracing is on).
    tracer.configure(role="worker", rank=args.index)
    # Live metrics plane: every role exposes /metrics and announces the
    # URL under the trial's metrics subtree for apps/metrics_report.py.
    metrics_server = metrics.MetricsServer(
        announce=(args.experiment, args.trial, f"model_worker/{args.index}")
    )
    from areal_tpu.system.stream import run_worker_stream
    from areal_tpu.system.transfer import ZMQTransfer
    from areal_tpu.system.worker import ModelWorker

    logger = logging.getLogger(f"worker{args.index}")
    with open(
        os.path.join(args.config, f"worker_{args.index}.pkl"), "rb"
    ) as f:
        config = pickle.load(f)
    seeding.set_random_seed(config.seed, config.worker_index)
    if config.dist_num_processes > 1:
        from areal_tpu.base import distributed

        distributed.initialize(
            args.experiment,
            args.trial,
            process_id=config.dist_process_id,
            num_processes=config.dist_num_processes,
        )
    # Lifecycle side channel (ping/pause/resume/exit + TTL keepalive) —
    # reference: worker_base.py WorkerServer, bound before the model build
    # so the controller can see the worker during its (slow) setup.
    from areal_tpu.system.worker_control import WorkerServer, WorkerState

    control = WorkerServer(
        args.experiment, args.trial, f"model_worker/{args.index}"
    )
    # Bulk worker-to-worker plane (data/param transfers planned by the
    # master); bound before model build so peers can connect early.
    transfer = ZMQTransfer(args.experiment, args.trial, args.index)
    with tracer.setup_span("build"):
        worker = ModelWorker(config, transfer=transfer)
    control.state = WorkerState.RUNNING
    logger.info(f"worker {args.index} ready, serving stream")
    try:
        run_worker_stream(
            worker, args.experiment, args.trial, control=control
        )
    finally:
        tracer.flush()
        metrics_server.close()
        transfer.close()
        control.stop()
    logger.info(f"worker {args.index} exiting")


if __name__ == "__main__":
    main()

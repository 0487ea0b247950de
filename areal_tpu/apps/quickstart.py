"""Quickstart CLI: `python -m areal_tpu.apps.quickstart <exp> [options]`.

Capability parity: realhf/apps/quickstart.py (hydra CLI over registered
experiment configs) — argparse-based (the config tree is small dataclasses;
a YAML file via --config covers the reference's prologue path).

Experiments:
    sft       — supervised fine-tuning (experiments/common.py build_sft)
    ppo-math  — PPO/GRPO with math-verified rewards (build_ppo_math)

Examples:
    python -m areal_tpu.apps.quickstart sft \
        --model.path /ckpts/qwen2-1.5b --dataset.path data.jsonl \
        --allocation d1f4m2 --batch-size 32 --epochs 1
    python -m areal_tpu.apps.quickstart ppo-math \
        --model.path /ckpts/qwen2-1.5b --dataset.path prompts.jsonl \
        --group-size 8 --workers 1
"""

import argparse
import json
import os
from typing import Optional

from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
from areal_tpu.api.model_api import GenerationHyperparameters, OptimizerConfig
from areal_tpu.base import logging
from areal_tpu.base.topology import ParallelConfig
from areal_tpu.experiments import common as exps
from areal_tpu.system.master import ExperimentSaveEvalControl

logger = logging.getLogger("quickstart")


def _eval_protocol_arg(value: str) -> str:
    """Reject a malformed protocol at PARSE time — a typo must not
    surface as a crash only after the multi-hour trial finishes."""
    from areal_tpu.scheduler.evaluator import parse_protocol

    try:
        parse_protocol(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None
    return value


def _add_common(p: argparse.ArgumentParser):
    p.add_argument("--config", default=None,
                   help="YAML file of option defaults (keys = flag names, "
                        "e.g. 'model.path:'); CLI flags override it — the "
                        "reference's prologue path (realhf/apps/main.py "
                        "--config)")
    p.add_argument("--model.path", dest="model_path", required=True,
                   help="HF checkpoint dir")
    p.add_argument("--dataset.path", dest="dataset_path", required=True,
                   help="jsonl dataset path")
    p.add_argument("--allocation", default="d1",
                   help="parallel layout, e.g. d2f2m2 / p2f2m2 / d1s4; "
                        "'search' runs the MCMC allocation search (ppo-math)")
    p.add_argument("--chip", default="v5e",
                   help="TPU chip spec for the allocation search (v5e/v5p)")
    p.add_argument("--search-devices", type=int, default=None,
                   help="chip count for --allocation search (required with "
                        "--multiprocess so the launcher never touches the "
                        "TPU runtime)")
    p.add_argument("--tokenizer-path", default=None,
                   help="tokenizer dir (default: model path); 'char:<n>' "
                        "loads the hermetic char tokenizer")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--max-tokens-per-mb", type=int, default=16384)
    p.add_argument("--lr", type=float, default=2e-5)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--experiment-name", default=None)
    p.add_argument("--trial-name", default="trial0")
    p.add_argument("--fileroot", default="/tmp/areal_tpu")
    p.add_argument("--save-freq-steps", type=int, default=None)
    p.add_argument("--ckpt-freq-steps", type=int, default=None)
    p.add_argument("--benchmark-steps", type=int, default=None)
    p.add_argument("--launcher", default="local",
                   choices=("local", "slurm", "tpu-pod"),
                   help="where workers run: this host (local), sbatch jobs "
                        "(slurm), or one process per TPU-VM host via "
                        "gcloud ssh (tpu-pod; needs a shared --fileroot, "
                        "e.g. GCS fuse)")
    p.add_argument("--tpu-name", default=None,
                   help="tpu-pod: TPU VM / pod-slice name")
    p.add_argument("--tpu-zone", default=None)
    p.add_argument("--tpu-project", default=None)
    p.add_argument("--tpu-num-hosts", type=int, default=1,
                   help="tpu-pod: hosts in the slice (worker i runs on "
                        "host i %% num-hosts)")
    p.add_argument("--multiprocess", action="store_true",
                   help="spawn workers as subprocesses over ZMQ (default: "
                        "in-process)")
    p.add_argument("--recover-retries", type=int, default=0)
    p.add_argument("--mfc-timeout-s", type=float, default=None,
                   help="per-MFC deadline; a worker that misses it AND "
                        "stops heartbeating is declared dead and the "
                        "master rolls back to the recover checkpoint "
                        "(default: no deadline)")
    p.add_argument("--worker-heartbeat-s", type=float, default=5.0,
                   help="worker liveness beat period (ZMQ runtime); long "
                        "MFCs stay alive by beating, so --mfc-timeout-s "
                        "distinguishes slow from dead")
    p.add_argument("--max-recoveries", type=int, default=3,
                   help="worker deaths the master absorbs by restoring "
                        "the recover checkpoint before exiting non-zero")
    p.add_argument("--anomaly-grad-norm-mult", type=float, default=0.0,
                   help="quarantine a train step whose grad norm exceeds "
                        "this multiple of the engine's running EWMA "
                        "(must be > 1; 0 = sentinel off; non-finite "
                        "loss/grads always quarantine)")
    p.add_argument("--anomaly-update-norm-max", type=float, default=0.0,
                   help="quarantine a train step whose optimizer update "
                        "norm exceeds this absolute ceiling (0 = off)")
    p.add_argument("--max-consecutive-quarantines", type=int, default=3,
                   help="consecutive quarantined steps before the master "
                        "rolls the fleet back to the last recover "
                        "checkpoint (0 = never escalate)")
    p.add_argument("--no-weight-push-checksum", action="store_true",
                   help="skip the per-leaf-norm content checksum "
                        "receivers verify on cross-worker weight pushes")
    p.add_argument("--eval-data", default=None,
                   help="held-out jsonl; after the trial, every saved "
                        "checkpoint is graded (pass@1) by the automatic "
                        "evaluator")
    p.add_argument("--eval-max-new-tokens", type=int, default=256)
    p.add_argument("--eval-protocol", default="greedy",
                   type=_eval_protocol_arg,
                   help="'greedy', 'avg@K' (avg@32 = the AIME avg-of-32 "
                        "pass@1 protocol at temperature 1.0), or 'maj@K' "
                        "(majority voting over K samples)")


def _apply_yaml_config(parser: argparse.ArgumentParser, argv):
    """Pre-read --config <yaml> and install its values as parser defaults
    (CLI flags still win).  YAML keys use the flag spelling ('model.path',
    'batch-size') or the python dest ('model_path')."""
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--config", default=None)
    known, _ = pre.parse_known_args(argv)
    if not known.config:
        return
    import yaml

    with open(known.config) as f:
        raw = yaml.safe_load(f) or {}
    dests = {a.dest for a in parser._actions}
    mapped = {}
    for key, val in raw.items():
        dest = key.replace("-", "_")
        if dest not in dests:
            dest = key.replace(".", "_").replace("-", "_")
        if dest not in dests:
            raise SystemExit(f"--config: unknown option {key!r}")
        mapped[dest] = val
    parser.set_defaults(**mapped)
    # YAML-provided values satisfy required flags.
    for a in parser._actions:
        if a.dest in mapped and a.required:
            a.required = False


def _maybe_eval(args, plan):
    if not args.eval_data:
        return
    from areal_tpu.scheduler.evaluator import AutomaticEvaluator, EvalConfig

    exp, trial = plan.experiment_name, plan.trial_name
    for node in plan.dfg.nodes:
        from areal_tpu.api.config import ModelInterfaceType

        if node.interface_type != ModelInterfaceType.TRAIN_STEP:
            continue
        ckpt_root = os.path.join(
            args.fileroot, "checkpoints", exp, trial, str(node.model_name)
        )
        if not os.path.isdir(ckpt_root):
            continue
        ev = AutomaticEvaluator(
            ckpt_root,
            os.path.join(args.fileroot, "eval", exp, trial),
            EvalConfig(
                data_path=args.eval_data,
                tokenizer_path=args.tokenizer_path or args.model_path,
                max_new_tokens=args.eval_max_new_tokens,
                protocol=args.eval_protocol,
            ),
        )
        steps = ev.step()
        logger.info(f"evaluated checkpoints at steps {steps}")


def _ctrl(args) -> ExperimentSaveEvalControl:
    return ExperimentSaveEvalControl(
        total_train_epochs=args.epochs,
        save_freq_steps=args.save_freq_steps,
        ckpt_freq_steps=args.ckpt_freq_steps,
        benchmark_steps=args.benchmark_steps,
    )


def _run(plan, args):
    from areal_tpu.apps import main as runner

    if args.multiprocess or args.launcher != "local":
        # This launcher must stay off the JAX backend: a chip belongs to
        # one process, and the workers are the ones that need it.
        kwargs = {}
        if args.launcher == "tpu-pod":
            if not args.tpu_name:
                raise SystemExit("--launcher tpu-pod needs --tpu-name")
            kwargs = dict(
                tpu_name=args.tpu_name,
                zone=args.tpu_zone,
                project=args.tpu_project,
                num_hosts=args.tpu_num_hosts,
            )
        return runner.run_experiment(
            plan,
            recover_retries=args.recover_retries,
            scheduler_mode=args.launcher,
            scheduler_kwargs=kwargs,
        )
    # Deferred here so `--help`/arg errors never pay the jax import.
    from areal_tpu.base import compilation_cache

    compilation_cache.enable()
    return runner.run_experiment_inproc(plan)


def cmd_sft(args):
    cfg = exps.SFTConfig(
        model=ModelAbstraction("hf", {"path": args.model_path}),
        dataset=DatasetAbstraction(
            "prompt_answer", {"dataset_path": args.dataset_path,
                              "max_length": args.max_seqlen}
        ),
        parallel=ParallelConfig.from_str(args.allocation),
        optimizer=OptimizerConfig(lr=args.lr),
        batch_size=args.batch_size,
        total_train_epochs=args.epochs,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=args.max_tokens_per_mb),
        ctrl=_ctrl(args),
        seed=args.seed,
        experiment_name=args.experiment_name or "sft",
        trial_name=args.trial_name,
        fileroot=args.fileroot,
        mfc_timeout_s=args.mfc_timeout_s,
        worker_heartbeat_s=args.worker_heartbeat_s,
        max_recoveries=args.max_recoveries,
        anomaly_grad_norm_mult=args.anomaly_grad_norm_mult,
        anomaly_update_norm_max=args.anomaly_update_norm_max,
        max_consecutive_quarantines=args.max_consecutive_quarantines,
        weight_push_checksum=not args.no_weight_push_checksum,
    )
    plan = exps.build_sft(cfg)
    for wc in plan.worker_configs:
        wc.tokenizer_path = args.tokenizer_path or args.model_path
    stats = _run(plan, args)
    _maybe_eval(args, plan)
    print(json.dumps(stats[-1] if stats else {}))


def _searched_ppo_allocation(args):
    """`--allocation search`: pick (mesh, layout) per MFC with the C++ MCMC
    search over the TPU roofline estimator (reference: apps/main.py:104-107
    driving search_rpc_allocations)."""
    import jax

    from areal_tpu.models.hf import registry as hf
    from areal_tpu.search_engine.search import search_ppo_math_allocations

    if args.multiprocess and not args.search_devices:
        # jax.device_count() would initialize the TPU runtime in THIS
        # launcher process, stealing the chips from the spawned workers.
        raise SystemExit(
            "--allocation search with --multiprocess needs an explicit "
            "--search-devices N (the launcher must not initialize the TPU "
            "runtime itself)"
        )
    n_devices = args.search_devices or jax.device_count()
    hf_cfg = hf.load_hf_config(args.model_path)
    model_cfg = hf.HF_FAMILIES[hf_cfg["model_type"]].config_from_hf(hf_cfg)
    allocs = search_ppo_math_allocations(
        model_cfg,
        n_prompts=args.batch_size,
        group_size=args.group_size,
        max_new_tokens=args.max_new_tokens,
        n_devices=n_devices,
        chip=args.chip,
        max_tokens_per_mb=args.max_tokens_per_mb,
        seed=args.seed,
    )
    train = allocs["actor_train"]
    gen = allocs["actor_gen"]
    logger.info(
        f"searched allocation: train {train.parallel.to_str()} on chips "
        f"{train.device_range}, gen {gen.parallel.to_str()} on chips "
        f"{gen.device_range}"
    )
    return train, gen


def _parse_mixture_weights(specs):
    """'task=weight' CLI pairs -> {task: float} for PPOMathConfig."""
    weights = {}
    for spec in specs:
        task, sep, w = spec.partition("=")
        if not sep or not task:
            raise SystemExit(
                f"--mixture-weight wants TASK=WEIGHT, got {spec!r}"
            )
        try:
            weights[task] = float(w)
        except ValueError:
            raise SystemExit(
                f"--mixture-weight {spec!r}: weight must be a number"
            )
    return weights


def cmd_ppo_math(args):
    searched = None
    if args.allocation == "search":
        if args.gen_allocation:
            raise SystemExit(
                "--gen-allocation conflicts with --allocation search "
                "(the search chooses the generation layout)"
            )
        searched = _searched_ppo_allocation(args)
    ppo_kwargs = {}
    if args.kl_ctl:
        if not args.ref_path:
            raise SystemExit(
                "--kl-ctl needs --ref-path: the KL penalty is computed "
                "against a reference policy's logprobs"
            )
        ppo_kwargs["kl_ctl"] = args.kl_ctl
    if args.kl_adaptive:
        if not args.kl_ctl:
            # The controller is multiplicative: a 0.0 start can never
            # leave 0, so silently "enabling" it would do nothing.
            raise SystemExit(
                "--kl-adaptive needs a nonzero --kl-ctl as the initial "
                "coefficient"
            )
        ppo_kwargs["kl_adaptive"] = True
        ppo_kwargs["adaptive_kl_target"] = args.adaptive_kl_target
        ppo_kwargs["adaptive_kl_horizon"] = args.adaptive_kl_horizon
    if args.generation_size is not None:
        ppo_kwargs["generation_size"] = args.generation_size
    if args.early_stop_imp_ratio is not None:
        ppo_kwargs["early_stop_imp_ratio"] = args.early_stop_imp_ratio
    if args.early_stop_kl is not None:
        ppo_kwargs["early_stop_kl"] = args.early_stop_kl
    cfg = exps.PPOMathConfig(
        actor=ModelAbstraction("hf", {"path": args.model_path}),
        ref=(
            ModelAbstraction("hf", {"path": args.ref_path})
            if args.ref_path else None
        ),
        ppo_kwargs=ppo_kwargs,
        ref_ema_eta=args.ref_ema_eta,
        fuse_rew_ref=args.fuse_rew_ref,
        offload_ref=args.offload_ref,
        gen_server_url=args.gen_server_url,
        rollout_ahead=args.rollout_ahead,
        max_head_offpolicyness=args.max_head_offpolicyness,
        replay_capacity=args.replay_capacity,
        pipeline_overlap=args.pipeline_overlap,
        overlap_window=args.overlap_window,
        pipeline_chunk_seqs=args.pipeline_chunk_seqs,
        inmem_weight_sync=args.inmem_weight_sync,
        param_push_tree=args.param_push_tree,
        param_push_fanout=args.param_push_fanout,
        gen_backend_args=(
            {"kv_cache_dtype": args.kv_cache_dtype}
            if args.kv_cache_dtype != "auto" else {}
        ),
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        kv_share_prefix=not args.no_kv_share_prefix,
        train_backend_args={
            k: v
            for k, v in (
                ("master_dtype", args.master_dtype),
                ("remat_policy", args.remat),
            )
            if v is not None
        },
        dataset=DatasetAbstraction(
            "math_code_prompt", {"dataset_path": args.dataset_path}
        ),
        actor_parallel=(
            searched[0].parallel
            if searched
            else ParallelConfig.from_str(args.allocation)
        ),
        gen_parallel=(
            searched[1].parallel
            if searched
            else ParallelConfig.from_str(args.gen_allocation)
            if args.gen_allocation
            else None
        ),
        actor_device_offset=searched[0].device_range[0] if searched else None,
        gen_device_offset=searched[1].device_range[0] if searched else None,
        optimizer=OptimizerConfig(lr=args.lr),
        gconfig=GenerationHyperparameters(
            n=args.group_size,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            spec_decode_k=args.spec_decode_k,
        ),
        batch_size=args.batch_size,
        total_train_epochs=args.epochs,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=args.max_tokens_per_mb),
        ctrl=_ctrl(args),
        seed=args.seed,
        experiment_name=args.experiment_name or "ppo-math",
        trial_name=args.trial_name,
        fileroot=args.fileroot,
        mfc_timeout_s=args.mfc_timeout_s,
        worker_heartbeat_s=args.worker_heartbeat_s,
        max_recoveries=args.max_recoveries,
        anomaly_grad_norm_mult=args.anomaly_grad_norm_mult,
        anomaly_update_norm_max=args.anomaly_update_norm_max,
        anomaly_kl_max=args.anomaly_kl_max,
        max_consecutive_quarantines=args.max_consecutive_quarantines,
        weight_push_checksum=not args.no_weight_push_checksum,
        episode_max_turns=args.episode_max_turns,
        episode_token_budget=args.episode_token_budget,
        tool_timeout_s=args.tool_timeout_s,
        reward_backend=args.reward_backend,
        verifier_pool=args.verifier_pool,
        mixture_weights=_parse_mixture_weights(args.mixture_weight),
        mixture_adaptive=args.mixture_adaptive,
    )
    plan = exps.build_ppo_math(cfg)
    for wc in plan.worker_configs:
        wc.tokenizer_path = args.tokenizer_path or args.model_path
    stats = _run(plan, args)
    _maybe_eval(args, plan)
    print(json.dumps(stats[-1] if stats else {}))


def main(argv=None):
    p = argparse.ArgumentParser(prog="areal_tpu.apps.quickstart")
    sub = p.add_subparsers(dest="exp", required=True)

    ps = sub.add_parser("sft", help="supervised fine-tuning")
    _add_common(ps)
    ps.add_argument("--max-seqlen", type=int, default=4096)
    ps.set_defaults(fn=cmd_sft)

    pp = sub.add_parser("ppo-math", help="PPO/GRPO with verified rewards")
    _add_common(pp)
    pp.add_argument("--group-size", type=int, default=4)
    pp.add_argument("--max-new-tokens", type=int, default=1024)
    pp.add_argument("--temperature", type=float, default=1.0)
    pp.add_argument("--gen-allocation", default=None,
                    help="separate layout for generation (decoupled meshes)")
    pp.add_argument("--gen-server-url", default=None,
                    help="decoupled serving: URL(s) of running "
                         "areal_tpu.system.gen_server instances, comma-"
                         "separated for one server per DP rank (actor_gen "
                         "becomes a weightless client; weight sync ships "
                         "checkpoints to every rank)")
    pp.add_argument("--ref-path", default=None,
                    help="reference policy checkpoint (enables KL control)")
    pp.add_argument("--kl-ctl", type=float, default=0.0)
    pp.add_argument("--kl-adaptive", action="store_true",
                    help="adapt the KL coefficient to hold the measured "
                         "policy-ref KL at --adaptive-kl-target "
                         "(Ziegler controller; --kl-ctl is the initial "
                         "value)")
    pp.add_argument("--adaptive-kl-target", type=float, default=6.0)
    pp.add_argument("--adaptive-kl-horizon", type=float, default=10000.0)
    pp.add_argument("--generation-size", type=int, default=None,
                    help="best-of-k: sample this many responses per prompt "
                         "but train on only the top --group-size by reward")
    pp.add_argument("--early-stop-imp-ratio", type=float, default=None,
                    help="skip remaining minibatches of a step once the "
                         "mean importance ratio exceeds this (e.g. 10.0)")
    pp.add_argument("--early-stop-kl", type=float, default=None,
                    help="skip remaining minibatches once |approx_kl| "
                         "exceeds this (e.g. 0.1)")
    pp.add_argument("--ref-ema-eta", type=float, default=None,
                    help="EMA-update the ref toward the actor each step")
    pp.add_argument("--kv-cache-dtype", default="auto",
                    choices=("auto", "int8"),
                    help="int8 halves KV HBM per generated token (the "
                         "capacity bound for 16k+ decodes)")
    pp.add_argument("--kv-page-size", type=int, default=128,
                    help="tokens per KV page in the serving plane's pool")
    pp.add_argument("--kv-pool-pages", type=int, default=0,
                    help="fixed KV pool size in pages (0 = auto-size "
                         "for the worst case; positive caps KV HBM and "
                         "bounds concurrent admissions)")
    pp.add_argument("--prefill-chunk-tokens", type=int, default=8,
                    help="serving plane: prompt tokens forwarded per "
                         "decode step inside the serving chunk (>= 1)")
    pp.add_argument("--no-kv-share-prefix", action="store_true",
                    help="disable copy-on-write prompt page sharing "
                         "across a sampling group (parity/debug)")
    pp.add_argument("--master-dtype", default=None,
                    choices=(None, "float32", "bfloat16"),
                    help="optimizer master/Adam dtype; bfloat16 halves "
                         "optimizer memory (the single-chip 1.5B fit)")
    pp.add_argument("--remat", default=None,
                    choices=(None, "full", "dots_small", "dots", "none"),
                    help="activation rematerialization policy for training")
    pp.add_argument("--fuse-rew-ref", action="store_true",
                    help="one fused MFC for reward grading + ref inference")
    pp.add_argument("--offload-ref", action="store_true",
                    help="host-offload ref params between steps")
    pp.add_argument("--spec-decode-k", type=int, default=0,
                    help="speculative decoding drafts per step (0 = off)")
    pp.add_argument("--rollout-ahead", type=int, default=0, choices=(0, 1),
                    help="1 = generate step t+1's rollouts while step t "
                         "trains (one-step-stale async rollout)")
    pp.add_argument("--max-head-offpolicyness", type=int, default=None,
                    help="enable the async-RL replay pipeline: keep up to "
                         "N+1 rollout batches in flight and train only on "
                         "batches whose head weight version lags the "
                         "trainer by <= N (0 = bounded pipeline that "
                         "degrades to synchronous numerics; mutually "
                         "exclusive with --rollout-ahead)")
    pp.add_argument("--replay-capacity", type=int, default=4,
                    help="async RL: max resident rollout batches in the "
                         "replay buffer (puts at capacity evict oldest)")
    pp.add_argument("--inmem-weight-sync", action="store_true",
                    help="decoupled serving: pause/resume generation "
                         "around weight pushes (in-flight decodes halt at "
                         "a chunk boundary and resume on their KV pages) "
                         "instead of draining the server")
    pp.add_argument("--param-push-tree", action="store_true",
                    help="decoupled serving: distribute weight pushes "
                         "down a broadcast tree over the gen-server "
                         "fleet (serialize once, servers relay to their "
                         "children before applying; O(log N) push "
                         "wall-time) instead of N serial point-to-point "
                         "pushes; requires --gen-server-url")
    pp.add_argument("--param-push-fanout", type=int, default=2,
                    help="broadcast-tree fan-out per relay server "
                         "(with --param-push-tree; depth ~ "
                         "log_fanout(N))")
    pp.add_argument("--pipeline-overlap", action="store_true",
                    help="overlap the stages INSIDE a step: slice the "
                         "batch into rollout-group chunks and stream each "
                         "through gen -> ref/reward -> train "
                         "forward-backward while later chunks still "
                         "decode; one optimizer step per global step "
                         "(mutually exclusive with --rollout-ahead and "
                         "--max-head-offpolicyness)")
    pp.add_argument("--overlap-window", type=int, default=2,
                    help="pipeline overlap: max chunks in flight at once "
                         "(1 = serial dispatch, bit-exact vs the barrier "
                         "scheduler)")
    pp.add_argument("--pipeline-chunk-seqs", type=int, default=1,
                    help="pipeline overlap: rollout groups per chunk")
    pp.add_argument("--anomaly-kl-max", type=float, default=None,
                    help="quarantine a batch whose mean |policy-ref KL| "
                         "exceeds this before it ever reaches the train "
                         "engine (needs --ref-path; omit to disable)")
    pp.add_argument("--episode-max-turns", type=int, default=0,
                    help="agent-serving runtime: >0 turns rollout into "
                         "multi-turn tool-use episodes parked on "
                         "persistent KV slots (0 = single-shot)")
    pp.add_argument("--episode-token-budget", type=int, default=0,
                    help="agent episodes: total transcript token cap per "
                         "episode (0 = engine default)")
    pp.add_argument("--tool-timeout-s", type=float, default=10.0,
                    help="agent episodes: wall-clock bound on each tool "
                         "call before it degrades to an error observation")
    pp.add_argument("--reward-backend", default="",
                    help="force one reward-fabric verifier backend (math, "
                         "code, judge, or a registered name) for every "
                         "sample instead of routing by per-row task")
    pp.add_argument("--verifier-pool", action="store_true",
                    help="route grading through the trial's announced "
                         "verifier-worker fleet (areal_tpu.apps.verifier) "
                         "instead of grading in-process")
    pp.add_argument("--mixture-weight", action="append", default=[],
                    metavar="TASK=WEIGHT",
                    help="task-mixture curriculum weight, e.g. "
                         "'math=3' 'code=1'; repeatable")
    pp.add_argument("--mixture-adaptive", action="store_true",
                    help="adaptively upweight tasks whose reward EMA is "
                         "below their watermark")
    pp.set_defaults(fn=cmd_ppo_math)

    # Install YAML defaults on whichever subcommand was chosen.
    import sys as _sys

    raw_argv = list(argv if argv is not None else _sys.argv[1:])
    if raw_argv and raw_argv[0] in ("sft", "ppo-math"):
        sub_parser = {"sft": ps, "ppo-math": pp}[raw_argv[0]]
        _apply_yaml_config(sub_parser, raw_argv[1:])
    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()

"""Experiment builders: user config -> (DFG, workers, placement).

Capability parity: realhf/experiments/common/ — `CommonExperimentConfig`
(allocation parsing, worker-config mapping), `sft_exp.py`, `ppo_math_exp.py`
(the north-star PPO dataflow with generation, reward, ref, critic and the
param-sync hooks wired automatically, reference utils.py resolve_rpc_hooks).
"""

import dataclasses
from typing import Any, Dict, List, Optional

from areal_tpu.api.config import (
    ModelAbstraction,
    ModelBackendAbstraction,
    ModelInterfaceAbstraction,
    ModelInterfaceType,
    ModelName,
)
from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
from areal_tpu.api.dfg import (
    DFG,
    MFCDef,
    OffloadHook,
    ParamReallocHook,
    build_graph,
)
from areal_tpu.api.model_api import FinetuneSpec, GenerationHyperparameters, OptimizerConfig
from areal_tpu.base.topology import ParallelConfig
from areal_tpu.system.master import ExperimentSaveEvalControl
from areal_tpu.system.worker import ModelShardSpec, WorkerConfig

# Ensure built-in interfaces are registered.
import areal_tpu.interfaces.sft  # noqa: F401
import areal_tpu.interfaces.ppo  # noqa: F401
import areal_tpu.interfaces.reward  # noqa: F401


@dataclasses.dataclass
class ExperimentPlan:
    """Everything the runtime needs to execute a trial."""

    dfg: DFG
    worker_configs: List[WorkerConfig]
    model_placement: Dict[str, int]
    data_worker_ids: List[int]
    ctrl: ExperimentSaveEvalControl
    experiment_name: str = "exp"
    trial_name: str = "trial"
    fileroot: str = "/tmp/areal_tpu/trial"
    # model key -> all worker ids forming its (multi-host) mesh; models
    # absent run on their single placement worker.  group[0] == placement.
    model_groups: Optional[Dict[str, List[int]]] = None
    # model key -> worker ids each holding an independent replica (DP
    # dispatch: generate/inference batches are token-balance-split).
    model_replicas: Optional[Dict[str, List[int]]] = None
    # {"min_accuracy": .., "max_accuracy": ..} -> dynamic difficulty
    # filtering of prompts by per-step group accuracy.
    difficulty_filter: Optional[Dict[str, float]] = None
    # Asynchronous rollout: generate step t+1's rollouts while step t
    # trains (one-step-stale behavior policy; see master._execute_step_async).
    rollout_ahead: int = 0
    # Asynchronous RL (staleness-bounded pipeline, replay-buffer-driven;
    # see master._execute_step_async_rl).  None = off.
    max_head_offpolicyness: Optional[int] = None
    replay_capacity: int = 4
    buffer_max_age_steps: Optional[int] = None
    # Pipeline-overlapped PPO: stream the step's batch through the graph
    # in rollout chunks (see master._execute_step_streamed).  window=1 is
    # the bit-exact overlap-off degenerate form.
    pipeline_overlap: bool = False
    overlap_window: int = 2
    pipeline_chunk_seqs: int = 1
    # Crash-safe trainer plane: per-MFC deadline (None = no deadline) and
    # worker heartbeat period (ZMQ runtime; beats keep long MFCs alive so
    # the deadline distinguishes slow from dead).  max_recoveries bounds
    # how many worker deaths the master absorbs by rolling back to the
    # recover checkpoint before exiting non-zero.
    mfc_timeout_s: Optional[float] = None
    worker_heartbeat_s: float = 5.0
    max_recoveries: int = 3
    # Numerical-integrity guard plane (see system/master.py): quarantine
    # streak length that escalates to a checkpoint rollback (0 = count
    # only), and content checksums on cross-set weight pushes.
    max_consecutive_quarantines: int = 3
    weight_push_checksum: bool = True


@dataclasses.dataclass
class SFTConfig:
    model: ModelAbstraction
    dataset: DatasetAbstraction
    # >1 = lay the model's mesh across this many worker PROCESSES (hosts):
    # each joins the jax.distributed world and `parallel` describes the
    # GLOBAL mesh over all their devices.  Requires the ZMQ runtime.
    n_hosts: int = 1
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    batch_size: int = 8
    total_train_epochs: int = 1
    mb_spec: MicroBatchSpec = dataclasses.field(default_factory=MicroBatchSpec)
    ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    seed: int = 1
    experiment_name: str = "sft"
    trial_name: str = "trial"
    fileroot: str = "/tmp/areal_tpu/trial"
    # Crash-safe trainer plane knobs (see ExperimentPlan).
    mfc_timeout_s: Optional[float] = None
    worker_heartbeat_s: float = 5.0
    max_recoveries: int = 3
    # Numerical-integrity guard plane: grad-norm-spike multiplier vs the
    # engine's running EWMA (0 = sentinel off; must be > 1 when set),
    # absolute update-norm ceiling (0 = off), quarantine-streak rollback
    # threshold, and checksummed weight pushes (see ExperimentPlan).
    anomaly_grad_norm_mult: float = 0.0
    anomaly_update_norm_max: float = 0.0
    max_consecutive_quarantines: int = 3
    weight_push_checksum: bool = True


def build_sft(cfg: SFTConfig, tokenizer=None) -> ExperimentPlan:
    from areal_tpu.experiments.check import check_sft

    check_sft(cfg)
    model_name = ModelName("default", 0)
    node = MFCDef(
        name="trainDefault",
        model_name=model_name,
        interface_type=ModelInterfaceType.TRAIN_STEP,
        interface_impl=ModelInterfaceAbstraction("sft"),
        input_keys=("packed_input_ids", "prompt_mask"),
        # Tokens feed the device only; prompt_mask stays broadcast (its
        # host-side counts set the global loss weight).
        shard_keys=("packed_input_ids",),
        n_seqs=cfg.batch_size,
        mb_spec=cfg.mb_spec,
    )
    dfg = build_graph([node])
    shard = ModelShardSpec(
        name=model_name,
        model=cfg.model,
        backend=ModelBackendAbstraction(
            "train", _anomaly_backend_args(cfg)
        ),
        interface=ModelInterfaceAbstraction("sft"),
        parallel=cfg.parallel,
        optimizer=cfg.optimizer,
    )
    ftspec = FinetuneSpec(
        total_train_epochs=cfg.total_train_epochs,
        train_batch_size=cfg.batch_size,
    )
    worker_configs = [
        WorkerConfig(
            worker_index=w,
            shards=[shard],
            datasets=[cfg.dataset] if w == 0 else [],
            batch_size=cfg.batch_size,
            seed=cfg.seed,
            ftspec=ftspec,
            dist_process_id=w,
            dist_num_processes=cfg.n_hosts,
        )
        for w in range(cfg.n_hosts)
    ]
    cfg.ctrl.total_train_epochs = cfg.total_train_epochs
    return ExperimentPlan(
        dfg=dfg,
        worker_configs=worker_configs,
        model_placement={str(model_name): 0},
        data_worker_ids=[0],
        ctrl=cfg.ctrl,
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        fileroot=cfg.fileroot,
        model_groups=(
            {str(model_name): list(range(cfg.n_hosts))}
            if cfg.n_hosts > 1
            else None
        ),
        mfc_timeout_s=cfg.mfc_timeout_s,
        worker_heartbeat_s=cfg.worker_heartbeat_s,
        max_recoveries=cfg.max_recoveries,
        max_consecutive_quarantines=cfg.max_consecutive_quarantines,
        weight_push_checksum=cfg.weight_push_checksum,
    )


def _anomaly_backend_args(cfg, base: Optional[Dict[str, Any]] = None):
    """Fold the config's engine-level anomaly knobs into a train-backend
    args dict (explicit train_backend_args entries win)."""
    args: Dict[str, Any] = dict(base or {})
    if cfg.anomaly_grad_norm_mult:
        args.setdefault(
            "anomaly_grad_norm_mult", cfg.anomaly_grad_norm_mult
        )
    if cfg.anomaly_update_norm_max:
        args.setdefault(
            "anomaly_update_norm_max", cfg.anomaly_update_norm_max
        )
    return args


@dataclasses.dataclass
class PPOMathConfig:
    actor: ModelAbstraction
    dataset: DatasetAbstraction
    # None -> GRPO (disable_value), matching the reference's disable_value.
    critic: Optional[ModelAbstraction] = None
    ref: Optional[ModelAbstraction] = None
    reward_interface_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Override the reward interface entirely (default: "rw-math-code" with
    # reward_interface_args).  A custom interface emitting per-token
    # "dense_rewards" pairs with ppo_kwargs={"use_dense_reward": True}.
    reward_interface: Optional[ModelInterfaceAbstraction] = None
    actor_parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    gen_parallel: Optional[ParallelConfig] = None  # None = same as actor
    # Device placement within the worker's local devices (None = worker
    # offset).  Set by `--allocation search` for disjoint gen/train meshes.
    actor_device_offset: Optional[int] = None
    gen_device_offset: Optional[int] = None
    critic_parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    # None = the actor's layout.  An independent ref layout makes every
    # MFC re-parallelizable on its own (the reference's "global reshard"
    # shape, tests/experiments/test_math_ppo.py:124-199).
    ref_parallel: Optional[ParallelConfig] = None
    # Extra kwargs for the critic interface (e.g. value_norm=True,
    # value_norm_type="exp" — reference ppo_interface.py:175-210).
    critic_interface_args: Dict[str, Any] = dataclasses.field(
        default_factory=dict
    )
    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=lambda: OptimizerConfig(lr=2e-5)
    )
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    ppo_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Remove prompts whose group accuracy falls outside this band after
    # each step (dynamic difficulty filtering; reference
    # model_worker.py:574-639).  e.g. {"min_accuracy": 0.05,
    # "max_accuracy": 0.95}.
    dataset_filter: Optional[Dict[str, float]] = None
    # Asynchronous rollout: overlap next-step generation with training
    # (one-step-stale behavior policy, PPO-ratio-corrected).
    rollout_ahead: int = 0
    # Asynchronous RL (AReaL-style, arxiv 2505.24298): keep
    # max_head_offpolicyness + 1 rollout batches in flight, admit them to
    # training through a staleness-bounded replay buffer, and correct the
    # off-policy gap with decoupled PPO (behav_imp_weight_cap is wired
    # into the actor interface automatically when the cap is > 0).
    # 0 = bounded pipeline that degrades to synchronous ordering.
    # None = async RL off.  Mutually exclusive with rollout_ahead.
    max_head_offpolicyness: Optional[int] = None
    # Replay capacity in batches for the async-RL pipeline.
    replay_capacity: int = 4
    # Pipeline-overlapped PPO (ROADMAP item 3; OPPO, arxiv 2509.25762):
    # stream the step's batch through gen -> ref/reward inference ->
    # train grad accumulation in chunks of `pipeline_chunk_seqs` prompts
    # with `overlap_window` chunks in flight, so post-generation stages
    # run while later chunks still decode and the optimizer step fires
    # once after the last chunk.  overlap_window=1 = overlap off: the
    # whole batch flows through the unchanged barrier node path
    # (bit-exact with pipeline_overlap=False).  Mutually exclusive with
    # rollout_ahead / max_head_offpolicyness; requires
    # donation_safe_swap on colocated generators (enforced in check.py).
    pipeline_overlap: bool = False
    overlap_window: int = 2
    pipeline_chunk_seqs: int = 1
    # Importance-weight cap for decoupled PPO; tokens whose behavior
    # weight exceeds it are masked out.  Only applied when
    # max_head_offpolicyness > 0 (at 0 the plain PPO loss keeps exact
    # synchronous numerics).  ppo_kwargs["behav_imp_weight_cap"] wins.
    behav_imp_weight_cap: float = 5.0
    # Interruptible weight sync for gen_server_url trials: pause the
    # servers at a chunk boundary around each weight push instead of
    # draining in-flight requests (GenerationServer pause/resume;
    # interrupted requests resume on their existing KV pages).  The
    # in-process path always hot-swaps in memory.
    inmem_weight_sync: bool = False
    # Broadcast-tree weight distribution (system/paramstore.py): when
    # True, set_params on the remote generator publishes ONE serialized
    # payload into a versioned ParamStore and pushes it down a fan-out
    # tree over the live fleet (each server relays to `param_push_fanout`
    # children before applying) instead of N serial point-to-point
    # pushes — O(log N) push wall-time at fleet scale.  Requires
    # gen_server_url (remote serving); the in-process path has no fleet
    # to fan out over.
    param_push_tree: bool = False
    param_push_fanout: int = 2
    # Extra GeneratorEngine kwargs (e.g. max_decode_batch, or forcing
    # donation_safe_swap — config check rejects the alias mode under
    # rollout_ahead>0).  Defaults supplied by build_ppo_math win unless
    # overridden here.
    gen_backend_args: Dict[str, Any] = dataclasses.field(
        default_factory=dict
    )
    # Serving-plane page pool (engines/generator.py): kv_pool_pages=0
    # auto-sizes the pool for the worst case; a positive value caps KV
    # HBM and makes admission wait for freed pages (gen_server splits
    # request groups against the resulting token budget).
    # gen_backend_args may still override both.
    kv_page_size: int = 128
    kv_pool_pages: int = 0
    # prefill_chunk_tokens (>= 1) is the slice width W in which an
    # admitted prompt is consumed inside the serving chunk (one compiled
    # program, no admission stall).  kv_share_prefix maps a group's
    # common prompt pages copy-on-write across rows.
    prefill_chunk_tokens: int = 8
    kv_share_prefix: bool = True
    # Extra TrainEngine kwargs for actor/critic (remat_policy,
    # master_dtype, pipe_schedule) — the single-chip 1.5B fit needs
    # master_dtype="bfloat16" here (as benchmark/run.py's PLAN sets it).
    train_backend_args: Dict[str, Any] = dataclasses.field(
        default_factory=dict
    )
    # Host-offload the reference model's params after each ref_inf call
    # (OffloadHook; frees its HBM between steps).
    offload_ref: bool = False
    # Run reward verification and ref-model inference as ONE fused MFC on
    # the ref worker (reference: FusedThreadingForwardInterface,
    # ppo_math_exp.py:132-136) — CPU reward grading overlaps the device
    # forward.  Requires a ref model.
    fuse_rew_ref: bool = False
    # EMA reference policy: after each actor train step, ref <-
    # eta*actor + (1-eta)*ref (reference: ppo_math_exp.py:345-364
    # ref_ema_eta option via ParamReallocHook).  None = frozen ref.
    ref_ema_eta: Optional[float] = None
    # Decoupled serving: URL of a standalone GenerationServer
    # (areal_tpu/system/gen_server.py).  actor_gen then uses the
    # remote_generator backend — this worker holds NO generation weights,
    # and the weight-sync hook ships checkpoints to the server (reference:
    # sglang decoupled allocations, backend/sglang.py).
    gen_server_url: Optional[str] = None
    # Model role -> worker index (e.g. {"actor_gen": 1} puts generation on a
    # second worker; the data/param planes move bytes between them) or a
    # LIST of worker indices (independent replicas: generate/inference
    # batches are token-balance-split across them — the reference's DP
    # dispatch).  Roles not listed run on worker 0.  Reference: device-mesh
    # allocations like `sglang.d64p1m1+d32p2m1` (api/cli_args.py).
    placement: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Per-worker first local device (in-process multi-worker trials carve
    # one host's device list into disjoint meshes).
    worker_device_offsets: Dict[int, int] = dataclasses.field(
        default_factory=dict
    )
    batch_size: int = 8  # prompts per step
    total_train_epochs: int = 1
    mb_spec: MicroBatchSpec = dataclasses.field(default_factory=MicroBatchSpec)
    ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    seed: int = 1
    experiment_name: str = "ppo-math"
    trial_name: str = "trial"
    fileroot: str = "/tmp/areal_tpu/trial"
    # Crash-safe trainer plane knobs (see ExperimentPlan).
    mfc_timeout_s: Optional[float] = None
    worker_heartbeat_s: float = 5.0
    max_recoveries: int = 3
    # Numerical-integrity guard plane: engine-level grad-spike multiplier
    # vs running EWMA and absolute update-norm ceiling (0 = off; folded
    # into train_backend_args, explicit entries win); batch-level KL
    # sentinel for the actor interface (None = off; ppo_kwargs wins);
    # quarantine-streak rollback threshold; checksummed weight pushes.
    anomaly_grad_norm_mult: float = 0.0
    anomaly_update_norm_max: float = 0.0
    anomaly_kl_max: Optional[float] = None
    max_consecutive_quarantines: int = 3
    weight_push_checksum: bool = True
    # Agent-serving runtime (system/episode.py): >0 max turns switches
    # rollout into multi-turn tool-use episodes parked on persistent KV
    # slots; token budget caps the whole transcript (0 = engine default);
    # tool_timeout_s bounds each ToolExecutor call; reward_backend forces
    # a verifier backend for every sample ("" = route by per-row task).
    episode_max_turns: int = 0
    episode_token_budget: int = 0
    tool_timeout_s: float = 10.0
    reward_backend: str = ""
    # Verifier service fleet (system/verifier_pool.py): route grading
    # through the trial's announced verifier workers — load-balanced with
    # per-server breakers and retry-to-a-different-server, degrading to
    # the in-process registry when no worker is live.  Precedence over a
    # fixed remote_url in reward_interface_args.
    verifier_pool: bool = False
    # Task-mixture curriculum (data/mixture.py): task -> weight for the
    # weighted multi-dataset prompt stream ({} = single prompt source).
    # Adaptive mode upweights tasks whose reward EMA sits below their
    # watermark (struggling tasks get more rollout budget).
    mixture_weights: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    mixture_adaptive: bool = False


def _remote_gen_shard(cfg: "PPOMathConfig", actor_gen, actor_if):
    """actor_gen as a weightless client of a GenerationServer."""
    model_type = "qwen2"
    if cfg.actor.type_ == "random":
        model_cfg = cfg.actor.args["config"]
        model_type = cfg.actor.args.get("model_type", model_type)
    elif cfg.actor.type_ == "hf":
        from areal_tpu.models.hf import registry as hf

        path = cfg.actor.args["path"]
        model_cfg = hf.load_model_config(path)
        # Weight-sync checkpoints must round-trip through the actor's OWN
        # HF family converter, not a default one.
        model_type = hf.load_hf_config(path)["model_type"]
    else:
        raise ValueError(
            f"gen_server_url with actor abstraction {cfg.actor.type_!r}"
        )
    return ModelShardSpec(
        name=actor_gen,
        model=ModelAbstraction("config", {"config": model_cfg}),
        backend=ModelBackendAbstraction(
            "remote_generator",
            {
                # Comma-separated = one GenerationServer per DP rank
                # (requests round-robin, weight updates broadcast).
                "url": [
                    u.strip()
                    for u in cfg.gen_server_url.split(",")
                    if u.strip()
                ],
                "model_type": model_type,
                "inmem_sync": cfg.inmem_weight_sync,
                "push_mode": (
                    "fabric" if cfg.param_push_tree else "disk"
                ),
                "push_fanout": cfg.param_push_fanout,
            },
        ),
        interface=actor_if,
        parallel=ParallelConfig(),
    )


def build_ppo_math(cfg: PPOMathConfig, tokenizer=None) -> ExperimentPlan:
    """The reference's ppo-math DFG (ppo_math_exp.py:335): generate ->
    {reward, ref, critic-inf} -> actor/critic train, with a weight-sync
    pre-hook on generation (train -> generator hot-swap)."""
    from areal_tpu.experiments.check import check_ppo_math

    check_ppo_math(cfg)
    disable_value = cfg.critic is None
    actor = ModelName("actor", 0)
    actor_gen = ModelName("actor_gen", 0)
    reward = ModelName("reward", 0)
    ref = ModelName("ref", 0) if cfg.ref is not None else None
    critic = ModelName("critic", 0) if not disable_value else None

    ppo_kwargs = dict(cfg.ppo_kwargs)
    ppo_kwargs.setdefault("disable_value", disable_value)
    if cfg.anomaly_kl_max is not None:
        ppo_kwargs.setdefault("anomaly_kl_max", cfg.anomaly_kl_max)
    train_backend_args = _anomaly_backend_args(
        cfg, cfg.train_backend_args
    )
    if (cfg.max_head_offpolicyness or 0) > 0:
        # Off-policy samples are admissible -> decoupled PPO corrects for
        # them.  At cap 0 the plain loss keeps exact synchronous numerics.
        ppo_kwargs.setdefault(
            "behav_imp_weight_cap", cfg.behav_imp_weight_cap
        )
    use_dense = bool(ppo_kwargs.get("use_dense_reward"))
    if use_dense and cfg.reward_interface is None:
        raise ValueError(
            "use_dense_reward needs a custom reward_interface that emits "
            "'dense_rewards' (the default rw-math-code grades scalars only)"
        )
    rew_args = dict(cfg.reward_interface_args)
    if cfg.reward_backend:
        rew_args.setdefault("reward_backend", cfg.reward_backend)
    if cfg.verifier_pool:
        rew_args.setdefault("verifier_pool", True)
        rew_args.setdefault("pool_experiment", cfg.experiment_name)
        rew_args.setdefault("pool_trial", cfg.trial_name)
    rew_if = cfg.reward_interface or ModelInterfaceAbstraction(
        "rw-math-code", rew_args
    )
    rew_outputs = (
        ("rewards", "dense_rewards") if use_dense else ("rewards",)
    )
    actor_if = ModelInterfaceAbstraction(
        "ppo_actor", {"gconfig": cfg.gconfig, **ppo_kwargs}
    )
    critic_if = ModelInterfaceAbstraction(
        "ppo_critic",
        {
            **{
                k: v for k, v in ppo_kwargs.items()
                if k in ("n_minibatches", "kl_ctl")
            },
            **cfg.critic_interface_args,
        },
    )
    nodes = [
        MFCDef(
            name="actor_gen",
            model_name=actor_gen,
            interface_type=ModelInterfaceType.GENERATE,
            interface_impl=actor_if,
            input_keys=("packed_prompts",),
            output_keys=(
                "packed_input_ids", "packed_logprobs", "prompt_mask",
                "seq_no_eos_mask",
            ),
            n_seqs=cfg.batch_size,
            mb_spec=cfg.mb_spec,
            pre_hooks=[],
        ),
    ]
    if cfg.fuse_rew_ref and ref is None:
        raise ValueError(
            "fuse_rew_ref=True requires a ref model (the fused MFC runs on "
            "the ref worker); set PPOMathConfig.ref or disable fusion"
        )
    fuse = cfg.fuse_rew_ref and ref is not None
    fused_if = ModelInterfaceAbstraction(
        "fused",
        {
            "interfaces": {
                "rew": {"type_": rew_if.type_, "args": rew_if.args},
                "ref": {"type_": "ppo_actor", "args": {}},
            }
        },
    )
    if fuse:
        # One MFC on the ref worker grades rewards (CPU process pool) while
        # the ref forward runs on device (reference: "fused-threading" MFC,
        # ppo_math_exp.py:132-136).
        nodes.append(
            MFCDef(
                name="fused_rew_ref",
                model_name=ref,
                interface_type=ModelInterfaceType.INFERENCE,
                interface_impl=fused_if,
                input_keys=("packed_input_ids", "prompt_mask"),
                output_keys=rew_outputs + ("packed_ref_logprobs",),
                output_key_remap={"logprobs": "packed_ref_logprobs"},
                n_seqs=cfg.batch_size,
                mb_spec=cfg.mb_spec,
                post_hooks=[OffloadHook()] if cfg.offload_ref else [],
            )
        )
    else:
        nodes.append(
            MFCDef(
                name="rew_inf",
                model_name=reward,
                interface_type=ModelInterfaceType.INFERENCE,
                interface_impl=rew_if,
                input_keys=("packed_input_ids", "prompt_mask"),
                output_keys=rew_outputs,
                n_seqs=cfg.batch_size,
                mb_spec=cfg.mb_spec,
            )
        )
    train_inputs = [
        "packed_input_ids", "prompt_mask", "packed_logprobs",
        "seq_no_eos_mask", "rewards",
    ]
    if use_dense:
        train_inputs.append("dense_rewards")
    if ref is not None:
        if not fuse:
            nodes.append(
                MFCDef(
                    name="ref_inf",
                    model_name=ref,
                    interface_type=ModelInterfaceType.INFERENCE,
                    interface_impl=ModelInterfaceAbstraction("ppo_actor"),
                    input_keys=("packed_input_ids",),
                    shard_keys=("packed_input_ids",),
                    output_keys=("packed_ref_logprobs",),
                    output_key_remap={"logprobs": "packed_ref_logprobs"},
                    n_seqs=cfg.batch_size,
                    mb_spec=cfg.mb_spec,
                    post_hooks=[OffloadHook()] if cfg.offload_ref else [],
                )
            )
        train_inputs.append("packed_ref_logprobs")
    if critic is not None:
        nodes.append(
            MFCDef(
                name="critic_inf",
                model_name=critic,
                interface_type=ModelInterfaceType.INFERENCE,
                interface_impl=critic_if,
                input_keys=("packed_input_ids", "prompt_mask"),
                shard_keys=("packed_input_ids",),
                output_keys=("values",),
                n_seqs=cfg.batch_size,
                mb_spec=cfg.mb_spec,
            )
        )
        train_inputs.append("values")
    # Sharded dispatch for the train steps: per-row math consumes only
    # the member's own (real) rows, and batch-GLOBAL statistics —
    # advantage moments, ref-KL (incl. the adaptive controller), the
    # critic's value-norm running moments — come from an exact in-mesh
    # reduction over the placed arrays (TrainEngine.masked_moments), so
    # every PPO configuration dispatches shard-exact.  prompt_mask stays
    # broadcast: sequence layout (loss masks, prompt lengths) must be
    # derivable by every member from global data.  (The reference
    # redistributes full batches instead, data_manager.py:144-416.)
    _heavy = (
        "packed_input_ids", "packed_logprobs", "packed_ref_logprobs",
        "values", "dense_rewards",
    )
    train_shard_keys = tuple(k for k in train_inputs if k in _heavy)
    train_post_hooks = [ParamReallocHook(target=actor_gen)]
    if cfg.ref_ema_eta is not None:
        if ref is None:
            raise ValueError("ref_ema_eta requires a ref model")
        train_post_hooks.append(
            ParamReallocHook(target=ref, eta=cfg.ref_ema_eta)
        )
        if cfg.offload_ref:
            # The EMA update reloads the ref onto device; push it back to
            # host so offload_ref keeps its HBM freed between steps.
            train_post_hooks.append(OffloadHook(target=ref))
    nodes.append(
        MFCDef(
            name="actor_train",
            model_name=actor,
            interface_type=ModelInterfaceType.TRAIN_STEP,
            interface_impl=actor_if,
            input_keys=tuple(train_inputs),
            shard_keys=train_shard_keys,
            n_seqs=cfg.batch_size,
            mb_spec=cfg.mb_spec,
            # After training, push fresh weights into the generator
            # (reference: param_realloc post-hook / update_weights_from_disk);
            # optionally EMA-update the reference policy.
            post_hooks=train_post_hooks,
        )
    )
    if critic is not None:
        nodes.append(
            MFCDef(
                name="critic_train",
                model_name=critic,
                interface_type=ModelInterfaceType.TRAIN_STEP,
                interface_impl=critic_if,
                input_keys=(
                    "packed_input_ids", "prompt_mask", "packed_logprobs",
                    "seq_no_eos_mask", "rewards", "values",
                ),
                shard_keys=(
                    "packed_input_ids", "packed_logprobs", "values",
                ),
                n_seqs=cfg.batch_size,
                mb_spec=cfg.mb_spec,
            )
        )
    dfg = build_graph(nodes)

    ftspec = FinetuneSpec(
        total_train_epochs=cfg.total_train_epochs,
        train_batch_size=cfg.batch_size,
    )
    shards = [
        ModelShardSpec(
            name=actor,
            model=cfg.actor,
            backend=ModelBackendAbstraction(
                "train", dict(train_backend_args)
            ),
            interface=actor_if,
            parallel=cfg.actor_parallel,
            optimizer=cfg.optimizer,
            device_offset=cfg.actor_device_offset,
        ),
        (
            _remote_gen_shard(cfg, actor_gen, actor_if)
            if cfg.gen_server_url
            else ModelShardSpec(
                name=actor_gen,
                model=cfg.actor,
                # Synchronous trials (rollout_ahead=0): generation never
                # overlaps the donating optimizer step, so the generator
                # may ALIAS the train master's buffers instead of copying
                # them (set_params' defensive copy is what the copy-vs-OOM
                # margin is for 1.5B on a 16 GB chip); the master releases
                # the alias before each aliased train step (see
                # MasterWorker._release_aliased_generators).  One-step-
                # ahead rollout decodes DURING training and must keep the
                # defensive copy.  Reference mechanism this replaces:
                # the weight-refresh dance in model_worker.py:1040-1067.
                backend=ModelBackendAbstraction(
                    "generator",
                    {
                        # Both async modes — and the within-step pipeline
                        # overlap, whose later chunks decode while earlier
                        # chunks accumulate grads — run generation
                        # concurrently with the donating optimizer step ->
                        # the generator MUST keep its defensive copy.
                        "donation_safe_swap": cfg.rollout_ahead > 0
                        or cfg.max_head_offpolicyness is not None
                        or cfg.pipeline_overlap,
                        "kv_page_size": cfg.kv_page_size,
                        "kv_pool_pages": cfg.kv_pool_pages,
                        "prefill_chunk_tokens": cfg.prefill_chunk_tokens,
                        "kv_share_prefix": cfg.kv_share_prefix,
                        **cfg.gen_backend_args,
                    },
                ),
                interface=actor_if,
                parallel=cfg.gen_parallel or cfg.actor_parallel,
                device_offset=cfg.gen_device_offset,
            )
        ),
    ]
    if not fuse:
        shards.append(
            ModelShardSpec(
                name=reward,
                model=ModelAbstraction("null"),
                backend=ModelBackendAbstraction("null"),
                interface=rew_if,
            )
        )
    if ref is not None:
        shards.append(
            ModelShardSpec(
                name=ref,
                model=cfg.ref,
                backend=ModelBackendAbstraction("inference"),
                interface=(
                    fused_if if fuse
                    else ModelInterfaceAbstraction("ppo_actor")
                ),
                parallel=cfg.ref_parallel or cfg.actor_parallel,
                device_offset=cfg.actor_device_offset,
            )
        )
    if critic is not None:
        shards.append(
            ModelShardSpec(
                name=critic,
                model=cfg.critic,
                backend=ModelBackendAbstraction(
                    "train", dict(train_backend_args)
                ),
                interface=critic_if,
                parallel=cfg.critic_parallel,
                optimizer=cfg.optimizer,
            )
        )
    workers_of: Dict[str, List[int]] = {}
    replicas: Dict[str, List[int]] = {}
    for s in shards:
        where = cfg.placement.get(s.name.role, 0)
        if isinstance(where, int):
            workers_of[str(s.name)] = [where]
        else:
            workers_of[str(s.name)] = list(where)
            if len(where) > 1:
                replicas[str(s.name)] = list(where)
    placement = {k: v[0] for k, v in workers_of.items()}
    n_workers = max(w for ws in workers_of.values() for w in ws) + 1
    worker_configs = []
    for w in range(n_workers):
        worker_configs.append(
            WorkerConfig(
                worker_index=w,
                shards=[s for s in shards if w in workers_of[str(s.name)]],
                # Datasets live on worker 0 (the data worker); outputs move
                # to consumers via the master-planned transfer plane.
                datasets=[cfg.dataset] if w == 0 else [],
                batch_size=cfg.batch_size,
                seed=cfg.seed,
                ftspec=ftspec,
                device_offset=cfg.worker_device_offsets.get(w, 0),
            )
        )
    cfg.ctrl.total_train_epochs = cfg.total_train_epochs
    return ExperimentPlan(
        dfg=dfg,
        worker_configs=worker_configs,
        model_placement=placement,
        data_worker_ids=[0],
        ctrl=cfg.ctrl,
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        fileroot=cfg.fileroot,
        model_replicas=replicas or None,
        difficulty_filter=cfg.dataset_filter,
        rollout_ahead=cfg.rollout_ahead,
        max_head_offpolicyness=cfg.max_head_offpolicyness,
        replay_capacity=cfg.replay_capacity,
        pipeline_overlap=cfg.pipeline_overlap,
        overlap_window=cfg.overlap_window,
        pipeline_chunk_seqs=cfg.pipeline_chunk_seqs,
        mfc_timeout_s=cfg.mfc_timeout_s,
        worker_heartbeat_s=cfg.worker_heartbeat_s,
        max_recoveries=cfg.max_recoveries,
        max_consecutive_quarantines=cfg.max_consecutive_quarantines,
        weight_push_checksum=cfg.weight_push_checksum,
    )


def run_experiment(plan: ExperimentPlan, tokenizer=None, inspect=None):
    """In-process runner: build workers, drive the master loop to completion.
    (The multi-process ZMQ runtime is areal_tpu/apps/main.py run_experiment.)

    `inspect(master, stage)`, when given, is called with stage "built" once
    every worker's models exist (before the first step) and "done" after
    the last step, while the engines (`master.pool.workers`) are alive.
    """
    import asyncio

    from areal_tpu.base import tracer
    from areal_tpu.system.master import InProcessPool, MasterWorker
    from areal_tpu.system.transfer import InProcTransfer
    from areal_tpu.system.worker import ModelWorker

    # One process hosts everything here, so all spans land in the master's
    # shard (threads are separate trace rows); set the shared dir before
    # any component configures the tracer.
    tracer.default_dir(
        plan.fileroot, plan.experiment_name, plan.trial_name
    )
    # What step 1's `setup/*` stats are made of (base/tracer.py): this
    # span and, inside it, each worker's `setup:worker`, `:mesh`,
    # `:weights`, `:engine` and `:datasets`.
    with tracer.setup_span("build"):
        planes = InProcTransfer.make_group(len(plan.worker_configs))
        workers = [
            ModelWorker(wc, tokenizer=tokenizer, transfer=planes[i])
            for i, wc in enumerate(plan.worker_configs)
        ]
        pool = InProcessPool(workers, mfc_timeout_s=plan.mfc_timeout_s)
        with tracer.setup_span("master"):
            master = MasterWorker(
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
                max_head_offpolicyness=plan.max_head_offpolicyness,
                replay_capacity=plan.replay_capacity,
                buffer_max_age_steps=plan.buffer_max_age_steps,
                pipeline_overlap=plan.pipeline_overlap,
                overlap_window=plan.overlap_window,
                pipeline_chunk_seqs=plan.pipeline_chunk_seqs,
                max_recoveries=plan.max_recoveries,
                max_consecutive_quarantines=plan.max_consecutive_quarantines,
                weight_push_checksum=plan.weight_push_checksum,
            )
            master.load_recover_info()
    if inspect is not None:
        inspect(master, "built")
    stats = asyncio.run(master.run())
    if inspect is not None:
        inspect(master, "done")
    return master, stats

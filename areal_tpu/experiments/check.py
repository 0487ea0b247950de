"""Experiment-config validation, run before any device work.

Capability parity: realhf/experiments/common/check.py (+ the scattered
asserts of api/cli_args.py) — fail a misconfigured trial at BUILD time
with a sentence naming the knob, instead of deep in a worker after
minutes of model loading.  Called by build_sft / build_ppo_math.
"""

import os
from typing import Optional

from areal_tpu.api.model_api import GenerationHyperparameters, OptimizerConfig
from areal_tpu.base.topology import ParallelConfig


def _fail(msg: str):
    raise ValueError(f"invalid experiment config: {msg}")


def check_optimizer(opt: OptimizerConfig) -> None:
    if opt.lr <= 0:
        _fail(f"optimizer.lr must be > 0, got {opt.lr}")
    if not 0.0 <= opt.warmup_steps_proportion <= 1.0:
        _fail(
            "optimizer.warmup_steps_proportion must be in [0, 1], got "
            f"{opt.warmup_steps_proportion}"
        )
    min_lr_ratio = getattr(opt, "min_lr_ratio", 0.0)
    if not 0.0 <= min_lr_ratio <= 1.0:
        _fail(f"optimizer.min_lr_ratio must be in [0, 1], got {min_lr_ratio}")


def check_model_path(role: str, spec) -> None:
    if spec is not None and spec.type_ == "hf":
        path = spec.args.get("path", "")
        if not os.path.exists(path):
            _fail(
                f"model path {path!r} for {role!r} does not exist locally "
                "(download the checkpoint first)"
            )


def check_gconfig(g: GenerationHyperparameters) -> None:
    if g.n < 1:
        _fail(f"gconfig.n must be >= 1, got {g.n}")
    if g.max_new_tokens < 1:
        _fail(f"gconfig.max_new_tokens must be >= 1, got {g.max_new_tokens}")
    if g.min_new_tokens > g.max_new_tokens:
        _fail(
            f"gconfig.min_new_tokens ({g.min_new_tokens}) > max_new_tokens "
            f"({g.max_new_tokens})"
        )
    if not g.greedy and g.temperature <= 0:
        _fail(f"gconfig.temperature must be > 0 when sampling, got "
              f"{g.temperature}")
    if not 0.0 < g.top_p <= 1.0:
        _fail(f"gconfig.top_p must be in (0, 1], got {g.top_p}")


def check_batch_vs_parallel(
    role: str,
    n_seqs: int,
    parallel: ParallelConfig,
    n_mbs: int = 1,
) -> None:
    """Every DP shard of every pipeline stage needs at least one sequence
    per microbatch (reference: check_valid_parallel_batch_size)."""
    need = parallel.dp_size * parallel.pipe * max(n_mbs, 1)
    if n_seqs < need:
        _fail(
            f"{role}: batch of {n_seqs} sequences cannot fill "
            f"dp={parallel.dp_size} x pipe={parallel.pipe} x "
            f"n_mbs={n_mbs} (needs >= {need})"
        )


def check_liveness(cfg) -> None:
    """Crash-safe trainer plane knobs: a deadline shorter than the
    heartbeat grace window (3x the beat period) would declare live
    workers dead on their first slow MFC."""
    timeout = getattr(cfg, "mfc_timeout_s", None)
    beat = getattr(cfg, "worker_heartbeat_s", 5.0)
    if beat <= 0:
        _fail(f"worker_heartbeat_s must be > 0, got {beat}")
    if timeout is not None:
        if timeout <= 0:
            _fail(
                f"mfc_timeout_s must be > 0 (omit it for no deadline), "
                f"got {timeout}"
            )
        if timeout <= beat:
            _fail(
                f"mfc_timeout_s ({timeout}) must exceed "
                f"worker_heartbeat_s ({beat}) — at least one beat must "
                "fit inside the deadline to tell slow from dead"
            )
    if getattr(cfg, "max_recoveries", 3) < 0:
        _fail(
            f"max_recoveries must be >= 0, got "
            f"{getattr(cfg, 'max_recoveries', 3)}"
        )


def check_anomaly(cfg) -> None:
    """Numerical-integrity guard-plane knobs (engines/train.py sentinels,
    interfaces/ppo.py batch sentinels, master quarantine escalation)."""
    mult = getattr(cfg, "anomaly_grad_norm_mult", 0.0)
    if mult < 0:
        _fail(
            f"anomaly_grad_norm_mult must be >= 0 (0 disables the "
            f"grad-spike sentinel), got {mult}"
        )
    if 0.0 < mult <= 1.0:
        # A spike threshold at-or-below the running mean would quarantine
        # routine steps — the knob is a MULTIPLIER over the EWMA.
        _fail(
            f"anomaly_grad_norm_mult must be > 1 when enabled (it "
            f"multiplies the running grad-norm EWMA), got {mult}"
        )
    unorm = getattr(cfg, "anomaly_update_norm_max", 0.0)
    if unorm < 0:
        _fail(
            f"anomaly_update_norm_max must be >= 0 (0 disables the "
            f"update-norm ceiling), got {unorm}"
        )
    kl_max = getattr(cfg, "anomaly_kl_max", None)
    if kl_max is not None and kl_max <= 0:
        _fail(
            f"anomaly_kl_max must be > 0 (omit it to disable the KL "
            f"sentinel), got {kl_max}"
        )
    mcq = getattr(cfg, "max_consecutive_quarantines", 3)
    if mcq < 0:
        _fail(
            f"max_consecutive_quarantines must be >= 0 (0 disables "
            f"rollback escalation), got {mcq}"
        )


def check_ppo_math(cfg) -> None:
    """Cross-field checks for PPOMathConfig (cheap, no jax import)."""
    check_optimizer(cfg.optimizer)
    check_gconfig(cfg.gconfig)
    check_liveness(cfg)
    check_anomaly(cfg)
    for role, spec in (
        ("actor", cfg.actor), ("ref", cfg.ref), ("critic", cfg.critic),
    ):
        check_model_path(role, spec)

    kw = cfg.ppo_kwargs
    if kw.get("kl_adaptive") and not kw.get("kl_ctl"):
        _fail(
            "kl_adaptive with kl_ctl=0: the multiplicative controller can "
            "never leave 0 — set a nonzero initial kl_ctl"
        )
    if (kw.get("kl_ctl") or kw.get("kl_adaptive")) and cfg.ref is None:
        _fail("KL control (kl_ctl/kl_adaptive) needs a ref model")
    if kw.get("use_dense_reward") and cfg.critic is None:
        _fail("use_dense_reward needs the critic (value) mode")
    for knob in ("early_stop_imp_ratio", "early_stop_kl"):
        v = kw.get(knob)
        if v is not None and v <= 0:
            # 0.0 would mean "trip on every minibatch" — but in this
            # ppo_kwargs dict 0.0 conventionally means "disabled"
            # (kl_ctl): reject the ambiguity instead of silently
            # collapsing every step to one minibatch.
            _fail(
                f"{knob} must be > 0 (omit it to disable early stopping)"
            )
    gen_size: Optional[int] = kw.get("generation_size")
    if gen_size is not None and gen_size < cfg.gconfig.n:
        _fail(
            f"generation_size ({gen_size}) must be >= group size "
            f"gconfig.n ({cfg.gconfig.n})"
        )
    if cfg.fuse_rew_ref and cfg.ref is None:
        _fail("fuse_rew_ref needs a ref model")
    if cfg.rollout_ahead not in (0, 1):
        _fail(f"rollout_ahead must be 0 or 1, got {cfg.rollout_ahead}")
    mho = getattr(cfg, "max_head_offpolicyness", None)
    if mho is not None:
        if mho < 0:
            _fail(
                f"max_head_offpolicyness must be >= 0, got {mho}"
            )
        if cfg.rollout_ahead > 0:
            # Both knobs claim ownership of the prefetch pipeline; the
            # async-RL replay path subsumes rollout_ahead=1 (it is
            # max_head_offpolicyness=0 plus admission control).
            _fail(
                "max_head_offpolicyness and rollout_ahead are mutually "
                "exclusive (async RL replaces the one-step-ahead path)"
            )
    if getattr(cfg, "replay_capacity", 4) < 1:
        _fail(
            f"replay_capacity must be >= 1, got "
            f"{getattr(cfg, 'replay_capacity', 4)}"
        )
    if getattr(cfg, "pipeline_overlap", False):
        if cfg.rollout_ahead > 0 or mho is not None:
            _fail(
                "pipeline_overlap is mutually exclusive with "
                "rollout_ahead / max_head_offpolicyness: those overlap "
                "generation ACROSS steps, pipeline overlap streams "
                "chunks WITHIN one on-policy step"
            )
        if getattr(cfg, "overlap_window", 2) < 1:
            _fail(
                f"overlap_window must be >= 1, got "
                f"{getattr(cfg, 'overlap_window', 2)}"
            )
        if getattr(cfg, "pipeline_chunk_seqs", 1) < 1:
            _fail(
                f"pipeline_chunk_seqs must be >= 1, got "
                f"{getattr(cfg, 'pipeline_chunk_seqs', 1)}"
            )
    if cfg.gen_server_url and getattr(cfg, "gen_backend_args", None):
        # Decoupled serving builds a weightless remote_generator backend;
        # local GeneratorEngine kwargs would be silently ignored — the
        # user's explicit flag (e.g. kv_cache_dtype) must not no-op.
        _fail(
            "gen_backend_args apply to the in-process GeneratorEngine "
            "and are ignored under gen_server_url (configure the "
            "standalone gen_server instead)"
        )
    mw = getattr(cfg, "mixture_weights", {}) or {}
    for task, w in mw.items():
        if not isinstance(w, (int, float)) or w <= 0:
            _fail(
                f"mixture_weights[{task!r}] must be a positive number "
                f"(got {w!r}); zero-weight tasks should be omitted"
            )
    if getattr(cfg, "mixture_adaptive", False) and not mw:
        _fail(
            "mixture_adaptive needs mixture_weights (the adaptive "
            "scheduler rebalances an explicit task mixture)"
        )
    if getattr(cfg, "verifier_pool", False) and not (
        cfg.experiment_name and cfg.trial_name
    ):
        _fail(
            "verifier_pool needs experiment_name and trial_name to "
            "discover the announced verifier fleet"
        )
    if getattr(cfg, "kv_page_size", 128) < 1:
        _fail(f"kv_page_size must be >= 1, got {cfg.kv_page_size}")
    if getattr(cfg, "kv_pool_pages", 0) < 0:
        _fail(
            f"kv_pool_pages must be >= 0 (0 = auto-size), got "
            f"{cfg.kv_pool_pages}"
        )
    gba = getattr(cfg, "gen_backend_args", None) or {}
    if gba and not cfg.gen_server_url:
        # gen_backend_args reach GeneratorEngine(**kwargs) inside a worker;
        # an option that no longer exists (or a typo) fails here instead.
        import inspect

        from areal_tpu.engines.generator import GeneratorEngine

        known = set(inspect.signature(GeneratorEngine.__init__).parameters)
        unknown = sorted(set(gba) - known)
        if unknown:
            _fail(
                f"gen_backend_args {unknown} are not GeneratorEngine "
                f"options (continuous batching always runs on the paged "
                f"serving plane; the options that selected the dense and "
                f"two-program inflight paths were removed)"
            )
    pct = gba.get(
        "prefill_chunk_tokens", getattr(cfg, "prefill_chunk_tokens", 8)
    )
    if pct < 1:
        _fail(
            f"prefill_chunk_tokens must be >= 1 (the serving chunk's "
            f"prefill slice width; the two-program admit path that 0 "
            f"selected was removed), got {pct}"
        )
    if cfg.gen_server_url and (
        getattr(cfg, "kv_page_size", 128) != 128
        or getattr(cfg, "kv_pool_pages", 0)
        or getattr(cfg, "prefill_chunk_tokens", 8) != 8
        or not getattr(cfg, "kv_share_prefix", True)
    ):
        # Same reasoning as gen_backend_args below: these configure the
        # in-process GeneratorEngine, which decoupled serving never
        # builds — a silently ignored capacity knob is a footgun.
        _fail(
            "kv_page_size/kv_pool_pages/prefill_chunk_tokens/"
            "kv_share_prefix apply to the in-process GeneratorEngine "
            "and are ignored under gen_server_url (configure the "
            "standalone gen_server instead)"
        )
    if getattr(cfg, "param_push_fanout", 2) < 1:
        _fail(
            f"param_push_fanout must be >= 1, got "
            f"{getattr(cfg, 'param_push_fanout', 2)}"
        )
    if getattr(cfg, "param_push_tree", False) and not cfg.gen_server_url:
        # The broadcast fabric fans out over the remote gen-server
        # fleet; the in-process path hot-swaps weights directly and has
        # nothing to relay through — a tree flag there would no-op.
        _fail(
            "param_push_tree requires gen_server_url (the broadcast "
            "fabric distributes over the remote serving fleet; the "
            "in-process engine swaps weights directly)"
        )
    if (
        cfg.rollout_ahead > 0
        or mho is not None
        or getattr(cfg, "pipeline_overlap", False)
    ) and getattr(cfg, "gen_backend_args", {}).get(
        "donation_safe_swap"
    ) is False:
        # The copy-free hot-swap aliases the train master's buffers; with
        # one-step-ahead rollout, async-RL prefetch, OR within-step
        # pipeline overlap the generator DECODES while the optimizer
        # donates (or is about to donate) those buffers — a
        # use-after-free, not a memory tradeoff.
        _fail(
            "donation_safe_swap=False requires fully synchronous rollout "
            "(rollout_ahead=0, no max_head_offpolicyness, no "
            "pipeline_overlap): overlapped generation would decode from "
            "buffers the optimizer step donates"
        )
    if cfg.dataset_filter:
        lo = cfg.dataset_filter.get("min_accuracy", 0.0)
        hi = cfg.dataset_filter.get("max_accuracy", 1.0)
        if not 0.0 <= lo < hi <= 1.0:
            _fail(
                f"dataset_filter accuracy band [{lo}, {hi}] must satisfy "
                "0 <= min < max <= 1"
            )
    for role, widx in cfg.placement.items():
        idxs = widx if isinstance(widx, list) else [widx]
        if not idxs or any(
            (not isinstance(i, int)) or i < 0 for i in idxs
        ):
            _fail(f"placement[{role!r}] must be a worker index or a "
                  f"non-empty list of them, got {widx!r}")
    n_seqs = cfg.batch_size * cfg.gconfig.n
    check_batch_vs_parallel(
        "actor train", n_seqs, cfg.actor_parallel, cfg.mb_spec.n_mbs
    )
    # Generation folds any pipe axis into model (generator.py
    # fold_pipe_into_model), so only the data axes constrain its batch.
    import dataclasses as _dc

    gen_pc = cfg.gen_parallel or cfg.actor_parallel
    check_batch_vs_parallel(
        "generation", cfg.batch_size, _dc.replace(gen_pc, pipe=1)
    )


def check_sft(cfg) -> None:
    check_optimizer(cfg.optimizer)
    check_liveness(cfg)
    check_anomaly(cfg)
    check_model_path("model", cfg.model)
    check_batch_vs_parallel(
        "train", cfg.batch_size, cfg.parallel, cfg.mb_spec.n_mbs
    )

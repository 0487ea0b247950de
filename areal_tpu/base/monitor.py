"""Observability: FLOPs accounting, timing marks, MFU, per-step stats sinks.

Capability parity: realhf/system/flops_counter.py (per-MFC FLOP tallies),
realhf/base/monitor.py:281-703 (time marks, metrics export) and the
master's per-step perf log (realhf/system/master_worker.py:434-473) —
rebuilt around analytic transformer FLOP formulas (the packed-sequence
attention term uses the exact sum of per-sequence s^2) and a jsonl +
optional tensorboard/wandb sink instead of CUDA counters.
"""

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

from areal_tpu.base import logging

logger = logging.getLogger("monitor")


# ---------------- FLOPs ----------------


def _attn_layers(cfg) -> int:
    """Layers with softmax attention: one per period of a hybrid pattern,
    and every leading dense layer (those lie outside the periods); the '*'
    layers of a pattern of one-branch layers; every layer of a window /
    full mix (a window layer is counted as a full one: this count bounds
    the program's own estimate, the benchmark's `peaks_swa.py` counts the
    band)."""
    if getattr(cfg, "layer_pattern", ""):
        return cfg.n_attn_layers
    if getattr(cfg, "window_pattern", ""):  # a short convolution is none
        return cfg.n_attn_layers + cfg.n_window_layers
    return getattr(cfg, "n_periods", cfg.n_layers) + getattr(
        cfg, "first_k_dense", 0)


def _ssm_params(cfg) -> int:
    """ONE Mamba-2 layer's matmul parameters, its recurrence counted as
    the multiply-adds a token takes: 2 * d_inner * N for the state (add the
    token's outer product, read y = S C) — what the decode step does; the
    chunked form's extra in-chunk products are not counted as useful."""
    h, di = cfg.hidden_dim, cfg.ssm_inner_dim
    return h * cfg.ssm_in_dim + di * h + 2 * di * cfg.ssm_state_dim


def _sconv_params(cfg) -> int:
    """ONE gated short-convolution layer's matmul parameters: in_proj [D,
    3 D] and out_proj [D, D], and the depthwise conv's K multiply-adds a
    channel."""
    h = cfg.hidden_dim
    return 4 * h * h + cfg.sconv_kernel * h


def _lightning_params(cfg) -> int:
    """ONE Lightning-attention layer's matmul parameters (q, k, v, the
    gate, the output projection), its recurrence counted as the 2 * d * d
    multiply-adds a head's state takes per token (add k^T v, read q S)."""
    w = cfg.lightning_dim
    return 5 * cfg.hidden_dim * w + 2 * w * cfg.lightning_head_dim


def _sparse_attn_flops(cfg, n_tokens: int, sum_sq_seqlens: float) -> float:
    """The score-and-value FLOPs of ONE block-sparse layer: a token's
    SELECTED keys (topk blocks, never more than its sequence has), not all
    of them, and its scores against one compressed key per stride."""
    n_sparse = getattr(cfg, "n_sparse_layers", 0)
    if not n_sparse:
        return 0.0
    hd = cfg.n_q_heads * cfg.head_dim
    chosen = min(
        sum_sq_seqlens, float(n_tokens) * cfg.sparse_topk * cfg.sparse_block_size)
    return n_sparse * (
        4.0 * hd * chosen + 2.0 * hd * sum_sq_seqlens / cfg.sparse_kernel_stride)


def _attn_params(cfg) -> int:
    """Matmul parameters of ONE softmax-attention layer's projections.
    Latent attention: the two low-rank query projections, the latent and
    shared-rope projection, the key and value up-projections and the
    output projection (the materialised form training and prefill run)."""
    h, d = cfg.hidden_dim, cfg.head_dim
    if getattr(cfg, "is_latent", False):
        hq, c = cfg.n_q_heads, cfg.kv_lora_rank
        return (
            h * cfg.q_lora_rank + cfg.q_lora_rank * hq * d
            + h * cfg.latent_dim
            + c * hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + hq * cfg.v_head_dim * h
        )
    q_mats = 2 if getattr(cfg, "attn_gate", False) else 1
    return (
        h * (q_mats * cfg.n_q_heads * d + 2 * cfg.n_kv_heads * d)
        + cfg.n_q_heads * d * h
    )


def matmul_params(cfg) -> int:
    """Parameters that participate in matmuls for ONE token's forward pass
    (the routed experts and the router for MoE; embedding lookup excluded).

    A pattern of one-branch layers counts each KIND over its own layers
    (`_ssm_params`, the attention projections, the mixture), a mix of
    short-convolution and attention layers likewise (`_sconv_params`); per
    layer KIND in a hybrid pattern: a softmax-attention layer's
    projections (the query's twice where it also gives the output gate), a
    Gated DeltaNet layer's projections plus its recurrence counted as the
    3 * d_k * d_v multiply-adds a value head's state takes per token
    (S^T k, S^T q, k d^T).  Of the routed experts the ones HELD here: a
    rank's share computes n_experts / router_width of a token's k choices
    in expectation, beside the whole router and the shared expert (its
    gate where it has one).  Leading dense layers (`first_k_dense`) count
    the dense MLP, the others the mixture."""
    h = cfg.hidden_dim
    n_attn = _attn_layers(cfg)
    mixers = n_attn * _attn_params(cfg)
    pattern = getattr(cfg, "layer_pattern", "")
    if getattr(cfg, "n_sparse_layers", 0) or getattr(
            cfg, "n_lightning_layers", 0):
        # minicpm_sala: a block-sparse layer has a softmax layer's
        # projections; its selected keys are `_sparse_attn_flops`'s.
        mixers += cfg.n_sparse_layers * _attn_params(cfg)
        mixers += cfg.n_lightning_layers * _lightning_params(cfg)
    elif pattern or getattr(cfg, "n_ssm_layers", 0):
        # Each kind over its own layers: a pattern's ONE branch a layer,
        # or Mamba-2 mixers in two-branch layers (granitemoehybrid).
        mixers += cfg.n_ssm_layers * _ssm_params(cfg)
    elif getattr(cfg, "n_sconv_layers", 0):
        mixers += cfg.n_sconv_layers * _sconv_params(cfg)
    elif n_attn != cfg.n_layers:
        hv = cfg.linear_n_v_heads
        linear = (
            h * (cfg.linear_conv_dim + cfg.linear_value_dim + 2 * hv)
            + cfg.linear_value_dim * h
            + 3 * hv * cfg.linear_k_head_dim * cfg.linear_v_head_dim
        )
        mixers += (cfg.n_layers - n_attn) * linear
    n_mats = 3 if getattr(cfg, "mlp_gated", True) else 2
    if cfg.is_moe:
        inter = cfg.moe_intermediate_dim or cfg.intermediate_dim
        width = getattr(cfg, "router_width", cfg.n_experts)
        held = cfg.n_experts_per_tok * cfg.n_experts / width
        mlp = n_mats * h * inter * held + h * width
        shared = getattr(cfg, "shared_expert_dim", 0)
        if shared:
            mlp += n_mats * h * shared + (
                h if getattr(cfg, "shared_expert_gated", True) else 0)
    else:
        mlp = n_mats * h * cfg.intermediate_dim
    n_lead = getattr(cfg, "first_k_dense", 0)
    n_mlp = cfg.n_moe_layers if pattern else cfg.n_layers - n_lead
    mlps = n_mlp * mlp + n_lead * n_mats * h * cfg.intermediate_dim
    head = 0 if cfg.is_critic else h * cfg.vocab_size
    return int(mixers + mlps + head)


def flops_forward(
    cfg, n_tokens: int, sum_sq_seqlens: Optional[float] = None
) -> float:
    """Forward-pass FLOPs over packed sequences: 2*N per token for matmuls
    plus the quadratic attention term 4*h_q*sum_i(s_i^2) per softmax-
    attention layer (QK^T and attn@V, causal factor folded into the
    constant the same way the reference counts it, flops_counter.py)."""
    mm = 2.0 * matmul_params(cfg) * n_tokens
    if sum_sq_seqlens is None:
        sum_sq_seqlens = float(n_tokens) ** 2
    attn = 2.0 * 2.0 * cfg.n_q_heads * cfg.head_dim * sum_sq_seqlens * _attn_layers(cfg)
    return mm + attn + _sparse_attn_flops(cfg, n_tokens, sum_sq_seqlens)


def flops_train(cfg, n_tokens: int, sum_sq_seqlens: Optional[float] = None) -> float:
    """fwd + bwd ~= 3x forward."""
    return 3.0 * flops_forward(cfg, n_tokens, sum_sq_seqlens)


def flops_generate(
    cfg,
    prompt_lens: Sequence[int],
    gen_lens: Sequence[int],
) -> float:
    """Prefill (packed forward over prompts) + incremental decode: each new
    token costs 2*N matmul FLOPs plus attention over its live prefix."""
    p_tokens = float(sum(prompt_lens))
    p_sq = float(sum(p * p for p in prompt_lens))
    total = flops_forward(cfg, int(p_tokens), p_sq)
    n = 2.0 * matmul_params(cfg)
    attn_c = 4.0 * cfg.n_q_heads * cfg.head_dim * _attn_layers(cfg)
    for p, g in zip(prompt_lens, gen_lens):
        total += n * g
        # sum over decode steps of (p + t) ~ g*p + g^2/2
        total += attn_c * (g * p + g * g / 2.0)
        total += _sparse_attn_flops(cfg, g, g * p + g * g / 2.0)
    return total


# Peak bf16 TFLOP/s per chip, keyed by a substring of the lower-cased
# `device_kind` JAX reports (public TPU specs; the v5e says "TPU v5 lite").
_PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,  # trillium
    "v6e": 918.0,
}


def peak_tflops_per_device() -> Optional[float]:
    """Peak of one device of the default backend.  None on CPU (no MFU
    there); an accelerator whose `device_kind` is not in the table is an
    error, so MFU never silently drops out of the stats on a chip."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    for key, val in _PEAK_TFLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s entry for device_kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}); add it to _PEAK_TFLOPS"
    )


def mfu(flops: float, seconds: float, n_devices: int) -> Optional[float]:
    peak = peak_tflops_per_device()
    if peak is None or seconds <= 0 or n_devices <= 0:
        return None
    return flops / seconds / (peak * 1e12 * n_devices)


# ---------------- stats sinks ----------------


class StatsLogger:
    """Per-step scalar sink: always jsonl; tensorboard / wandb when asked.

    Capability parity: the reference's wandb+tensorboard loggers
    (realhf/base/stats_logger.py via master worker) — jsonl is the source
    of truth so trials remain greppable with zero services running.
    """

    def __init__(
        self,
        fileroot: str,
        experiment_name: str,
        trial_name: str,
        use_tensorboard: Optional[bool] = None,
        use_wandb: Optional[bool] = None,
    ):
        self.dir = os.path.join(fileroot, "logs", experiment_name, trial_name)
        os.makedirs(self.dir, exist_ok=True)
        self.path = os.path.join(self.dir, "stats.jsonl")
        # Persistent append handle: reopening per step costs an
        # open/close syscall pair every step and loses append atomicity
        # on some filesystems; explicit flush keeps the file greppable
        # mid-trial.
        self._jsonl = open(self.path, "a")
        if use_tensorboard is None:
            use_tensorboard = bool(os.environ.get("AREAL_TENSORBOARD"))
        if use_wandb is None:
            use_wandb = bool(os.environ.get("AREAL_WANDB"))
        self._tb = None
        if use_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir=os.path.join(self.dir, "tb"))
            except Exception as e:  # torch/tb missing or broken: jsonl only
                logger.warning(f"tensorboard disabled: {e!r}")
        self._wandb = None
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb
                wandb.init(
                    project=experiment_name,
                    name=trial_name,
                    dir=self.dir,
                    mode=os.environ.get("WANDB_MODE", "offline"),
                )
            except Exception as e:
                logger.warning(f"wandb disabled: {e!r}")

    def log(self, step: int, stats: Dict[str, float]) -> None:
        row = {"global_step": step, "ts": time.time(), **stats}
        self._jsonl.write(json.dumps(row) + "\n")
        self._jsonl.flush()
        if self._tb is not None:
            for k, v in stats.items():
                self._tb.add_scalar(k, v, global_step=step)
            self._tb.flush()
        if self._wandb is not None:
            self._wandb.log(stats, step=step)

    def close(self):
        if self._jsonl is not None and not self._jsonl.closed:
            self._jsonl.close()
        if self._tb is not None:
            self._tb.close()
        if self._wandb is not None:
            self._wandb.finish()


def read_stats(fileroot: str, experiment_name: str, trial_name: str) -> List[Dict]:
    path = os.path.join(
        fileroot, "logs", experiment_name, trial_name, "stats.jsonl"
    )
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]

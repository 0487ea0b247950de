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


def _layers_of(cfg):
    """(layers with the kind, its record) of every kind of `cfg.plan`:
    the FLOP counts sum what the records say of ONE layer
    (`models/branches.py`: `Branch.matmul_params`, `Branch.attn_flops`)."""
    from areal_tpu.models.transformer import branches_of

    return [(cfg.plan.count(name), b) for name, b in branches_of(cfg).items()]


def matmul_params(cfg) -> int:
    """Parameters that participate in matmuls for ONE token's forward pass
    (the routed experts held here and the router for MoE; embedding lookup
    excluded): each KIND of branch over its own layers — a mixer's
    projections, a recurrence counted as the multiply-adds its state takes
    per token, a dense MLP or the mixture — and the head."""
    layers = sum(n * b.matmul_params(cfg) for n, b in _layers_of(cfg))
    head = 0 if cfg.is_critic else cfg.hidden_dim * cfg.vocab_size
    return int(layers + head)


def _attn_flops(layers, cfg, n_tokens, sum_sq_seqlens: float) -> float:
    """The score-and-value FLOPs beside the matmuls of `layers`
    (`_layers_of`): 4*h_q*d*sum_i(s_i^2) per softmax-attention layer (QK^T
    and attn@V, causal factor folded into the constant the same way the
    reference counts it, flops_counter.py), a block-sparse layer's
    selected keys."""
    return sum(
        n * b.attn_flops(cfg, n_tokens, sum_sq_seqlens)
        for n, b in layers if b.attn_flops)


def flops_forward(
    cfg, n_tokens: int, sum_sq_seqlens: Optional[float] = None
) -> float:
    """Forward-pass FLOPs over packed sequences: 2*N per token for matmuls
    plus the attention term over the exact sum of per-sequence s^2."""
    if sum_sq_seqlens is None:
        sum_sq_seqlens = float(n_tokens) ** 2
    if getattr(cfg, "block_length", 0):
        return _flops_two_streams(cfg, n_tokens, sum_sq_seqlens)
    return 2.0 * matmul_params(cfg) * n_tokens + _attn_flops(
        _layers_of(cfg), cfg, n_tokens, sum_sq_seqlens)


def _flops_two_streams(cfg, n_tokens: int, sum_sq_seqlens: float) -> float:
    """`flops_forward` of a model that generates by diffusion over blocks
    (`cfg.block_length`): the stack runs over STREAM slots — a clean and a
    masked slot a token where every block is scored (the count here; a
    loss mask that leaves the prompt's blocks out runs fewer) — a masked
    query sees about the keys its clean twin does, and the head reads the
    masked stream alone."""
    head = 0 if cfg.is_critic else cfg.hidden_dim * cfg.vocab_size
    layers = matmul_params(cfg) - head
    return 2.0 * (2 * layers + head) * n_tokens + 2 * _attn_flops(
        _layers_of(cfg), cfg, n_tokens, sum_sq_seqlens)


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
    n, layers = 2.0 * matmul_params(cfg), _layers_of(cfg)
    if getattr(cfg, "block_length", 0):
        # A block of B tokens takes T denoising forwards and a commit, each
        # of B tokens through the layers (the commit without the head);
        # the prompt's blocks are prefilled with no head.
        steps = cfg.denoising_forwards
        head = 2.0 * cfg.hidden_dim * cfg.vocab_size
        total = 2.0 * (matmul_params(cfg) - head / 2) * p_tokens + _attn_flops(
            layers, cfg, int(p_tokens), p_sq)
        for p, g in zip(prompt_lens, gen_lens):
            total += ((steps + 1) * (n - head) + steps * head) * g + (
                steps + 1) * _attn_flops(layers, cfg, g, g * p + g * g / 2.0)
        return total
    for p, g in zip(prompt_lens, gen_lens):
        # sum over decode steps of (p + t) ~ g*p + g^2/2
        total += n * g + _attn_flops(layers, cfg, g, g * p + g * g / 2.0)
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

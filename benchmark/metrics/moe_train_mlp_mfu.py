"""FLOPs the MoE layers' MLPs of one train step need — forward and
backward, `3 x n_layers x peaks.moe_layer_flops` over the step's trained
tokens; the recomputed forward is NOT counted as work — over ALL the
device seconds the train step spends in them (scope `train/grad` +
`layer/mlp` in every phase, plus XLA's ragged-dot kernels of the gradient
program, which carry no scope and no phase: `_moe.py`) at the published
bf16 peak, in %.  So the recomputation's seconds are in the denominator:
model-FLOP utilisation as the on-chip-measurement guide defines it, about
three quarters of what the same FLOPs over forward and backward seconds
alone would read.  Compute-bound: 1,024 rows an expert at 8,192 tokens."""
from benchmark import peaks
from benchmark.metrics import _moe


def read(run):
    seconds = _moe.mlp_seconds(run, _moe.TRAIN)
    cfg = run.model_cfg
    if (seconds is None or run.peaks is None
            or not getattr(cfg, "n_experts", 0)):
        return None
    tokens = sum(run.steps[-1]["seq_lens"])
    flops = 3.0 * cfg.n_layers * peaks.moe_layer_flops(cfg, tokens)
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

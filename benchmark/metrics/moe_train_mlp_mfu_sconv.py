"""`moe_train_mlp_mfu` for the MLPs of a short-convolution / attention
mix: forward and backward FLOPs of all the layers' MLPs over the step's
trained tokens (`peaks_sconv.mlps_train_flops`: the leading dense MLPs,
the router's whole width, the rows expected to fall to experts held here;
the recomputed forward and the rows gathered but never multiplied are NOT
work) over ALL the device seconds the gradient program spends in them
(scope `train/grad` + `layer/mlp` plus XLA's ragged-dot kernels:
`_moe.py`) at the published bf16 peak, in %."""
from benchmark import peaks_sconv
from benchmark.metrics import _moe, _sconv


def read(run):
    seconds = _moe.mlp_seconds(run, _moe.TRAIN)
    if seconds is None or run.peaks is None or not _sconv.is_mix(run):
        return None
    tokens = sum(run.steps[-1]["seq_lens"])
    flops = peaks_sconv.mlps_train_flops(run.model_cfg, tokens)
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

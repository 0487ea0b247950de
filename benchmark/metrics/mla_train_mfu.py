"""FLOPs latent attention of one train step needs — forward and backward
of the projections and of the causal per-segment scores and sums
(`peaks_mla.mla_train_flops` over the step's trained sequences; the
recomputed forward is NOT work) — over ALL the device seconds the gradient
program spends under the three attention scopes, at the published bf16
peak, in %."""
from benchmark import peaks_mla
from benchmark.metrics import _mla


def read(run):
    seconds = _mla.attn_seconds(run, "train/grad")
    if seconds is None or run.peaks is None:
        return None
    flops = peaks_mla.mla_train_flops(run.model_cfg, run.steps[-1]["seq_lens"])
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

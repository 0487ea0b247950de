"""FLOPs the Gated DeltaNet mixers of one train step need — forward and
backward of the projections and of the delta rule as the recurrence counts
it (`peaks_hybrid.gdn_train_flops` over the step's trained tokens; the
recomputed forward and the chunked form's surplus are NOT work) — over ALL
the device seconds the gradient program spends under `layer/linear_attn`,
at the published bf16 peak, in %."""
from benchmark import peaks_hybrid
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "train/grad", "layer/linear_attn")
    if seconds is None or run.peaks is None:
        return None
    tokens = sum(run.steps[-1]["seq_lens"])
    flops = peaks_hybrid.gdn_train_flops(run.model_cfg, tokens)
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

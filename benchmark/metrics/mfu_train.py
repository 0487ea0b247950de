"""Model FLOP/s utilisation of the train request: forward + backward
FLOPs of the trained sequences (benchmark/peaks.py, recompute excluded)
over request seconds, chips and the chip's bf16 peak, in %.  An
end-to-end utilisation of the request, not a kernel's roofline share."""
import statistics

from benchmark import peaks
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None:
        return None
    rate = statistics.median(
        peaks.flops_train(run.model_cfg, s["seq_lens"]) / s["spans"][TRAIN]
        for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

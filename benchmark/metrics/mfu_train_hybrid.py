"""`mfu_train` for a hybrid layer pattern: forward + backward FLOPs of the
trained sequences as `benchmark/peaks_hybrid.py` counts them (per layer
kind, the experts held; recompute excluded) over request seconds, chips
and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_hybrid
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not getattr(run.model_cfg, "is_hybrid", False):
        return None
    rate = statistics.median(
        peaks_hybrid.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""`mfu_train` for a window / full mix: forward + backward FLOPs of the
trained sequences as `benchmark/peaks_swa.py` counts them (a window
layer's attention over its band, the experts held; recompute excluded)
over request seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_swa
from benchmark.metrics import _swa
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _swa.is_mix(run):
        return None
    rate = statistics.median(
        peaks_swa.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

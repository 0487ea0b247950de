"""`mfu_train` for a short-convolution / attention mix: forward + backward
FLOPs of the trained sequences as `benchmark/peaks_sconv.py` counts them
(the conv layers' projections, the attention layers' causal pairs, the
leading dense MLPs, the experts held; recompute excluded) over request
seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_sconv
from benchmark.metrics import _sconv
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _sconv.is_mix(run):
        return None
    rate = statistics.median(
        peaks_sconv.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

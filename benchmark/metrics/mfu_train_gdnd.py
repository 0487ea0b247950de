"""`mfu_train` for Gated DeltaNet mixers before a dense MLP: forward +
backward FLOPs of the trained sequences as `benchmark/peaks_gdnd.py` counts
them (a mixer and a dense MLP a layer, the delta rule as the recurrence
defines it, the head; recompute excluded) over request seconds, chips and
the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_gdnd
from benchmark.metrics import _gdnd
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _gdnd.is_gdnd(run):
        return None
    rate = statistics.median(
        peaks_gdnd.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

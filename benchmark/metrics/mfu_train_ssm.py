"""`mfu_train` for a pattern of one-branch layers: forward + backward
FLOPs of the trained sequences as `benchmark/peaks_ssm.py` counts them
(each kind over its own layers, the experts held; recompute excluded) over
request seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_ssm
from benchmark.metrics import _ssm
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _ssm.is_pattern(run):
        return None
    rate = statistics.median(
        peaks_ssm.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

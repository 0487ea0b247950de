"""`mfu_train` for Mamba-2 mixers in two-branch layers: forward + backward
FLOPs of the trained sequences as `benchmark/peaks_ssmd.py` counts them (a
mixer and a dense MLP a layer, the recurrence as defined, the tied head;
recompute excluded) over request seconds, chips and the chip's bf16 peak,
in %."""
import statistics

from benchmark import peaks_ssmd
from benchmark.metrics import _ssmd
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _ssmd.is_ssmd(run):
        return None
    rate = statistics.median(
        peaks_ssmd.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

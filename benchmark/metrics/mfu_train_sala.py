"""`mfu_train` for minicpm_sala: forward + backward FLOPs of the trained
sequences as `benchmark/peaks_sala.py` counts them (the SELECTED keys of a
block-sparse layer, the Lightning recurrence as defined, a dense MLP a
layer, the head; recompute excluded) over request seconds, chips and the
chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_sala
from benchmark.metrics import _sala
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _sala.is_sala(run):
        return None
    rate = statistics.median(
        peaks_sala.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

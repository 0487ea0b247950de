"""`mfu_train` for dots3_note: forward + backward FLOPs of the trained
sequences as `benchmark/peaks_dsa.py` counts them (the SELECTED keys of a
full layer at its two head widths, the band of a sliding one, the
indexer's scores once — it has no backward — the held experts' share, the
head; recompute excluded) over request seconds, chips and the chip's bf16
peak, in %."""
import statistics

from benchmark import peaks_dsa
from benchmark.metrics import _dsa
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _dsa.is_dsa(run):
        return None
    rate = statistics.median(
        peaks_dsa.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""`mfu_train` for latent attention, leading dense layers and a rank's
share of the experts: forward + backward FLOPs of the trained sequences as
`benchmark/peaks_mla.py` counts them (per layer kind, the experts held;
recompute excluded) over request seconds, chips and the chip's bf16 peak,
in %."""
import statistics

from benchmark import peaks_mla
from benchmark.metrics import _mla
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _mla.is_latent(run):
        return None
    rate = statistics.median(
        peaks_mla.flops_train(run.model_cfg, s["seq_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""`mfu_train` for generation by diffusion over blocks: forward + backward
FLOPs of the trained sequences as `benchmark/peaks_bd.py` counts them (the
layers over STREAM slots, the two-stream mask's visible pairs, the head
over the response's tokens; recompute excluded) over request seconds,
chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_bd
from benchmark.metrics import _bd
from benchmark.metrics._labels import TRAIN


def read(run):
    if run.peaks is None or not _bd.is_bd(run):
        return None
    rate = statistics.median(
        peaks_bd.flops_train(run.model_cfg, s["seq_lens"], s["prompt_lens"])
        / s["spans"][TRAIN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

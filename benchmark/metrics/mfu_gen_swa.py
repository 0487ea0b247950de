"""`mfu_gen` for a window / full mix: FLOPs of the generate request
(prefill + one token at a time, a window layer's new token over its last W
keys) as `benchmark/peaks_swa.py` counts them over request seconds, chips
and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_swa
from benchmark.metrics import _swa
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _swa.is_mix(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_swa.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""Model FLOP/s utilisation of the generate request (prefill + one token
at a time), in %."""
import statistics

from benchmark import peaks
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None:
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

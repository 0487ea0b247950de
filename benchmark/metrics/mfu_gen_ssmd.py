"""`mfu_gen` for Mamba-2 mixers in two-branch layers: FLOPs of the generate
request (every prompt and every new token forwarded once) as
`benchmark/peaks_ssmd.py` counts them over request seconds, chips and the
chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_ssmd
from benchmark.metrics import _ssmd
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _ssmd.is_ssmd(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_ssmd.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""`mfu_gen` for a pattern of one-branch layers: FLOPs of the generate
request (prefill + one token at a time) as `benchmark/peaks_ssm.py` counts
them (each kind over its own layers, the experts held) over request
seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_ssm
from benchmark.metrics import _ssm
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _ssm.is_pattern(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_ssm.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

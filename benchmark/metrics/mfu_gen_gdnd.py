"""`mfu_gen` for Gated DeltaNet mixers before a dense MLP: FLOPs of the
generate request (prefill + one token at a time) as
`benchmark/peaks_gdnd.py` counts them over request seconds, chips and the
chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_gdnd
from benchmark.metrics import _gdnd
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _gdnd.is_gdnd(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_gdnd.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

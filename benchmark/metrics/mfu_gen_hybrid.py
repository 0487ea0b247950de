"""`mfu_gen` for a hybrid layer pattern: FLOPs of the generate request
(prefill + one token at a time) as `benchmark/peaks_hybrid.py` counts them
(per layer kind, the experts held) over request seconds, chips and the
chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_hybrid
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not getattr(run.model_cfg, "is_hybrid", False):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_hybrid.flops_generate(
            run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

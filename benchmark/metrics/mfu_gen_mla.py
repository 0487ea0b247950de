"""`mfu_gen` for latent attention, leading dense layers and a rank's
share of the experts: FLOPs of the generate request (prefill materialised
+ one token at a time absorbed) as `benchmark/peaks_mla.py` counts them
(per layer kind, the experts held, scores and sums over latent rows) over
request seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_mla
from benchmark.metrics import _mla
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _mla.is_latent(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_mla.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

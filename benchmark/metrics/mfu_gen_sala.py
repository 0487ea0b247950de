"""`mfu_gen` for minicpm_sala: FLOPs of the generate request (every prompt
forwarded once, every new token through the cache; a block-sparse layer's
SELECTED keys) as `benchmark/peaks_sala.py` counts them over request
seconds, chips and the chip's bf16 peak, in %."""
import statistics

from benchmark import peaks_sala
from benchmark.metrics import _sala
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _sala.is_sala(run):
        return None

    def flops(s):
        gen = [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])]
        return peaks_sala.flops_generate(run.model_cfg, s["prompt_lens"], gen)

    rate = statistics.median(flops(s) / s["spans"][GEN] for s in run.steps)
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

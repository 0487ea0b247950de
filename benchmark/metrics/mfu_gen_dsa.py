"""`mfu_gen` for dots3_note: the generate request's FLOPs as
`benchmark/peaks_dsa.py` counts them (every prompt forwarded once, every
new token through the caches: index scores against every visible key, the
selected rows, the ring) over request seconds, chips and the chip's bf16
peak, in %."""
import statistics

from benchmark import peaks_dsa
from benchmark.metrics import _dsa
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _dsa.is_dsa(run):
        return None
    rate = statistics.median(
        peaks_dsa.flops_generate(
            run.model_cfg, s["prompt_lens"],
            [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])])
        / s["spans"][GEN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

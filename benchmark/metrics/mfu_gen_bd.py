"""`mfu_gen` for generation by diffusion over blocks: the generate
request's FLOPs as `benchmark/peaks_bd.py` counts them (the prompts' whole
blocks prefilled with no head, T + 1 forwards of B tokens a block, T of
them through the head) over request seconds, chips and the chip's bf16
peak, in %."""
import statistics

from benchmark import peaks_bd
from benchmark.metrics import _bd
from benchmark.metrics._labels import GEN


def read(run):
    if run.peaks is None or not _bd.is_bd(run):
        return None
    rate = statistics.median(
        peaks_bd.flops_generate(
            run.model_cfg, s["prompt_lens"],
            [l - p for l, p in zip(s["seq_lens"], s["prompt_lens"])])
        / s["spans"][GEN] for s in run.steps
    )
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops"])

"""FLOPs the short convolutions of one train step need — forward and
backward of in_proj and out_proj over the step's trained tokens
(`peaks_sconv.sconv_train_flops`: 2 x 16,777,216 a token and conv layer
forward, twice that backward; the recomputed forward is NOT work) — over
ALL the device seconds the gradient program spends under `layer/sconv`,
at the published bf16 peak, in %."""
from benchmark import peaks_sconv
from benchmark.metrics import _sconv
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "train/grad", _sconv.SCOPE)
    if seconds is None or run.peaks is None or not _sconv.is_mix(run):
        return None
    tokens = sum(run.steps[-1]["seq_lens"])
    flops = peaks_sconv.sconv_train_flops(run.model_cfg, tokens)
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

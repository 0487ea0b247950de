"""`ssm_train_mfu` for Mamba-2 mixers in two-branch layers: forward and
backward FLOPs of the mixers' projections and of the recurrence as it is
defined per token (`peaks_ssmd.ssm_train_flops`; the recomputed forward and
the chunked form's surplus are NOT work) over ALL the device seconds the
gradient program spends under `layer/ssm`, at the published bf16 peak, in
%."""
from benchmark import peaks_ssmd
from benchmark.metrics import _ssmd
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "train/grad", "layer/ssm")
    if seconds is None or run.peaks is None or not _ssmd.is_ssmd(run):
        return None
    tokens = sum(run.steps[-1]["seq_lens"])
    flops = peaks_ssmd.ssm_train_flops(run.model_cfg, tokens)
    return 100.0 * flops / seconds / (run.chips * run.peaks["bf16_flops"])

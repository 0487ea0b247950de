"""The time the published HBM bandwidth allows the Mamba-2 mixers of one
inner step of the serving chunk (`peaks_ssmd.ssm_serving_bytes` at the
step's live slots: projection weights, a live slot's fp32 state read once
and written once, its conv tail) as a share of `ssm_serving_ms`, in %.
Bandwidth-bound: at 64 live slots the state is seven times the mixers'
weights."""
from benchmark import peaks_ssmd
from benchmark.metrics import _ssmd, ssm_serving_ms


def read(run):
    ms = ssm_serving_ms.read(run)
    slots = _ssmd.live_slots(run)
    if ms is None or slots is None or run.peaks is None or not _ssmd.is_ssmd(run):
        return None
    floor_s = peaks_ssmd.ssm_serving_bytes(run.model_cfg, slots) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

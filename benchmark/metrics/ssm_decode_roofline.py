"""The time the published HBM bandwidth allows the Mamba-2 mixers of one
decode step (`peaks_ssm.ssm_decode_bytes` at this step's rows: projection
weights, the fp32 state read once and written once, conv tail) as a share
of `ssm_decode_ms`, in %.  Bandwidth-bound: at 64 rows the state is three
and a half times the weights and the FLOPs are nothing."""
from benchmark import peaks_ssm
from benchmark.metrics import _ssm, ssm_decode_ms


def read(run):
    ms = ssm_decode_ms.read(run)
    if ms is None or run.peaks is None or not _ssm.is_pattern(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_ssm.ssm_decode_bytes(run.model_cfg, rows) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

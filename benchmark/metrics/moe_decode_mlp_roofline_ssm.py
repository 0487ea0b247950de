"""`moe_decode_mlp_roofline` for the expert layers of a pattern of
one-branch layers (two matrices an expert, a rank's share, an ungated
shared expert): the time the published HBM bandwidth allows all the expert
layers of one decode step (`peaks_ssm.experts_decode_bytes` at this step's
rows, at the held experts the program COUNTED as touched and the rows it
counted as local) as a share of `moe_decode_mlp_ms`, in %."""
from benchmark import peaks_ssm
from benchmark.metrics import _ssm, moe_decode_mlp_ms


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    if mlp_ms is None or run.peaks is None or not _ssm.is_pattern(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_ssm.experts_decode_bytes(
        run.model_cfg, rows, _ssm.experts_touched(run), _ssm.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

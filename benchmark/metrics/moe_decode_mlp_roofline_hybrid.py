"""`moe_decode_mlp_roofline` for a rank's share of the experts and a
shared expert: the time the published HBM bandwidth allows all the layers'
MLPs of one decode step (`peaks_hybrid.experts_decode_bytes` at this
step's rows, at the held experts the program COUNTED as touched and the
rows it counted as local) as a share of `moe_decode_mlp_ms`, in %.
Bandwidth-bound: at 64 rows the weights are 99% of the bytes."""
from benchmark import peaks_hybrid
from benchmark.metrics import _hybrid, moe_decode_mlp_ms


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    if (mlp_ms is None or run.peaks is None
            or not getattr(run.model_cfg, "is_hybrid", False)):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_hybrid.experts_decode_bytes(
        run.model_cfg, rows, _hybrid.experts_touched(run),
        _hybrid.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

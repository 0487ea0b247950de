"""`moe_decode_mlp_roofline` for the expert layers of a window / full mix
(gated experts, a rank's share, no shared expert): the time the published
HBM bandwidth allows all the layers' MLPs of one decode step
(`peaks_swa.experts_decode_bytes` at this step's rows, at the held experts
the program COUNTED as touched and the rows it counted as local) as a
share of `moe_decode_mlp_ms`, in %."""
from benchmark import peaks_swa
from benchmark.metrics import _swa, moe_decode_mlp_ms


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    if mlp_ms is None or run.peaks is None or not _swa.is_mix(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_swa.experts_decode_bytes(
        run.model_cfg, rows, _swa.experts_touched(run), _swa.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

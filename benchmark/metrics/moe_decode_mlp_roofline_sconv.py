"""`moe_decode_mlp_roofline` for the MLPs of a short-convolution /
attention mix (two leading dense MLPs, then gated experts, a rank's share,
no shared expert): the time the published HBM bandwidth allows all the
layers' MLPs of one decode step (`peaks_sconv.mlps_decode_bytes` at this
step's rows, at the held experts the program COUNTED as touched and the
rows it counted as local) as a share of `moe_decode_mlp_ms`, in %."""
from benchmark import peaks_sconv
from benchmark.metrics import _sconv, moe_decode_mlp_ms


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    if mlp_ms is None or run.peaks is None or not _sconv.is_mix(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_sconv.mlps_decode_bytes(
        run.model_cfg, rows, _sconv.experts_touched(run),
        _sconv.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

"""`moe_decode_mlp_roofline` for a rank's share of sigmoid-routed experts,
an ungated shared expert and a leading dense layer: the time the published
HBM bandwidth allows all the layers' MLPs of one decode step
(`peaks_mla.mlp_decode_bytes` at this step's rows, at the held experts the
program COUNTED as touched and the rows it counted as local; the dense
layer's MLP runs under the same scope and is counted) as a share of
`moe_decode_mlp_ms`, in %."""
from benchmark import peaks_mla
from benchmark.metrics import _mla, moe_decode_mlp_ms


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    if mlp_ms is None or run.peaks is None or not _mla.is_latent(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_mla.mlp_decode_bytes(
        run.model_cfg, rows, _mla.experts_touched(run), _mla.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

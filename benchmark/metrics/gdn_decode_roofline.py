"""The time the published HBM bandwidth allows the Gated DeltaNet mixers
of one decode step (`peaks_hybrid.gdn_decode_bytes` at this step's rows:
projection weights, recurrent state read and written once, conv tail) as
a share of `gdn_decode_ms`, in %.  Bandwidth-bound: at 64 rows the state
is four times the weights and the FLOPs are nothing."""
from benchmark import peaks_hybrid
from benchmark.metrics import gdn_decode_ms


def read(run):
    ms = gdn_decode_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_hybrid.gdn_decode_bytes(run.model_cfg, rows) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

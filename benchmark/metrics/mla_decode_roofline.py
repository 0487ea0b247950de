"""The time the published HBM bandwidth allows latent attention of one
decode step (`peaks_mla.mla_decode_bytes`: every layer's attention weights
once, each row's live context of latent rows read ONCE at its mean
context, the new row written) as a share of `mla_decode_ms`, in %.
Bandwidth-bound: at 64 rows the FLOPs are nothing.  The static program
streams the whole allocated window and, as XLA ops, the rows once for the
scores and once for the sum: both show here as distance from 100."""
from benchmark import peaks_mla
from benchmark.metrics import _mla, mla_decode_ms


def read(run):
    ms = mla_decode_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    floor_s = peaks_mla.mla_decode_bytes(
        run.model_cfg, _mla.contexts(run.steps[-1])
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

"""The time the published HBM bandwidth allows the short convolutions of
one decode step (`peaks_sconv.sconv_decode_bytes` at this step's rows:
every conv layer's projection weights and taps, its tail read and written,
the rows' activations) as a share of `sconv_decode_ms`, in %.
Bandwidth-bound: one token a row, and the tails are a hundredth of the
weights."""
from benchmark import peaks_sconv
from benchmark.metrics import _sconv, sconv_decode_ms


def read(run):
    ms = sconv_decode_ms.read(run)
    if ms is None or run.peaks is None or not _sconv.is_mix(run):
        return None
    rows = len(run.steps[-1]["seq_lens"])
    floor_s = peaks_sconv.sconv_decode_bytes(run.model_cfg, rows) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

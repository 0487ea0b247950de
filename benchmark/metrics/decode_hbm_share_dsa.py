"""`decode_hbm_share` for dots3_note: the time the published HBM bandwidth
allows one decode iteration (`peaks_dsa.decode_bytes`: every weight once,
the experts the rows touch, each row's visible index keys, its SELECTED
latent rows and its live ring at the rows' mean contexts, the rows'
logits) as a share of the device time of an iteration under
`gen/decode_step`, in %.  Not of `decode_loop_ms`: in a cell of 13 k-token
prompts the generate request's longest loop is the prefill's."""
from benchmark import peaks_dsa
from benchmark.metrics import _dsa


def read(run):
    ms = _dsa.decode_ms(run)
    if ms is None or run.peaks is None:
        return None
    floor_s = peaks_dsa.decode_bytes(run.model_cfg, _dsa.contexts(run)) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

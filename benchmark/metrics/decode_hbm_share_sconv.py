"""`decode_hbm_share` for a short-convolution / attention mix: the time the
published HBM bandwidth allows one decode step (`peaks_sconv.decode_bytes`:
the conv layers' weights and tails, the attention layers' weights and K/V
at every row's mean context, the leading dense MLPs, the held experts the
program counted as touched, router, head) as a share of `decode_loop_ms`,
in %."""
from benchmark import peaks_sconv
from benchmark.metrics import _sconv, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None or not _sconv.is_mix(run):
        return None
    floor_s = peaks_sconv.decode_bytes(
        run.model_cfg, _sconv.mean_contexts(run.steps[-1]),
        _sconv.experts_touched(run), _sconv.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

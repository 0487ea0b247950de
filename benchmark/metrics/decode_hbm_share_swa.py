"""`decode_hbm_share` for a window / full mix: the time the published HBM
bandwidth allows one decode step (`peaks_swa.decode_bytes`: every layer's
attention weights, a ring's live entries and a full layer's K/V at every
row's mean context, the held experts the program counted as touched,
router, head) as a share of `decode_loop_ms`, in %."""
from benchmark import peaks_swa
from benchmark.metrics import _swa, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None or not _swa.is_mix(run):
        return None
    floor_s = peaks_swa.decode_bytes(
        run.model_cfg, _swa.mean_contexts(run.steps[-1]),
        _swa.experts_touched(run), _swa.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

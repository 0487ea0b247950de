"""`decode_hbm_share` for latent attention with a rank's share of the
experts: the time the published HBM bandwidth allows one decode step
(`peaks_mla.decode_bytes`: attention weights and each row's latent rows
at its mean context, the held experts the program counted as touched,
shared expert, router, the leading dense MLP, head) as a share of
`decode_loop_ms`, in %."""
from benchmark import peaks_mla
from benchmark.metrics import _mla, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None or not _mla.is_latent(run):
        return None
    floor_s = peaks_mla.decode_bytes(
        run.model_cfg, _mla.contexts(run.steps[-1]),
        _mla.experts_touched(run), _mla.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

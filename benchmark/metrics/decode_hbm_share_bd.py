"""`decode_hbm_share` for generation by diffusion over blocks: the time the
published HBM bandwidth allows ONE denoising forward
(`peaks_bd.forward_bytes`: attention weights, the experts the rows' B
tokens touch, the K and V of each row's context and block at the rows'
mean contexts, the head and the logits) as a share of the device time of
one (`bd_denoise_ms`), in %."""
from benchmark import peaks_bd
from benchmark.metrics import _bd, bd_denoise_ms
from benchmark.metrics._hybrid import experts_touched, local_rows


def read(run):
    ms = bd_denoise_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    floor_s = peaks_bd.forward_bytes(
        run.model_cfg, _bd.contexts(run), experts_touched(run),
        local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

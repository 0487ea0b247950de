"""Device milliseconds one DENOISING forward of the block loop takes — the
scope `gen/bd_denoise`: B tokens a row through every layer and the head —
over the denoising forwards the generate call made (`bd/denoise_forwards`),
mean over chips.  Traced run; None without the scope or the counter."""
from benchmark.metrics import _bd
from benchmark.metrics._program import scope_seconds


def read(run):
    if not _bd.is_bd(run):
        return None
    seconds, n = scope_seconds(run, "gen/bd_denoise"), _bd.forwards(
        run, "denoise")
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n

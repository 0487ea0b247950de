"""FLOPs the flash kernels spend on the live tiles of a train step
(`peaks_swa.flash_tile_flops`: both kinds' schedules as the trainer
counted them for its last minibatch, scaled to the step's trained tokens;
the forward kernel as many times as the trace shows it running, the
recomputation included, since its seconds are) over the device seconds of
`flash_fwd` + `flash_dq` + `flash_dkv` under `train/grad`, at the
published bf16 peak, in %."""
from benchmark import peaks_swa
from benchmark.metrics import _swa
from benchmark.metrics._program import scope_seconds

TRAIN = "train/grad"


def read(run):
    if run.peaks is None or not _swa.is_mix(run) or not run.steps:
        return None
    parts = [scope_seconds(run, TRAIN, k)
             for k in ("flash_fwd", "flash_dq", "flash_dkv")]
    pack = run.steps[-1]["pack"]
    if None in parts or "flash_live_tiles_window" not in pack:
        return None
    fwd_runs = sum(
        scope_seconds(run, TRAIN, "flash_fwd", phase=ph) is not None
        for ph in ("fwd", "recompute"))
    scale = sum(run.steps[-1]["seq_lens"]) / max(pack["real_tokens"], 1)
    flops = scale * peaks_swa.flash_tile_flops(
        run.model_cfg, pack["flash_live_tiles"],
        pack["flash_live_tiles_window"], fwd_runs)
    return 100.0 * flops / sum(parts) / (
        run.chips * run.peaks["bf16_flops"])

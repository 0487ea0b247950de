"""FLOPs the flash kernels spend on the live tiles of a train step at
heads of 64 (`peaks_sconv.flash_tile_flops`: the schedule as the trainer
counted it for its last minibatch, scaled to the step's trained tokens;
the forward kernel as many times as the trace shows it running, the
recomputation included, since its seconds are) over the device seconds of
`flash_fwd` + `flash_dq` + `flash_dkv` under `train/grad`, at the
published bf16 peak, in %."""
from benchmark import peaks_sconv
from benchmark.metrics import _sconv
from benchmark.metrics._program import scope_seconds

TRAIN = "train/grad"


def read(run):
    if run.peaks is None or not _sconv.is_mix(run) or not run.steps:
        return None
    parts = [scope_seconds(run, TRAIN, k)
             for k in ("flash_fwd", "flash_dq", "flash_dkv")]
    pack = run.steps[-1]["pack"]
    if None in parts or "flash_live_tiles" not in pack:
        return None
    fwd_runs = sum(
        scope_seconds(run, TRAIN, "flash_fwd", phase=ph) is not None
        for ph in ("fwd", "recompute"))
    scale = sum(run.steps[-1]["seq_lens"]) / max(pack["real_tokens"], 1)
    flops = scale * peaks_sconv.flash_tile_flops(
        run.model_cfg, pack["flash_live_tiles"], fwd_runs)
    return 100.0 * flops / sum(parts) / (
        run.chips * run.peaks["bf16_flops"])

"""`moe_decode_mlp_roofline` for generation by diffusion over blocks: the
time the published HBM bandwidth allows the MoE layers' MLPs of ONE forward
of the block loop — `n_layers x peaks_bd.moe_layer_parts` at the rows' B
tokens and at the experts and local rows the program COUNTED — as a share
of `moe_bd_mlp_ms`, in %."""
from benchmark import peaks_bd
from benchmark.metrics import moe_bd_mlp_ms
from benchmark.metrics._hybrid import experts_touched, local_rows


def read(run):
    ms = moe_bd_mlp_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    cfg = run.model_cfg
    tokens = len(run.steps[-1]["seq_lens"]) * cfg.block_length
    floor_s = cfg.n_layers * sum(
        by for _, by in peaks_bd.moe_layer_parts(
            cfg, tokens, experts_touched(run), local_rows(run)).values()
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

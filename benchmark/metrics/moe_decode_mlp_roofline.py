"""The time the published HBM bandwidth allows the MoE layers' MLPs of one
decode step — `n_layers x peaks.moe_layer_bytes` at this step's rows and
at the experts the program COUNTED as touched (`moe_experts_touched`), not
the uniform-routing expectation — as a share of `moe_decode_mlp_ms`, in %.
Bandwidth-bound: at 8 rows the expert weights are 99% of the bytes and the
FLOPs are nothing."""
from benchmark import peaks
from benchmark.metrics import moe_decode_mlp_ms, moe_experts_touched


def read(run):
    mlp_ms = moe_decode_mlp_ms.read(run)
    touched = moe_experts_touched.read(run)
    if mlp_ms is None or touched is None or run.peaks is None:
        return None
    cfg, rows = run.model_cfg, len(run.steps[-1]["seq_lens"])
    floor_s = cfg.n_layers * peaks.moe_layer_bytes(
        cfg, rows, experts_touched=touched
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / mlp_ms

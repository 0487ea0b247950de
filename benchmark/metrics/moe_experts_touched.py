"""Experts of a MoE layer with at least one row, mean over the decode
steps and the MoE layers of a generate call (the generator's counter
`last_pool_stats["moe_experts_touched"]`, summed on the device inside the
decode loop), median step.  What the decode step HAD to read of the expert
weights: `moe_decode_mlp_roofline` takes it; uniform routing at 8 rows of
8-of-64 would give 42.0 (`peaks.experts_expected`)."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(run, "pool", lambda p: p["moe_experts_touched"])

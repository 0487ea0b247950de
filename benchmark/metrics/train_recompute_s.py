"""Device seconds per step of the forward pass RECOMPUTED inside the
backward (scope `train/grad` under `jax.checkpoint`'s
`rematted_computation`), mean over chips: what `remat` costs."""
from benchmark.metrics._program import scope_seconds


def read(run):
    return scope_seconds(run, "train/grad", phase="recompute")

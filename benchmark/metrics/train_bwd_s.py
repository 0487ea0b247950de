"""Device seconds per step of the train step's BACKWARD pass (scope
`train/grad` under `transpose(jvp(...))`, the recomputation excluded),
mean over chips."""
from benchmark.metrics._program import scope_seconds


def read(run):
    return scope_seconds(run, "train/grad", phase="bwd")

"""Device seconds per step of the train step's FORWARD pass (scope
`train/grad`, operations neither under `rematted_computation` nor under a
`transpose(jvp(...))`), mean over chips."""
from benchmark.metrics._program import scope_seconds


def read(run):
    return scope_seconds(run, "train/grad", phase="fwd")

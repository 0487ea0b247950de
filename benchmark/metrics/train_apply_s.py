"""Device seconds per step of the optimizer's apply (scope `train/apply`:
clipping, Adam, the new weights), mean over chips."""
from benchmark.metrics._program import scope_seconds


def read(run):
    return scope_seconds(run, "train/apply")

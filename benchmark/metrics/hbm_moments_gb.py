"""The optimizer's state on the peak's device (`hbm/moments_gb`: Adam's
two moments and its count), at the warm-up step's close, in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "moments_gb")

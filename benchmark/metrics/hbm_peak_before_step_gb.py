"""The device's peak at the first request's open
(`hbm/peak_before_step_gb`: the build and whatever ran between `built`
and the step), in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "peak_before_step_gb")

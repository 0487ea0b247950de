"""Live arrays on the peak's device that no engine owns
(`hbm/other_live_gb`: `jax.live_arrays()` less weights, moments and
cache), at the warm-up step's close, in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "other_live_gb")

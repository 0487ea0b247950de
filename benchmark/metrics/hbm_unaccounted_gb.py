"""The warm-up step's peak less weights, moments, cache, other live
arrays, code and temporaries (`hbm/unaccounted_gb`: the remainder row of
the account, whatever its sign), in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "unaccounted_gb")

"""What the generator keeps between calls beside its weights
(`hbm/cache_gb`: a parked session's page pool, recurrent state and
buffers; 0.0 where every call runs to its end and frees its cache), at
the warm-up step's close, in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "cache_gb")

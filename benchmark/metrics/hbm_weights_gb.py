"""Weights the engines keep on the peak's device between calls
(`hbm/weights_gb`: the trainer's masters and, where it holds a tree of its
own, the generator's copy; buffers the two share are counted once), at
the warm-up step's close, in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "weights_gb")

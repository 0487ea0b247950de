"""Median seconds of the train_step request (worker boundary)."""
from benchmark.metrics._labels import TRAIN


def read(run):
    return run.span_median(TRAIN)

"""Median seconds of the generate request (worker boundary)."""
from benchmark.metrics._labels import GEN


def read(run):
    return run.span_median(GEN)

"""Median seconds of the reward (verifier) request."""
from benchmark.metrics._labels import REWARD


def read(run):
    return run.span_median(REWARD)

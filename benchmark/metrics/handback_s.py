"""Median seconds of the weight hand-back request, the colocated
`param_sync`.  A re-layout that only enqueues device copies returns
early; those copies then show in `breakdown` and in the next generate."""
import statistics

from benchmark.metrics._labels import handback


def read(run):
    return statistics.median(handback(s) for s in run.steps)

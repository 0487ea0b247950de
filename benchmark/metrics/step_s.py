"""Median wall seconds of a whole timed step (benchmark clock)."""
import statistics


def read(run):
    return statistics.median(s["wall_s"] for s in run.steps)

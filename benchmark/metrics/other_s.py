"""Median of step wall minus generate, reward, train and hand-back:
the master/worker plane's own overhead and data movement."""
import statistics

from benchmark.metrics._labels import GEN, REWARD, TRAIN, handback


def read(run):
    return statistics.median(
        s["wall_s"] - sum(s["spans"].get(k, 0.0) for k in (GEN, REWARD, TRAIN))
        - handback(s)
        for s in run.steps
    )

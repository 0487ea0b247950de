"""Trained tokens (prompt + response) over the seconds of the train
request, per chip: the median over the timed steps."""
import statistics

from benchmark.metrics._labels import TRAIN


def read(run):
    return statistics.median(
        sum(s["seq_lens"]) / s["spans"][TRAIN] for s in run.steps
    ) / run.chips

"""Trained tokens (prompt + response) over the seconds of the train
request, per chip: the median over the timed steps — a fixed count of
them, on the configuration's own weights and the cell's own rows, where
their files say so (`timed_steps`, `traffic_seed`, `weights_seed`:
`benchmark/run.py`)."""
import statistics

from benchmark.metrics._labels import TRAIN


def read(run):
    return statistics.median(
        sum(s["seq_lens"]) / s["spans"][TRAIN] for s in run.steps
    ) / run.chips

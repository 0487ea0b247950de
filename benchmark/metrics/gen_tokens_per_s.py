"""Generated tokens over the seconds of the generate request, per chip:
the median over the timed steps.  Where a step's pace follows the weights
and the tokens and drifts with the updates (`olmoe-decode-tail`), the cell's
`timed_steps` and `traffic_seed` and its configuration's `weights_seed`
make those the same steps of the same trajectory in every run
(`benchmark/run.py`)."""
import statistics

from benchmark.metrics._labels import GEN


def read(run):
    return statistics.median(
        run.gen_tokens(s) / s["spans"][GEN] for s in run.steps
    ) / run.chips

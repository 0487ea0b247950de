"""Generated tokens over the seconds of the generate request, per chip:
the median over the timed steps."""
import statistics

from benchmark.metrics._labels import GEN


def read(run):
    return statistics.median(
        run.gen_tokens(s) / s["spans"][GEN] for s in run.steps
    ) / run.chips

"""Device time of collective operations (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute) over the traced window, mean
over chips, in %.  Hidden or exposed is not told apart here."""
from benchmark import trace


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    return 100.0 * trace.op_seconds_matching(run.trace, trace.COLLECTIVES) / run.trace["window_s"]

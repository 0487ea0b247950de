"""1 - (union of device-operation intervals) / window over the traced
steady steps, mean over chips, in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

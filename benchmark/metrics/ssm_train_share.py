"""Device seconds of the train step's gradient program under the scope
`layer/ssm` (forward, recomputed forward and backward of every Mamba-2
mixer: projections, conv, the chunked scan, the gated norm) over all of
`train/grad`'s, in %."""
from benchmark.metrics._program import scope_seconds


def read(run):
    mixer = scope_seconds(run, "train/grad", "layer/ssm")
    whole = scope_seconds(run, "train/grad")
    if mixer is None or whole is None:
        return None
    return 100.0 * mixer / whole

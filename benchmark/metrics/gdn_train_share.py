"""Device seconds of the train step's gradient program under the scope
`layer/linear_attn` (forward, recomputed forward and backward of every
Gated DeltaNet mixer: projections, conv, gates, the chunked delta rule,
the gated norm) over all of `train/grad`'s, in %."""
from benchmark.metrics._program import scope_seconds


def read(run):
    mixer = scope_seconds(run, "train/grad", "layer/linear_attn")
    whole = scope_seconds(run, "train/grad")
    if mixer is None or whole is None:
        return None
    return 100.0 * mixer / whole

"""Device seconds of the train step's gradient program under the scope
`layer/lightning` (forward, recomputed forward and backward of every
Lightning mixer: projections, rope, the chunked recurrence, the output
norm and gate) over all of `train/grad`'s, in %."""
from benchmark.metrics import _sala


def read(run):
    return _sala.train_share(run, "layer/lightning")

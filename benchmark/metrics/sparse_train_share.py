"""Device seconds of the train step's gradient program under the scope
`layer/sparse_attn` (forward, recomputed forward and backward of the
block-sparse layers: compressed keys, selection, attention under the
choice) over all of `train/grad`'s, in %."""
from benchmark.metrics import _sala


def read(run):
    return _sala.train_share(run, "layer/sparse_attn")

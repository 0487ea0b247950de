"""Device seconds of the train step's gradient program under the scope
`layer/latent_attn` (forward, recomputed forward and backward of the full
layers: projections, the indexer's scores and selection in blocks of
queries, the attention under the mask) over all of `train/grad`'s, in %."""
from benchmark.metrics import _dsa


def read(run):
    return _dsa.train_share(run, "layer/latent_attn")

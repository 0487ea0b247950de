"""Device milliseconds per decode-loop iteration in the full layers' token
indexer (scope `layer/latent_attn/indexer` under `gen/decode_step`: its
projections, the scores against the row's index keys, the top-k), mean over
chips.  Static-route cells of a plan with a token indexer, traced run."""
from benchmark.metrics import _dsa


def read(run):
    return _dsa.decode_ms(run, "layer/latent_attn/indexer")

"""Device milliseconds per decode-loop iteration in the block-sparse
layers' selection and attention (scope `layer/sparse_attn` under
`gen/decode_step`: the new compressed key, the scores against the
compressed keys, the top-k, the gather of the chosen blocks and the
attention over them), mean over chips.  Static-route cells of a plan with
block-sparse layers, traced run."""
from benchmark.metrics import _sala


def read(run):
    return _sala.decode_ms(run, "layer/sparse_attn")

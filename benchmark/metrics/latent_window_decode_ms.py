"""Device milliseconds per decode-loop iteration in the sliding layers
(scope `layer/latent_window` under `gen/decode_step`: projections, the
ring's write, the absorbed attention over its live rows, gate and o),
mean over chips.  Static-route cells with latent window layers, traced."""
from benchmark.metrics import _dsa


def read(run):
    return _dsa.decode_ms(run, "layer/latent_window")

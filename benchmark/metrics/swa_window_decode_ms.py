"""`swa_decode_ms` of the sliding-window layers alone: the operations
under the inner scope `window` of the three attention scopes (projections
and per-head norms, the ring write and the attention over the ring, the
output projection)."""
from benchmark.metrics import swa_decode_ms


def read(run):
    return swa_decode_ms.read(run, "window")

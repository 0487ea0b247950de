"""Of the block loop's device time (`_bd.loop_seconds`), the share of the
slices that copy a layer's K and V out of the stacked cache in front of
each forward's attention (`_bd.cache_copy_seconds`: told by their result,
[1, rows, slots, key heads, head width], directly under a forward's scope),
in %: bytes the attention then reads a second time.  0 where the layer's
K and V are read in place.  Traced run."""
from benchmark.metrics import _bd


def read(run):
    whole, part = _bd.loop_seconds(run), _bd.cache_copy_seconds(run)
    if not whole or part is None:
        return None
    return 100.0 * part / whole

"""Device seconds of the train step's gradient program under the scope
`layer/sconv` (forward, recomputed forward and backward of every gated
short convolution: in_proj, the gates, the conv, out_proj) over all of
`train/grad`'s, in %."""
from benchmark.metrics import _sconv
from benchmark.metrics._program import scope_seconds


def read(run):
    mixer = scope_seconds(run, "train/grad", _sconv.SCOPE)
    whole = scope_seconds(run, "train/grad")
    if mixer is None or whole is None:
        return None
    return 100.0 * mixer / whole

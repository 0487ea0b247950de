"""Device seconds of the train step's gradient program under the three
attention scopes (`layer/attn_qkv`, `layer/attn`, `layer/attn_out`:
forward, recomputed forward and backward of every layer's projections,
norms, rope and flash kernels, window and full) over all of `train/grad`'s,
in %."""
from benchmark.metrics import _swa
from benchmark.metrics._program import scope_seconds


def read(run):
    whole = scope_seconds(run, "train/grad")
    if whole is None or not _swa.is_mix(run):
        return None
    attn = _swa.attn_seconds(run, "train/grad")
    return None if attn is None else 100.0 * attn / whole

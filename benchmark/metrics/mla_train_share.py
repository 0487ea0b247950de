"""Device seconds of the train step's gradient program under latent
attention's scopes (`layer/attn_qkv`, `layer/attn`, `layer/attn_out`:
forward, recomputed forward and backward of the low-rank projections, the
up-projection of keys and values, the flash kernels, the output
projection) over all of `train/grad`'s, in %."""
from benchmark.metrics import _mla
from benchmark.metrics._program import scope_seconds


def read(run):
    attn = _mla.attn_seconds(run, "train/grad")
    whole = scope_seconds(run, "train/grad")
    if attn is None or whole is None:
        return None
    return 100.0 * attn / whole

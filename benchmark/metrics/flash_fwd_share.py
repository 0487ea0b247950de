"""Device time of the flash-attention FORWARD kernel (`flash_fwd`: the
train step's forward, its recomputation under `jax.checkpoint`, and
prefill's) over device busy time, in %.  With `flash_bwd_share` it adds
up to `flash_time_share` wherever no other Mosaic kernel runs."""
from benchmark.metrics._program import kernel_share


def read(run):
    return kernel_share(run, ("flash_fwd",))

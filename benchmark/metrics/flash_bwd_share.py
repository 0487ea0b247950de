"""Device time of the flash-attention BACKWARD kernels (`flash_dq` and
`flash_dkv`) over device busy time, in %."""
from benchmark.metrics._program import kernel_share


def read(run):
    return kernel_share(run, ("flash_dq", "flash_dkv"))

"""Code of every program loaded on the peak's device
(`hbm/code_gb`: the sum of `generated_code_size_in_bytes` over
`client.live_executables()`), at the warm-up step's close, in GB."""
from benchmark.metrics import _hbm


def read(run):
    return _hbm.first(run, "code_gb")

"""Device time of the flash-attention kernels (forward and backward) over
device busy time, from the trace, in %.  The trace gives a Pallas kernel
no name (`custom-call`, target `tpu_custom_call`), so this is the time of
ALL Mosaic kernels; on the default path (train step and prefill, decode
kernels off) those are the flash forward, dq and dkv kernels and nothing
else.  A name per kernel needs `jax.named_scope` inside the program."""
from benchmark import trace

PATTERNS = ("tpu_custom_call",)


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace.op_seconds_matching(run.trace, PATTERNS) / run.trace["busy_s"]

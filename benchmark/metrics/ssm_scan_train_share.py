"""Device seconds of the train step's gradient program under the scope
`layer/ssm/ssd_scan` (forward, recomputed forward and backward of every
Mamba-2 mixer's chunked recurrence alone: the `jnp` form's `[C, C]` blocks
and scan, or the Pallas sweep `ssd_chunk_fwd` / `ssd_chunk_bwd` with the
slices, the running sum and the D skip around it) over all of
`train/grad`'s, in %."""
from benchmark.metrics._program import scope_seconds


def read(run):
    scan = scope_seconds(run, "train/grad", "layer/ssm/ssd_scan")
    whole = scope_seconds(run, "train/grad")
    if scan is None or whole is None:
        return None
    return 100.0 * scan / whole

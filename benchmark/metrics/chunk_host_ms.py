"""Host milliseconds per serving chunk spent OUTSIDE the compiled chunk:
admission, page reservation and copy-on-write before it, the copies to
the host and the drain after it (`last_pool_stats["chunk_host_s"]` over
`["chunks"]`, the seconds of the program's `chunk_host` spans), median
step.  The device waits through all of it."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool", lambda p: 1e3 * p["chunk_host_s"] / p["chunks"]
    )

"""Programs compiled AND written to the persistent cache, process start
to the end of the warm-up step (`setup/cache_misses`): programs the cache
should have held.  Over 0 in a warm run means the machine's cache did not
keep them (PERF.md section 2: 160-180 MiB kept, one gradient program 186
MB); `setup_programs`' table on stderr names them."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/cache_misses")

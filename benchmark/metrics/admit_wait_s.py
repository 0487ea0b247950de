"""Mean seconds from the start of `generate()` to a request's admission
to a decode slot (`last_pool_stats["admit_wait_mean_s"]`), median step:
what the requests beyond the slots wait for a retirement."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(run, "pool", lambda p: p["admit_wait_mean_s"])

"""Times an admission round of the serving plane passed over a queued
request whose prefix owner was still prefilling (`last_pool_stats
["admit_passed_over"]`, per `generate()`), median step."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(run, "pool", lambda p: float(p["admit_passed_over"]))

"""Bytes of the generator's weights as placed by the hand-back (global,
from shapes) over the seconds its handler took on the worker
(`actor_gen/sync/bytes` over `actor_gen/sync/time_s` of the step's
stats), in GB/s, median step."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "stats",
        lambda st: st["actor_gen/sync/bytes"] / st["actor_gen/sync/time_s"] / 1e9,
    )

"""Share of the timed wall seconds beyond what steps of the median length
would have taken, in %: what sporadic stalls (host pauses, allocator
waits) cost.  The end-to-end rates are taken at the median step and do
not see it."""
import statistics


def read(run):
    walls = [s["wall_s"] for s in run.steps]
    return 100.0 * max(0.0, sum(walls) - len(walls) * statistics.median(walls)) / sum(walls)

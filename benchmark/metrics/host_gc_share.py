"""Seconds inside the cyclic collector (`gc.callbacks`, step stats
`host/gc_s`) over the timed wall seconds, in %."""
from benchmark.metrics._host import share_of_wall


def read(run):
    return share_of_wall(run, "host/gc_s")

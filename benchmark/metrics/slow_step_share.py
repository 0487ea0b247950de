"""Seconds by which the steps the PROGRAM itself flagged as slow (wall
over the median of its last eight by max(0.1 s, 3%); step stats
`time/slow_excess_s`, 0 for a step not flagged) ran over that median,
over the timed wall seconds, in %: the program's own reading of what
`stall_share` sees from outside.  Each such step has a `slow_step` flight
event naming the spans that grew and the host's record."""
from benchmark.metrics._host import share_of_wall


def read(run):
    return share_of_wall(run, "time/slow_excess_s")

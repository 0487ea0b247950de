"""Seconds the PROGRAM's own ticker (`areal_tpu/base/hostwatch.py`: sleeps
20 ms, late past 100 ms; step stats `host/late_s`) woke late, over the
timed wall seconds, in %.  The inside twin of `host_pause_share`, which
the benchmark's ticker measures by the same rule: the two agree, and this
one's pauses each have a `host_pause` flight event naming the spans open
and every thread's frame."""
from benchmark.metrics._host import share_of_wall


def read(run):
    return share_of_wall(run, "host/late_s")

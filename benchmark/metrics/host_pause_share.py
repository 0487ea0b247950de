"""Seconds in which a thread of this process that sleeps 20 ms at a time
woke more than 100 ms late (the process was not scheduled, or one thread
kept the interpreter lock: a collection, a long C call) over the timed
wall seconds, in %.  Beside `stall_share` it says whether a stalled step
was stalled on the host."""


def read(run):
    return 100.0 * run.total(lambda s: s["host"]["late_s"]) / run.total(
        lambda s: s["wall_s"]
    )

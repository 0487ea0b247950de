"""Thread-seconds this process's threads were runnable but not running
(`/proc/self/task/*/schedstat`, step stats `host/runq_wait_s`) over the
timed wall seconds, in %.  Over all threads, so it can pass 100; a pause
with this beside it is the host not scheduling the process, one without
it a thread of the process holding the interpreter lock.  0.0 also where
the host's kernel keeps no schedstat (the chip machine's does not: PERF.md
section 6, PR 36), so there it says nothing either way."""
from benchmark.metrics._host import share_of_wall


def read(run):
    return share_of_wall(run, "host/runq_wait_s")

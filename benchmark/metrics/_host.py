"""What the readers of the program's OWN host watch and step ledger share
(PR 36: `host/<key>` and `time/slow_excess_s` in every step's stats,
written by `tracer.close_step` with no switch).  A share is a sum over
the timed steps over the timed wall seconds, in %: 0.0 where nothing
happened, None (the line leaves the metric out) where the program under
test has no host watch at all, which `host/late_s` in the step stats
tells.  A key the watch leaves out because this host's kernel keeps no
such file (`runq_wait_s` without `/proc/self/task/*/schedstat`, as on the
chip machine's sandboxed kernel) reads 0.0: nothing could be seen."""


def share_of_wall(run, key):
    if not run.steps or any("host/late_s" not in s["stats"] for s in run.steps):
        return None
    return 100.0 * run.total(lambda s: s["stats"].get(key, 0.0)) / run.total(
        lambda s: s["wall_s"]
    )

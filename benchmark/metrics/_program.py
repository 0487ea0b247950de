"""What the readers of the PROGRAM's own names and counters share (PR 23:
kernel names on the device, `last_pool_stats` / `last_pack_stats` /
step-stats keys).  Every reader returns None, and never raises, where the
program under test does not keep the name or counter yet."""
import re
import statistics


def kernel_share(run, kernels):
    """Device self time of the Mosaic kernels called `kernels` over busy
    time, in %.  A Pallas kernel's instruction carries the kernel's name
    (`%flash_dkv.10 = ... custom-call(...)`), which `trace.short_op_name`
    keeps as the first word of the operation's name.  None where the
    trace names no `flash_*` kernel at all (a program without the names)."""
    if run.trace is None:
        return None
    ops = run.trace["op_seconds"]
    if not any(re.match(r"flash_\w+?(\.\d+)? ", name) for name in ops):
        return None
    mine = re.compile(r"(%s)(\.\d+)? " % "|".join(kernels))
    seconds = sum(s for name, s in ops.items() if mine.match(name))
    return 100.0 * seconds / run.trace["busy_s"]


def step_median(run, group, fn):
    """Median over the timed steps of fn(step[group]), over the steps
    whose record has what fn reads."""
    vals = []
    for s in run.steps:
        try:
            vals.append(fn(s[group]))
        except (KeyError, ZeroDivisionError):
            pass
    return statistics.median(vals) if vals else None

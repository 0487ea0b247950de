"""What the readers of the PROGRAM's own names and counters share (PR 23:
kernel names and `jax.named_scope`s on the device, `last_pool_stats` /
`last_pack_stats` / step-stats keys).  Every reader returns None, and
never raises, where the program under test does not keep the name or
counter yet.

A per-layer metric for a mechanism that is not a named kernel — the MoE
block is `ragged_dot`s, a router, a gather and a scatter under the scope
`layer/mlp` — is a file of three lines over `scope_seconds` or
`scope_share`:

    from benchmark.metrics._program import scope_share
    def read(run):
        return scope_share(run, "gen/decode_step", "layer/mlp")
"""
import re
import statistics

from benchmark import program_trace


def _scope_total(run, needles, phase):
    """Seconds over the whole traced window, or None where the run was
    not traced or no operation ran under such a scope."""
    if not run.trace or not run.trace.get("scope_seconds"):
        return None
    total = program_trace.scope_total(run.trace, *needles, phase=phase)
    return total if total > 0 else None


def scope_seconds(run, *needles, phase=None):
    """Device self seconds PER TRACED STEP, mean over chips, of the
    operations whose scope holds every needle as a run of elements
    (`"train/grad"`, `"layer/mlp"`), in one phase (`"fwd"`, `"recompute"`,
    `"bwd"`) or in all.  None where the run was not traced or no
    operation ran under such a scope."""
    total = _scope_total(run, needles, phase)
    return None if total is None else total / run.trace["traced_steps"]


def scope_share(run, *needles, phase=None):
    """The same operations' seconds over the device's busy time, in %."""
    total = _scope_total(run, needles, phase)
    return None if total is None else 100.0 * total / run.trace["busy_s"]


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

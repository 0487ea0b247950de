"""What the readers of the program's HBM ledger share (PR 66: the
`hbm/<key>` stats `tracer.close_step` returns -- `in_use_gb`, `peak_gb`
and `peak_rise_gb` from every step, and from a process's FIRST close
alone, which is the warm-up step here, the account of the peak: the
owners' rows, `code_gb`, `temp_gb`, `unaccounted_gb`,
`peak_before_step_gb`).  The trial runs under one roof, so the keys are
the master's own.  None (the line leaves the metric out) where the
program under test keeps no such ledger, as the parent of PR 66 does
not."""
from benchmark.metrics import _setup


def first(run, key):
    """`hbm/<key>` of the warm-up step, in GB."""
    return _setup.stats(run).get("hbm/" + key)

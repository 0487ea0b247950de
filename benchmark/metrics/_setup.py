"""What the readers of the program's set-up ledger share (PR 51: the
`setup/<key>` stats `tracer.close_step` returns from a process's FIRST
step alone, which is the warm-up step here, and the `perf/trace_s`,
`perf/lower_s`, `perf/compile_s` of every MFC reply).  A worker in a
process of its own replies `<node>/setup/<key>`, as every MFC replies
`<node>/perf/<key>`; nodes are summed.  None (the line leaves the metric
out) where the program under test keeps no such ledger, as the parent of
PR 51 does not."""


def stats(run):
    return (run.warmup or {}).get("stats", {})


def total(run, *keys):
    """The warm-up step's stats `<key>` and `<node>/<key>`, summed over
    nodes and keys; None where any one key is nowhere."""
    out = 0.0
    for key in keys:
        found = [
            v for k, v in stats(run).items()
            if k == key or k.endswith("/" + key)
        ]
        if not found:
            return None
        out += sum(found)
    return out

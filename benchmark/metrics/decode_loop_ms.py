"""Device milliseconds per iteration of the static decode program's loop:
the longest outermost `while` inside each traced generate request (the
`lax.while_loop` over the new tokens, whole, sampling included; prefill
and the host are outside it) over the decode steps run, the median over
the traced requests, mean over chips.  Static-route cells, traced run."""
import statistics

from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._labels import GEN


def read(run):
    loops = (run.trace or {}).get("loop_seconds", {}).get(GEN)
    if not loops or run.cell["route"] != "static":
        return None
    return 1e3 * statistics.median(loops) / decode_ms_per_step.steps_run(
        run.steps[-1]
    )

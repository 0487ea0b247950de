"""Device milliseconds per decode-loop iteration in the Mamba-2 mixers of
`gen/decode_step` (scope `layer/ssm`: in_proj, conv, ssm_step,
out_norm_proj), all Mamba layers of one step together, mean over chips.
Static-route cells of a pattern with 'M' layers, traced run."""
from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "gen/decode_step", "layer/ssm")
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

"""Device milliseconds per decode-loop iteration in the gated short
convolutions of `gen/decode_step` (scope `layer/sconv`: in_proj, conv,
out_proj), all conv layers of one step together, mean over chips.
Static-route cells of a plan with such layers, traced run."""
from benchmark.metrics import _sconv, decode_ms_per_step
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "gen/decode_step", _sconv.SCOPE)
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

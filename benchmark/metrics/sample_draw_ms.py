"""Device milliseconds per decode step under the scope `sample_draw` — the
sampler's inverse-CDF draw (`ops/sampling._inverse_cdf_draw`: the masses,
their scans, the pick and the drawn token's log-probability), in the
static loop and in the serving chunk's inner step — mean over chips.
Traced run; None where the program has no such scope."""
from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "sample_draw")
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

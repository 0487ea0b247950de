"""Device milliseconds per decode-loop iteration in the Gated DeltaNet
mixers of `gen/decode_step` (scope `layer/linear_attn`: in_proj, conv,
gates, delta_step, out_norm_proj), all linear layers of one step together,
mean over chips.  Static-route cells of a hybrid config, traced run."""
from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._program import scope_seconds


def read(run):
    seconds = scope_seconds(run, "gen/decode_step", "layer/linear_attn")
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

"""Device milliseconds per decode-loop iteration in the attention branches
of `gen/decode_step` (scopes `layer/attn_qkv`, `layer/attn`,
`layer/attn_out`), window and full layers together, all layers of one step,
mean over chips.  Static-route cells of a window / full mix, traced run."""
from benchmark.metrics import _swa, decode_ms_per_step


def read(run, *inner):
    if not _swa.is_mix(run):
        return None
    seconds = _swa.attn_seconds(run, "gen/decode_step", *inner)
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

"""Device milliseconds an inner step of the serving chunk in the Mamba-2
mixers (scope `layer/ssm` under `gen/serving_chunk`: in_proj, the ragged
recurrence `ssm_ragged` with its conv and its scan, out_norm_proj), all
Mamba layers of one step together, mean over chips.  Serving-route cells
of a plan with Mamba-2 layers, traced run."""
from benchmark.metrics import _ssmd


def read(run):
    return _ssmd.chunk_ms(run, "layer/ssm")

"""Device milliseconds per decode-loop iteration in the MoE MLPs of
`gen/decode_step` — the scope `layer/mlp` (router, sort and gather,
activation, scatter) plus XLA's ragged-dot kernels of the decode program,
which carry no scope (`_moe.py`) — all MoE layers of one step together,
mean over chips.  Static-route cells of a MoE config, traced run."""
from benchmark.metrics import _moe, decode_ms_per_step


def read(run):
    seconds = _moe.mlp_seconds(run, _moe.DECODE)
    if seconds is None or not getattr(run.model_cfg, "n_experts", 0):
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

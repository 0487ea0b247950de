"""Of the decode step's device time in the MoE MLPs (`moe_decode_mlp_ms`:
the scope `layer/mlp` plus the ragged-dot kernels), the share under the
scopes `router` (logits, softmax, top-k, load-balancing loss), `dispatch`
(sort by expert, group sizes, gather) and `combine` (router weights,
scatter-add) — everything but the expert matmuls and their activation —
in %: what sparsity costs beside the weights it saves."""
from benchmark.metrics import _moe
from benchmark.metrics._program import scope_seconds


def read(run):
    whole = _moe.mlp_seconds(run, _moe.DECODE)
    parts = [
        scope_seconds(run, _moe.DECODE, f"layer/mlp/{part}")
        for part in ("router", "dispatch", "combine")
    ]
    if whole is None or all(p is None for p in parts):
        return None
    return 100.0 * sum(p or 0.0 for p in parts) / whole

"""Device seconds of the train step's gradient program under the scope
`layer/post_norm` (the norms on a residual branch's OUTPUT, `cfg.branch_norm`
"output": forward, recomputed forward and backward) over all of
`train/grad`'s, in %.  None where the run was not traced or no operation
ran under the scope (every plan with its norms on the input)."""
from benchmark.metrics._program import scope_seconds


def read(run):
    norms = scope_seconds(run, "train/grad", "layer/post_norm")
    whole = scope_seconds(run, "train/grad")
    if norms is None or whole is None:
        return None
    return 100.0 * norms / whole

"""What the readers of a hybrid configuration's MoE share: the program's
own counts of one decode step's work in a layer's MLP, for
`benchmark/peaks_hybrid.py` to count bytes by."""
from benchmark.metrics._program import step_median


def experts_touched(run):
    """Held experts of a layer with at least one row, mean over the decode
    steps and layers of a generate call (median step); None without the
    counter (the arithmetic then takes its expectation)."""
    return step_median(run, "pool", lambda p: p["moe_experts_touched"])


def local_rows(run):
    """(row, choice) pairs of one decode step that fell to experts held
    here, per layer; None without the counters."""
    n_layers = run.model_cfg.n_layers
    return step_median(
        run, "pool",
        lambda p: p["moe_rows_local"] / (p["moe_decode_steps"] * n_layers),
    )

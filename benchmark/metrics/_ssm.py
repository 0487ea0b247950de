"""What the readers of a pattern of one-branch layers share (nemotron_h):
whether the run's model is one, and the program's own counts of one decode
step's work in an expert layer, for `benchmark/peaks_ssm.py` to count
bytes by.  Every reader returns None for another model and for a program
that does not know the pattern."""
from benchmark.metrics._hybrid import experts_touched  # noqa: F401
from benchmark.metrics._program import step_median


def is_pattern(run):
    return bool(getattr(run.model_cfg, "layer_pattern", ""))


def local_rows(run):
    """(row, choice) pairs of one decode step that fell to experts held
    here, per expert layer; None without the counters."""
    n_layers = run.model_cfg.n_moe_layers
    return step_median(
        run, "pool",
        lambda p: p["moe_rows_local"] / (p["moe_decode_steps"] * n_layers),
    )

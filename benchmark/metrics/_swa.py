"""What the readers of a window / full mix share (mellum): whether the
run's model is one, the program's own counts of one decode step's work in
an expert layer, and the device seconds of the attention scopes.  Every
reader returns None for another model and for a program that does not know
the mix."""
from benchmark.metrics._hybrid import experts_touched, local_rows  # noqa: F401
from benchmark.metrics._program import scope_seconds

ATTN_SCOPES = ("layer/attn_qkv", "layer/attn", "layer/attn_out")


def is_mix(run):
    return bool(getattr(run.model_cfg, "window_pattern", ""))


def attn_seconds(run, program, *inner):
    """Device seconds per traced step under `program` and the three
    attention scopes (and `inner`: `window` or `full`); None where none
    of them ran."""
    parts = [scope_seconds(run, program, s, *inner) for s in ATTN_SCOPES]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)


def mean_contexts(step):
    """Every row's mean context over its decode steps."""
    return [p + (l - p) / 2.0
            for l, p in zip(step["seq_lens"], step["prompt_lens"])]

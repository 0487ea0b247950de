"""What the readers of a short-convolution / attention mix share
(lfm2_moe): whether the run's model is one, the program's own counts of
one decode step's work in an expert layer, and the step's mean contexts.
Every reader returns None for another model and for a program that does
not know the mixer."""
from benchmark.metrics._hybrid import experts_touched  # noqa: F401
from benchmark.metrics._mla import local_rows  # noqa: F401 - per sparse layer
from benchmark.metrics._swa import mean_contexts  # noqa: F401

SCOPE = "layer/sconv"


def is_mix(run):
    return "C" in getattr(run.model_cfg, "window_pattern", "")

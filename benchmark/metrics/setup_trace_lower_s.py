"""Seconds of tracing and lowering over every program from process start
to the end of the warm-up step (`setup/trace_s` + `setup/lower_s`, each
phase's OWN seconds: a nested jit's trace is counted once): what no
compile cache removes, because the cache's key is made from the lowered
module."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/trace_s", "setup/lower_s")

"""Seconds of the backend phase of every program the persistent cache did
NOT serve, process start to the end of the warm-up step
(`setup/compile_s`).  In a truly warm run what is left are the programs
that compile in under 0.1 s, which are never written
(`base/compilation_cache.py`)."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/compile_s")

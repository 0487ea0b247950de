"""Seconds from the process's start (the kernel's, `/proc/self/stat`) to
the first set-up span, `setup:build` (`setup/to_run_s`): the interpreter,
jax and the backend's client, the package's imports, and the harness's
own rows and plan."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/to_run_s")

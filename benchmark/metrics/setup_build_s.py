"""Host seconds of `setup:build` (`setup/build_s`): workers, pool and
master up to the harness's "built" hook.  A span adds no wait for the
device, so what the weights' initialiser dispatched is paid in the
warm-up step, where the host first waits."""
from benchmark.metrics import _setup


def read(run):
    return _setup.total(run, "setup/build_s")

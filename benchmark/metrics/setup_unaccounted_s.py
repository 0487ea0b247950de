"""`setup_s` less `setup/to_run_s`, `setup/build_s` and the warm-up
step's wall: the harness's own work between the build and the first step
and after the step's end (the generator's weight sums, a collection),
and whatever no span covers.  Negative by what of the process's life
came before the harness read its own clock."""
from benchmark.metrics import _setup


def read(run):
    covered = _setup.total(run, "setup/to_run_s", "setup/build_s")
    if covered is None:
        return None
    return run.setup_s - covered - _setup.stats(run)["time/step_s"]

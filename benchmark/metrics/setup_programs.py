"""Programs compiled or loaded from process start to the end of the
warm-up step (`setup/programs`), the reference check's among them.  Also
writes the warm-up step's `setup/*` stats and the rows of step 1's
program ledger that took longest (`tracer.setup_report`) to the run's
stderr, so that every log carries the table."""
import sys

from benchmark.metrics import _setup


def read(run):
    n = _setup.total(run, "setup/programs")
    if n is None:
        return None
    from areal_tpu.base import tracer

    rows = [
        r for step in tracer.step_ledger() if step["step"] == 1
        for r in step["programs"]
    ]
    print("[benchmark] " + tracer.setup_report(_setup.stats(run), rows),
          file=sys.stderr, flush=True)
    return n

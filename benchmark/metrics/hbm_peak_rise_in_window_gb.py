"""By how much the TIMED steps raised the process's peak: the sum of
their `hbm/peak_rise_gb`, in GB.  0.0 where set-up or the warm-up step
set the run's peak; what the harness runs between the warm-up step's
close and the window's opening (its weight sums) is marked at the first
timed step's first request and so counts here."""


def read(run):
    if not run.steps or any(
        "hbm/peak_rise_gb" not in s["stats"] for s in run.steps
    ):
        return None
    return run.total(lambda s: s["stats"]["hbm/peak_rise_gb"])

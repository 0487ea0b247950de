"""Seconds of backend compilation and persistent-cache loads the workers
saw inside the window: every `<node>/perf/compile_s` of the timed steps'
stats, summed.  `compiles_in_window` is the count; this is the cost, and
the step stats say which request paid it."""


def read(run):
    found = [
        v for s in run.steps for k, v in s["stats"].items()
        if k.endswith("perf/compile_s")
    ]
    return sum(found) if found else None

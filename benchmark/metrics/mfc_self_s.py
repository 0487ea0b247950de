"""Seconds of the workers' `mfc:*` spans under no child span, summed over
the step's nodes (`<node>/perf/self_s`, from the program's step ledger),
median step: what of a model function call's handler no inner span
covers yet."""
import statistics


def read(run):
    vals = []
    for s in run.steps:
        mine = [
            v for k, v in s["stats"].items()
            if k == "perf/self_s" or k.endswith("/perf/self_s")
        ]
        if mine:
            vals.append(sum(mine))
    return statistics.median(vals) if vals else None

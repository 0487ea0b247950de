"""What the readers of Mamba-2 mixers in two-branch layers share
(granitemoehybrid on the serving plane): whether the run's model is one,
and the program's own counts of one inner step of the serving chunk, for
`benchmark/peaks_ssmd.py` to count bytes by.  Every reader returns None
for another model and for a program that keeps no such counter."""
from benchmark.metrics import decode_ms_per_step
from benchmark.metrics._program import scope_seconds, step_median


def is_ssmd(run):
    cfg = run.model_cfg
    return "M" in (getattr(cfg, "window_pattern", "") or "")


def inner_steps(run):
    """Inner steps of the serving chunk in the last timed step."""
    return decode_ms_per_step.steps_run(run.steps[-1])


def chunk_ms(run, *needles):
    """Device milliseconds an inner step under `gen/serving_chunk` (and
    `needles`), traced run; None without the scope."""
    seconds = scope_seconds(run, "gen/serving_chunk", *needles)
    if seconds is None:
        return None
    return 1e3 * seconds / inner_steps(run)


def live_slots(run):
    """Slots with state, mean over the step's chunks."""
    return step_median(
        run, "pool", lambda p: p["ssm_live_slot_chunks"] / p["chunks"])

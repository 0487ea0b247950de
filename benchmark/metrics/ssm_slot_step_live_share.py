"""The (slot, inner step) pairs of the serving chunk in which the slot held
a lane (the generator's counter `ssm_slot_steps_live`) over slots x inner
steps, in %, median step: how much of the slots' recurrent state a step of
the recurrence has to read and rewrite — a slot without a lane (done,
parked, not yet admitted) is skipped.  The slots are counted from the bytes
of state the pool holds (`ssm_state_bytes`: fp32 [H, P, N] a slot and Mamba
layer).  None for a program without the counter."""
import statistics

from benchmark.metrics import _ssmd, decode_ms_per_step
from benchmark.peaks_ssmd import FP32


def read(run):
    if not _ssmd.is_ssmd(run):
        return None
    cfg = run.model_cfg
    a_slot = cfg.n_ssm_layers * cfg.ssm_inner_dim * cfg.ssm_state_dim * FP32
    shares = []
    for step in run.steps:
        pool = step["pool"]
        if "ssm_slot_steps_live" not in pool or not pool.get("ssm_state_bytes"):
            continue
        slots = pool["ssm_state_bytes"] / a_slot
        inner = decode_ms_per_step.steps_run(step)
        shares.append(100.0 * pool["ssm_slot_steps_live"] / (slots * inner))
    return statistics.median(shares) if shares else None

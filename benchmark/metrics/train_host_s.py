"""Seconds the host itself works in one `train_batch` call — packing,
uploads, dispatch of every gradient program and of the apply — before it
sits down to wait for the device's answer (`last_pack_stats["host_s"]`,
the step's last minibatch), median step.  Against `train_s` it says
whether the host runs ahead of the device or the device waits for it."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(run, "pack", lambda p: p["host_s"])

"""The time the published HBM bandwidth allows one decode step (weights
once + every row's cache at its mean length, bytes from shapes, over the
chips' bandwidth: benchmark/peaks.py) as a share of `decode_loop_ms`, the
device's time per decode-loop iteration from the trace, in %.  A share of
the published peak, not of a measured copy rate.  Static-route cells."""
from benchmark import peaks
from benchmark.metrics import decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None:
        return None
    step = run.steps[-1]
    ctx = [p + (l - p) / 2.0 for l, p in zip(step["seq_lens"], step["prompt_lens"])]
    floor_s = peaks.decode_step_bytes(run.model_cfg, ctx) / (
        run.chips * run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * floor_s * 1e3 / loop_ms

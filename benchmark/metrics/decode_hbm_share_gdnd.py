"""`decode_hbm_share` for Gated DeltaNet mixers before a dense MLP: the
time the published HBM bandwidth allows one decode step
(`peaks_gdnd.decode_bytes`: every weight once, the recurrent state read and
written once, conv tails, the attention layers' K/V at every row's mean
context) as a share of `decode_loop_ms`, in %."""
from benchmark import peaks_gdnd
from benchmark.metrics import _gdnd, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None or not _gdnd.is_gdnd(run):
        return None
    step = run.steps[-1]
    ctx = [p + (l - p) / 2.0
           for l, p in zip(step["seq_lens"], step["prompt_lens"])]
    floor_s = peaks_gdnd.decode_bytes(run.model_cfg, ctx) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

"""`decode_hbm_share` for a pattern of one-branch layers: the time the
published HBM bandwidth allows one decode step (`peaks_ssm.decode_bytes`:
Mamba weights, the state read and written once, conv tails, K/V of the
attention layers at every row's mean context, the held experts the program
counted as touched, shared expert, router, head) as a share of
`decode_loop_ms`, in %."""
from benchmark import peaks_ssm
from benchmark.metrics import _ssm, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if loop_ms is None or run.peaks is None or not _ssm.is_pattern(run):
        return None
    step = run.steps[-1]
    ctx = [p + (l - p) / 2.0
           for l, p in zip(step["seq_lens"], step["prompt_lens"])]
    floor_s = peaks_ssm.decode_bytes(
        run.model_cfg, ctx, _ssm.experts_touched(run), _ssm.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

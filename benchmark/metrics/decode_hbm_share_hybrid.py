"""`decode_hbm_share` for a hybrid layer pattern: the time the published
HBM bandwidth allows one decode step (`peaks_hybrid.decode_bytes`: mixer
weights, recurrent state read and written once, conv tails, K/V of the
attention layers at every row's mean context, the held experts the program
counted as touched, shared expert, router, head) as a share of
`decode_loop_ms`, in %."""
from benchmark import peaks_hybrid
from benchmark.metrics import _hybrid, decode_loop_ms


def read(run):
    loop_ms = decode_loop_ms.read(run)
    if (loop_ms is None or run.peaks is None
            or not getattr(run.model_cfg, "is_hybrid", False)):
        return None
    step = run.steps[-1]
    ctx = [p + (l - p) / 2.0
           for l, p in zip(step["seq_lens"], step["prompt_lens"])]
    floor_s = peaks_hybrid.decode_bytes(
        run.model_cfg, ctx, _hybrid.experts_touched(run),
        _hybrid.local_rows(run),
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / loop_ms

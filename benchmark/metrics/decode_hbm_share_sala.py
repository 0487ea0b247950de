"""`decode_hbm_share` for minicpm_sala: the time the published HBM
bandwidth allows one decode iteration (`peaks_sala.decode_bytes`: every
weight once, each row's fp32 Lightning state read and written once, the
selected blocks' K and V rows and the visible compressed keys at every
row's mean context, the rows' logits) as a share of the device time of an
iteration under `gen/decode_step`, in %.  Not of `decode_loop_ms`: that
reads the generate request's LONGEST outermost loop, which in a cell of
13 k-token prompts and 256 new tokens is the prefill's scan over waves."""
from benchmark import peaks_sala
from benchmark.metrics import _sala


def read(run):
    ms = _sala.decode_ms(run)
    if ms is None or run.peaks is None:
        return None
    step = run.steps[-1]
    ctx = [int(p + (l - p) / 2.0)
           for l, p in zip(step["seq_lens"], step["prompt_lens"])]
    floor_s = peaks_sala.decode_bytes(run.model_cfg, ctx) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

"""The time the published HBM bandwidth allows one inner step of the
serving chunk (`peaks_ssmd.serving_step_bytes`: every weight once, the
live slots' state read and written once, their conv tails, the cached
tokens under the live lanes' windows, the lanes' logits) as a share of the
device time of an inner step under `gen/serving_chunk`, in %.  The counts
are the program's: live slots a chunk, live lanes and live pages an inner
step."""
from benchmark import peaks_ssmd
from benchmark.metrics import _ssmd


def read(run):
    ms = _ssmd.chunk_ms(run)
    slots = _ssmd.live_slots(run)
    if ms is None or slots is None or run.peaks is None or not _ssmd.is_ssmd(run):
        return None
    step = run.steps[-1]
    inner = _ssmd.inner_steps(run)
    lanes = step["gen"]["lanes_live"] / inner
    page_tokens = step["pool"]["pages_live"] * step["pool"]["page_size"] / inner
    floor_s = peaks_ssmd.serving_step_bytes(
        run.model_cfg, slots, lanes, page_tokens,
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

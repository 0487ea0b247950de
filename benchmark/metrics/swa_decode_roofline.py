"""The time the published HBM bandwidth allows the attention branches of
one decode step (`peaks_swa.attn_decode_bytes`: every layer's projection
weights, the rings' LIVE entries as the program counted them, the full
layers' windows of every allocated slot, which is what the program reads,
and the rows written) as a share of `swa_decode_ms`, in %.
Bandwidth-bound: one query a row."""
from benchmark import peaks_swa
from benchmark.metrics import _swa, swa_decode_ms
from benchmark.metrics._program import step_median


def read(run):
    ms = swa_decode_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    cfg = run.model_cfg
    rows = len(run.steps[-1]["seq_lens"])
    ring = step_median(run, "pool", lambda p: p["window_slots_live"])
    full = step_median(
        run, "pool",
        lambda p: p["kv_cache_bytes"] / (
            peaks_swa.n_full(cfg) * peaks_swa.kv_token_bytes(cfg)),
    )
    if ring is None or full is None:
        return None
    floor_s = peaks_swa.attn_decode_bytes(cfg, ring, full, rows) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / ms

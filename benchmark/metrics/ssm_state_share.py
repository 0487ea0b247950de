"""Bytes of the static program's recurrent state and conv tails over all
its cache bytes (the generator's counters `state_cache_bytes` /
(`state_cache_bytes` + `kv_cache_bytes`), from shapes), in %, median step.
The state does not grow with the answer: 4 x 64 x 2 MB beside one
attention layer's K/V reads 91.6% at a 768-slot window."""
from benchmark.metrics import _ssm
from benchmark.metrics._program import step_median


def read(run):
    if not _ssm.is_pattern(run):
        return None
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["state_cache_bytes"]
        / (p["state_cache_bytes"] + p["kv_cache_bytes"]),
    )

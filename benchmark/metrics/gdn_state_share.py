"""Bytes of the Gated DeltaNet layers' recurrent state and conv tails over
all the static program's cache bytes (the generator's counters
`state_cache_bytes` / (`state_cache_bytes` + `kv_cache_bytes`), from
shapes), in %, median step.  The state does not grow with the answer; the
K/V beside it does."""
from benchmark.metrics._program import step_median


def read(run):
    if not getattr(run.model_cfg, "is_hybrid", False):
        return None
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["state_cache_bytes"]
        / (p["state_cache_bytes"] + p["kv_cache_bytes"]),
    )

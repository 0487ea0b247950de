"""Bytes of the static program's cache — the conv layers' tails and the
attention layers' K/V (`conv_cache_bytes` + `kv_cache_bytes`) — over what
K/V at every layer of the plan would take (`kv_cache_bytes_all_attention`),
from shapes, in %, median step: (1 x 302.0 MB + 5 x 0.26 MB) / (6 x 302.0
MB) reads 16.7% at 32 rows of 4,608 slots."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * (p["conv_cache_bytes"] + p["kv_cache_bytes"])
        / p["kv_cache_bytes_all_attention"],
    )

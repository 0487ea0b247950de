"""Bytes of the static program's attention cache — the window layers'
rings and the full layers' windows (`window_cache_bytes` +
`kv_cache_bytes`) — over what a cache of every slot in every attention
layer would take (`kv_cache_bytes_unwindowed`), from shapes, in %, median
step: (3 x 1,024 + 4,608) / (4 x 4,608) reads 41.7%."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * (p["window_cache_bytes"] + p["kv_cache_bytes"])
        / p["kv_cache_bytes_unwindowed"],
    )

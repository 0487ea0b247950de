"""Bytes of the static program's latent cache over what per-head K/V of
the same window would have taken (the generator's counters
`latent_cache_bytes` / `kv_cache_bytes_as_heads`, from shapes), in %,
median step: (kv_lora_rank + rope) / (heads x (qk + v)), 5.6% at 576 of
10,240."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["latent_cache_bytes"] / p["kv_cache_bytes_as_heads"],
    )

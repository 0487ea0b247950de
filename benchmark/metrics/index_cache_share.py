"""Bytes of the static program's cache that are the indexer's key rows,
over all it keeps for the attention layers (latent rows, index keys,
rings), in %, from the generator's `last_pool_stats`: 128 of 576 + 128
values a slot of a full layer."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["index_cache_bytes"] / (
            p["index_cache_bytes"] + p["latent_cache_bytes"]
            + p["latent_ring_bytes"]))

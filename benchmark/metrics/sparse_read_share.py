"""Keys a decode iteration of the block-sparse layers READ over the keys
their caches held (the generator's counters `sparse_keys_read` — the chosen
blocks x block + the visible compressed rows, a key head's, summed over
rows, layers and iterations — and `sparse_keys_cached`), in %, median step.
A cache read by selection reads (topk x block + cache / stride) / cache of
it: 37-46% at caches of 10.5-13.3 k; a window read whole would read 100."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["sparse_keys_read"] / p["sparse_keys_cached"])

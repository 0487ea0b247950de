"""Latent rows a decode iteration of the full layers READ over the rows
their caches held for the row (the generator's counters `latent_rows_read`
— the selected rows, summed over rows, layers and iterations — and
`latent_rows_visible`), in %, median step.  A cache read by selection
reads index_topk / context of it: 15-20% at caches of 10.5-13.3 k; a dense
read under a mask would read 100."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["latent_rows_read"] / p["latent_rows_visible"])

"""Attention tiles the flash kernels visit in a SLIDING-WINDOW layer
(`last_pack_stats["flash_live_tiles_window"]`: the band of the live
schedule) over the rows' full squares (`["flash_grid_tiles"]`), in %,
median step; `flash_live_tile_share` stays the full layers'."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pack",
        lambda p: 100.0 * p["flash_live_tiles_window"] / p["flash_grid_tiles"],
    )

"""Attention tiles the flash kernels visit (`last_pack_stats
["flash_live_tiles"]`: 128 x 128 tiles of the call's packed rows with an
unmasked element) over the rows' full squares (`["flash_grid_tiles"]`),
in %, median step: the share of a grid step per tile the kernels still
pay since PR 34.  0.0 where a call packed no tile."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pack",
        lambda p: 100.0 * p["flash_live_tiles"] / p["flash_grid_tiles"]
        if p["flash_grid_tiles"] else 0.0,
    )

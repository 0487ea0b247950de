"""Packed rows with no real token (`last_pack_stats["empty_rows"]`) over
the rows of the `train_batch` call (`["n_rows"]`), in %, median step:
under batch sharding an empty row is a chip that trains zeros."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pack",
        lambda p: 100.0 * p["empty_rows"] / p["n_rows"]
        if p["n_rows"] else 0.0,
    )

"""Of the (row, choice) pairs the router made over a generate call's
decode steps and layers, the share that fell to experts HELD here (the
generator's counters `moe_rows_local` / `moe_rows_routed`, summed on the
device inside the decode loop), in %, median step.  One expert-parallel
rank's share under balanced routing: experts held over the router's width
(12.5% at 64 of 512)."""
from benchmark.metrics._program import step_median


def read(run):
    return step_median(
        run, "pool",
        lambda p: 100.0 * p["moe_rows_local"] / p["moe_rows_routed"],
    )

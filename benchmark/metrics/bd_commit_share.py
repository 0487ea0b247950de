"""Of the block loop's device time (the scopes `gen/bd_denoise`,
`gen/bd_unmask`, `gen/bd_commit`, `gen/bd_first_block_logp`), the share of
the COMMIT forwards, in %: what it costs to leave a block's clean K and V
in the cache.  1 / (T + 1) of the forwards, less the head they skip; 0
would mean the commit rides another forward.  Traced run."""
from benchmark.metrics import _bd
from benchmark.metrics._program import scope_seconds


def read(run):
    whole = _bd.loop_seconds(run)
    part = scope_seconds(run, "gen/bd_commit") if whole else None
    if part is None:
        return None
    return 100.0 * part / whole

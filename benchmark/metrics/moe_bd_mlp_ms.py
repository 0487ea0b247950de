"""`moe_decode_mlp_ms` for generation by diffusion over blocks: device
milliseconds ONE forward of the block loop spends in its MoE MLPs — the
scope `layer/mlp` (router, sort and gather, activation, scatter) under
`gen/bd_denoise`, `gen/bd_commit` and `gen/bd_first_block_logp`, plus XLA's
ragged-dot kernels at the loop's rows (`_bd.mlp_seconds`) — all layers
together, over every forward the generate call made (`bd/forwards`: each
steps the same rows).  Traced run; None without the scopes or the counter."""
from benchmark.metrics import _bd


def read(run):
    seconds, n = _bd.mlp_seconds(run), _bd.all_forwards(run)
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n

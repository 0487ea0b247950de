"""`moe_route_share` for generation by diffusion over blocks: of the
device time the block loop's forwards spend in their MoE MLPs
(`moe_bd_mlp_ms`'s seconds), the share under the scopes `router`,
`dispatch` and `combine` — everything but the expert matmuls and their
activation — in %.  Traced run."""
from benchmark.metrics import _bd


def read(run):
    whole = _bd.mlp_seconds(run)
    parts = _bd.mlp_seconds(run, "router", "dispatch", "combine")
    if whole is None or parts is None:
        return None
    return 100.0 * parts / whole

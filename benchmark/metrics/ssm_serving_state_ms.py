"""Device milliseconds an inner step of the serving chunk in the part of
the Mamba-2 mixers that touches the carried state (scope `ssd_scan` under
`gen/serving_chunk`: the lanes' read of each live slot's fp32 state and its
rewrite — as XLA fusions or as the Pallas kernel `ssm_slab_step`, whose
scope lies under `ssd_scan` — with the chunk's own lower triangle and
cumulative decays), all Mamba layers of one step together, mean over
chips.  Serving-route cells of a plan with Mamba-2 layers, traced run."""
from benchmark.metrics import _ssmd


def read(run):
    return _ssmd.chunk_ms(run, "ssd_scan")

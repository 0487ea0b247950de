"""Device seconds of the train step's gradient program under the scope
`layer/linear_attn/delta_rule` (the Gated DeltaNet's chunked delta rule:
q, k, v cut into heads and normalised, then the rule itself — the `jnp`
form's blocks, solve and two loops, or the Pallas sweeps `gdn_chunk_fwd`
and `gdn_chunk_bwd`; forward, recomputed forward and backward) over those
under `layer/linear_attn`, the whole mixer, in %.  Both scopes are the
program's own names (`models/linear_attention.py`); None where the run was
not traced or no operation ran under them."""
from benchmark.metrics._program import scope_seconds


def read(run):
    rule = scope_seconds(
        run, "train/grad", "layer/linear_attn", "delta_rule")
    mixer = scope_seconds(run, "train/grad", "layer/linear_attn")
    if rule is None or mixer is None:
        return None
    return 100.0 * rule / mixer

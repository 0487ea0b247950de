"""Device milliseconds per decode-loop iteration in latent attention of
`gen/decode_step` — the scopes `layer/attn_qkv` (q_lora, kv_lora,
absorb_q), `layer/attn` (latent_scores, latent_out over the latent rows)
and `layer/attn_out` (absorb_out, the output projection) — all layers of
one step together, mean over chips.  Static-route cells of a latent
config, traced run."""
from benchmark.metrics import _mla, decode_ms_per_step


def read(run):
    seconds = _mla.attn_seconds(run, "gen/decode_step")
    if seconds is None:
        return None
    return 1e3 * seconds / decode_ms_per_step.steps_run(run.steps[-1])

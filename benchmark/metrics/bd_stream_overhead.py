"""Slots the trainer's stack ran over a token it trained: clean + masked
stream slots over the batch's own tokens (the train engine's
`last_pack_stats["bd/stream_overhead"]`), median step.  ~1.8 where a
512-token response follows a 130-token prompt; 1 for an autoregressive
model, which keeps no such counter."""
from benchmark.metrics import _bd
from benchmark.metrics._program import step_median


def read(run):
    if not _bd.is_bd(run):
        return None
    return step_median(run, "pack", lambda p: p["bd/stream_overhead"])

"""Device time under the scope `head_logprob` — the fused log-prob head of
the train step (forward, its recomputation, backward) and the decode
head — over device busy time, in %.  On four chips its fp32 logits cross
the chips in the forward and again in the recomputation."""
from benchmark.metrics._program import scope_share


def read(run):
    return scope_share(run, "head_logprob")

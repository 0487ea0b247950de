"""Tokens a row KEPT over every forward the block loop made (denoising
steps, commits, the first block's log-prob forward): the generator's
counter `bd/tokens_per_forward`, median step.  B / (T + 1) = 4/3 at a
block of 4 in 2 denoising steps, less the one first-block forward and the
last block's dropped places; an autoregressive program reads 1 and keeps
no such counter."""
from benchmark.metrics import _bd


def read(run):
    return _bd.counter(run, "tokens_per_forward")

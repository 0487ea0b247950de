"""Train-step component timing at bench shapes (the MFU-gap hunt).

Times, each in its own jitted program:
  1. backbone forward only
  2. backbone forward + fused logprob head
  3. full value_and_grad (fwd+bwd) under the chosen remat policy
  4. optimizer apply
and prints achieved TFLOP/s per stage against the analytic FLOPs, so the
slow stage is identified instead of guessed (bench r4/r5 measured
mfu_train ~0.13 with remat=full and no further breakdown).

Usage: python scripts/profile_train.py [--size 1.5b] [--tokens 8192]
       [--remat full|dots_small|dots|none] [--iters 3]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="1.5b")
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--seqlen", type=int, default=1024)
    p.add_argument("--remat", default="full")
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.base import compilation_cache

    compilation_cache.enable()
    from areal_tpu.base import monitor
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import qwen2_config, tiny_config

    cfg = (
        tiny_config()
        if args.size == "tiny"
        else qwen2_config(args.size, param_dtype="bfloat16")
    )
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    b = max(args.tokens // args.seqlen, 1)
    s = args.seqlen
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    seg = jnp.ones((b, s), jnp.int32)
    pos = jnp.tile(jnp.arange(s)[None], (b, 1))
    n_tok = b * s
    fwd_flops = monitor.flops_forward(cfg, n_tok, float(b * s * s))

    def bench(name, fn, ops_flops, *fargs):
        jax.block_until_ready(fn(*fargs))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*fargs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.iters
        tf = ops_flops / dt / 1e12
        print(f"{name:28s}: {dt * 1e3:8.1f} ms  {tf:7.1f} TFLOP/s")
        return dt

    @jax.jit
    def backbone(params, tokens, seg, pos):
        x, _ = tfm.hidden_states(
            params, cfg, tokens, seg, positions=pos, remat=args.remat
        )
        return x.sum()

    @jax.jit
    def fwd_head(params, tokens, seg, pos):
        x, _ = tfm.hidden_states(
            params, cfg, tokens, seg, positions=pos, remat=args.remat
        )
        return tfm.per_token_output(params, cfg, x, tokens, seg).sum()

    def loss(p):
        x, _ = tfm.hidden_states(
            p, cfg, tokens, seg, positions=pos, remat=args.remat
        )
        lp = tfm.per_token_output(p, cfg, x, tokens, seg)
        return lp.sum()

    grad = jax.jit(jax.grad(loss))

    print(
        f"# {args.size} tokens={n_tok} (b={b} s={s}) remat={args.remat} "
        f"analytic fwd={fwd_flops / 1e12:.1f} TF"
    )
    bench("backbone fwd", backbone, fwd_flops, params, tokens, seg, pos)
    bench("fwd + fused head", fwd_head, fwd_flops, params, tokens, seg, pos)
    # bwd ~2x fwd; remat recompute adds ~1x for "full" and ~0.9x for
    # "dots_small" (everything but the residual-branch outputs is
    # recomputed: qkv, attention, gate/up — nearly the whole layer).
    mult = 3.0
    if args.remat in ("full", True):
        mult += 1.0
    elif args.remat == "dots_small":
        mult += 0.9
    bench("fwd+bwd (grad)", grad, mult * fwd_flops, params)


if __name__ == "__main__":
    main()

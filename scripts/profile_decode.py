"""Decode-step roofline profile: measured per-token latency vs the
HBM-bandwidth bound.

Decode is bandwidth-bound: every generated token streams all weights
plus the live KV window.  This script times ONE jitted inflight decode
step at a sweep of (batch, window) points and prints the roofline ratio,
so generator tuning (spec decoding, window buckets, batch size) can be
judged against the physical limit instead of guessed at.  A profile only
on a TPU; under `JAX_PLATFORMS=cpu` (use `--size tiny`) it is a smoke run
of the script and says so.

Usage: python scripts/profile_decode.py [--size 1.5b] [--batches 8,32]
       [--windows 1280,4096] [--steps 64]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="1.5b")
    p.add_argument("--batches", default="8,32")
    p.add_argument("--windows", default="1280,4096")
    p.add_argument("--steps", type=int, default=64)
    # v5e: ~819 GB/s HBM. Override per chip (v5p ~2765, v4 ~1228).
    p.add_argument("--hbm-gbps", type=float, default=819.0)
    p.add_argument("--unroll", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.base import compilation_cache

    compilation_cache.enable()
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import qwen2_config, tiny_config

    on_cpu = jax.default_backend() == "cpu"
    cfg = (
        tiny_config()
        if args.size == "tiny"
        else qwen2_config(args.size, param_dtype="bfloat16")
    )
    if on_cpu:
        print("# NOTE: cpu backend — numbers are a smoke run, not a profile")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    bpe = 2 if cfg.param_dtype == "bfloat16" else 4

    import functools

    for b in [int(x) for x in args.batches.split(",")]:
        for w in [int(x) for x in args.windows.split(",")]:
            cache = tfm.init_kv_cache(cfg, b, w, dtype=params_dtype(params))
            toks = jnp.zeros((b,), jnp.int32)
            pos = jnp.full((b,), w // 2, jnp.int32)
            slots = jnp.full((b,), w // 2, jnp.int32)
            valid = jnp.full((b,), w // 2 + 1, jnp.int32)

            n_steps = args.steps

            # Time N steps inside ONE program (like the generator's
            # static while_loop and the inflight chunk fn), so per-call
            # dispatch does not count against the step.
            @functools.partial(jax.jit, donate_argnums=(1,))
            def chunk(params, cache, toks, pos, slots, valid):
                def body(i, st):
                    toks, cache = st
                    logits, cache = tfm.decode_step_inflight(
                        params, cfg, toks, pos + i, cache, slots + i,
                        valid + i, unroll=args.unroll,
                    )
                    return jnp.argmax(logits, -1).astype(jnp.int32), cache

                toks, cache = jax.lax.fori_loop(
                    0, n_steps, body, (toks, cache)
                )
                return toks, cache

            toks2, cache = chunk(params, cache, toks, pos, slots, valid)
            jax.block_until_ready(toks2)  # compile + warm
            t0 = time.perf_counter()
            toks2, cache = chunk(params, cache, toks2, pos, slots, valid)
            jax.block_until_ready(toks2)
            dt = (time.perf_counter() - t0) / n_steps

            kv_bytes = (
                2 * cfg.n_layers * b * w * cfg.n_kv_heads * cfg.head_dim
                * cache.k.dtype.itemsize
            )
            stream = n_params * bpe + kv_bytes
            roofline_s = stream / (args.hbm_gbps * 1e9)
            print(
                f"b={b:4d} window={w:6d}: {dt * 1e3:7.2f} ms/step "
                f"({b / dt:8.0f} tok/s) | stream {stream / 1e9:.2f} GB "
                f"-> roofline {roofline_s * 1e3:.2f} ms "
                f"({dt / roofline_s:5.1f}x off bound)"
            )


def params_dtype(params):
    import jax

    return jax.tree.leaves(params)[0].dtype


if __name__ == "__main__":
    main()

"""Measured MXU peak probe: big bf16 matmuls, chained in one program.

MFU divides by the public spec peak of the chip (`base/monitor.py`
`_PEAK_TFLOPS`; v5e: 197 bf16 TFLOP/s).  This prints the best sustained
TFLOP/s over a few shapes — what a matmul can actually reach on the chip,
the ceiling to read an MFU against.  Needs a TPU.

Usage: python scripts/probe_matmul.py [--steps 32]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=32)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"needs a TPU: jax.default_backend() is {jax.default_backend()!r}"
        )

    from areal_tpu.base import compilation_cache, monitor

    compilation_cache.enable()

    shapes = [
        (4096, 4096, 4096),
        (8192, 8192, 8192),
        (4096, 1536, 8960),   # qwen2-1.5b MLP up
        (4096, 8960, 1536),   # qwen2-1.5b MLP down
        (4096, 1536, 151936),  # LM head
    ]
    best = 0.0
    for (m, k, n) in shapes:
        a = jnp.ones((m, k), jnp.bfloat16)
        b = jnp.ones((k, n), jnp.bfloat16)
        steps = args.steps

        @jax.jit
        def chain(a, b):
            def body(i, acc):
                # Depend on the loop carry so steps serialize; scale to
                # keep values finite in bf16.
                return (acc @ b @ b.T) * jnp.bfloat16(1e-8)

            return jax.lax.fori_loop(0, steps, body, a)

        chain(a, b).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        chain(a, b).block_until_ready()
        dt = time.perf_counter() - t0
        flops = 2.0 * m * k * n * 2 * steps  # two matmuls per step
        tf = flops / dt / 1e12
        best = max(best, tf)
        print(
            f"[{m}x{k}]@[{k}x{n}]: {tf:8.1f} TFLOP/s "
            f"({dt / steps * 1e3:.2f} ms/step-pair)"
        )
    print(f"best sustained: {best:.1f} TFLOP/s on "
          f"{jax.devices()[0].device_kind} (spec peak in base/monitor.py: "
          f"{monitor.peak_tflops_per_device()})")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What the static program's softmax attention costs on the chip, alone, at
the shapes of the cells whose cache is k/v alone: the XLA form
(`ops/attention.decode_attention` on the layer sliced out of the stacked
cache, what every other plan runs) beside the Pallas kernel
(`ops/pallas/kv_decode.kv_decode`) at every `--blocks` (rows x slots a grid
step; the first is `blocks_for`'s own).

    chiprun -- python3 scripts/kv_decode_bench.py

A variant is one jitted program that makes its caches INSIDE (a cache
handed in keeps an entry parameter's layout and XLA copies all of it in
front of the kernel) and walks `--steps` decode steps spread over the
cell's budget of new tokens, every layer a step writing its token and
attending, rows' prompts as the cell's (right-aligned at the bucket); a
call's time is the program's median over `--reps` runs on the host's clock
after two warm runs, divided by steps x layers.  One JSON line a variant on
stdout and all of them in `chiprun_out/kv_decode_bench.json`: microseconds a
call, the live K and V bytes a call over the time as a share of the chip's
published rate, and the largest distance from the XLA form's output beside
its largest entry.  `--cpu-rehearsal` runs it here at toy size, interpreted:
control flow only, no time worth reading.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: layers, rows, prompt bucket, slots, key heads, query heads a key
# head, tokens a row and forward, prompt lengths
SHAPES = {
    "q1p5b-decode-static": (28, 8, 256, 1280, 2, 6, 1, (96, 160)),
    "olmoe-decode-tail": (3, 8, 256, 1280, 16, 1, 1, (96, 160)),
    "sdar-rollout64-512": (8, 64, 256, 896, 4, 8, 4, (98, 158)),
    "q1p5b-train-longprompt": (28, 16, 2560, 2816, 2, 6, 1, (256, 2560)),
}
HBM_BYTES_PER_S = 819e9  # TPU v5e, published
D = 128


def program(shape, form, block, steps):
    """f(q, k_new, v_new) -> the last step's output summed into q's shape:
    `steps` decode steps over `layers` layers on caches made inside."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.attention import decode_attention
    from areal_tpu.ops.pallas.kv_decode import kv_decode

    layers, b, sp, s, g, rep, tok, (short, long) = shape
    lens = np.linspace(short, long, b).astype(np.int32)
    valid_from = jnp.asarray(sp - lens)
    stride = max((s - sp - tok) // steps, 1)

    def attend(q, kc, vc, li, to):
        if form == "kernel":
            return kv_decode(q, kc, vc, li, valid_from, to, block=block)
        k = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
        qx = q.reshape(b, tok, g, rep, D).transpose(0, 2, 1, 3, 4)
        out = decode_attention(
            qx.reshape(b, 1, -1, D), k, v, valid_from, to)
        return out.reshape(b, g, tok, rep, D).transpose(
            0, 2, 1, 3, 4).reshape(q.shape)

    def run(q, k_new, v_new):
        kc = jnp.ones((layers, b, s, g, D), q.dtype) * k_new[0, 0, 0, 0]
        vc = jnp.ones((layers, b, s, g, D), q.dtype) * v_new[0, 0, 0, 0]

        def step(i, state):
            kc, vc, acc = state
            slot = sp + i * stride

            def layer(c, li):
                kc, vc, acc = c
                kc = jax.lax.dynamic_update_slice(
                    kc, k_new[None], (li, 0, slot, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    vc, v_new[None], (li, 0, slot, 0, 0))
                out = attend(q + acc, kc, vc, li, slot + tok)
                return (kc, vc, out * 0.5), None

            return jax.lax.scan(layer, (kc, vc, acc), jnp.arange(layers))[0]

        return jax.lax.fori_loop(
            0, steps, step, (kc, vc, jnp.zeros_like(q)))[2]

    live = sum(
        float(np.sum(sp + i * stride + tok - (sp - lens)))
        for i in range(steps)) / steps  # slots a call, all rows
    return jax.jit(run), live * g * D * 2 * 2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--blocks", default="0x0,8x128,4x128,2x128,1x128,4x256,2x256")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from areal_tpu.base import compilation_cache
    from areal_tpu.ops.pallas.kv_decode import blocks_for

    compilation_cache.enable()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        sys.exit(f"no TPU here (platform {platform}): a time off the chip "
                 f"is worth nothing; --cpu-rehearsal for the control flow")
    report = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        if args.cpu_rehearsal:
            layers, b, sp, s, g, rep, tok, lens = shape
            shape = (2, min(b, 4), 128, 256, g, rep, tok, (40, 100))
            args.steps, args.reps = 2, 1
        layers, b, sp, s, g, rep, tok, _ = shape
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, tok, g * rep, D), jnp.bfloat16)
        k_new = jax.random.normal(ks[1], (b, tok, g, D), jnp.bfloat16)
        v_new = jax.random.normal(ks[2], (b, tok, g, D), jnp.bfloat16)
        own = blocks_for(b, s, g, D)
        seen, want = set(), None
        variants = [("xla", None)] + [
            ("kernel", tuple(int(x) for x in bl.split("x")))
            for bl in args.blocks.split(",")]
        for form, block in variants:
            if block == (0, 0):
                block = own
            if block is not None and (
                    block in seen or b % block[0] or s % block[1]):
                continue
            seen.add(block)
            fn, live_bytes = program(shape, form, block, args.steps)
            try:
                out = jax.block_until_ready(fn(q, k_new, v_new))
                jax.block_until_ready(fn(q, k_new, v_new))
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q, k_new, v_new))
                    times.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 - a block Mosaic refuses
                print(json.dumps({"shape": name, "form": form, "block": block,
                                  "refused": str(e)[:300]}), flush=True)
                continue
            out = out.astype(jnp.float32)
            want = out if want is None else want
            call = statistics.median(times) / (args.steps * layers)
            row = {
                "shape": name, "cache": [layers, b, s, g, D],
                "form": form if block is None else f"kernel{block}",
                "own_block": block == own, "platform": platform,
                "us_a_call": None if platform != "tpu" else call * 1e6,
                "live_bytes_share_of_peak": None if platform != "tpu"
                else live_bytes / call / HBM_BYTES_PER_S,
                "max_abs_vs_xla": float(jnp.max(jnp.abs(out - want))),
                "max_abs": float(jnp.max(jnp.abs(want))),
            }
            report.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_decode_bench.json", "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()

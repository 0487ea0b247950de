#!/usr/bin/env python3
"""What the state's part of the serving plane's Mamba-2 recurrence costs on
the chip, alone, at the shape of `granite4hm-serving-waves`: one layer's
buffer `f32[1, 64, 64, 64, 128]` (64 slots, 64 heads of [64, 128]), slabs of
W = 8 lanes — the `jnp` form (`mamba.ssd_slab` between a
`dynamic_index_in_dim` and the `where(held, new, state)` write-back, what
the parent's `ssm_ragged` runs) beside the Pallas kernel
(`mamba.ssd_slab_in_place` -> `ops/pallas/ssm_slab.ssm_slab_step`), at
every `--live` count of slots that hold a lane (the rest hold none; the
first `--prefill` of the live ones hold W lanes, the others one) and, for
the kernel, every `--heads` a grid step.

    chiprun -- python3 scripts/ssm_slab_bench.py

A variant is one jitted program that steps the buffer `--steps` times in a
`fori_loop` (the state made INSIDE the program and carried, as the serving
chunk carries its pool: a buffer handed in from outside keeps an entry
parameter's layout); a call's time is the median of `--reps` calls on the
host's clock after two warm calls, over `--steps`.  One JSON line a variant
on stdout and all of them in `chiprun_out/ssm_slab_bench.json`: milliseconds
a step, the GB/s of the live slots' state read once and written once, and
the largest distance of y and of the new state from the `jnp` form's beside
the largest entries.  `--cpu-rehearsal` runs it here at 4 slots and 4
heads, interpreted: control flow only, no time worth reading.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def slab(r, w, h, g, p, n, live, prefill, seed=0):
    """The slab's operands as `ssm_ragged` makes them, and its lanes: the
    live slots spread over the buffer, `prefill` of them with W lanes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import mamba
    from areal_tpu.ops.pallas.ssm_slab import live_slots

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    count = np.zeros((r,), np.int32)
    at = np.linspace(0, r - 1, live).round().astype(int) if live else []
    count[at] = 1
    count[at[:prefill]] = w
    count = jnp.asarray(count)
    valid = jnp.arange(w)[None] < count[:, None]
    x = jax.random.normal(ks[0], (r, w, h, p))
    dt = jnp.where(
        valid[..., None],
        jax.nn.softplus(jax.random.normal(ks[1], (r, w, h)) - 2.0), 0.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    bm = jax.random.normal(ks[3], (r, w, g, n))
    cm = jax.random.normal(ks[4], (r, w, g, n))
    fresh = jnp.zeros((r,), bool).at[at[:1]].set(True) if live else (
        jnp.zeros((r,), bool))
    # The stream: each live slot's lanes packed in slot order, as the
    # serving chunk packs them, and dead lanes behind them up to R + 4 W.
    t = r + 4 * w
    rid = jnp.repeat(jnp.arange(r), count, total_repeat_length=t)
    start = jnp.cumsum(count) - count
    q = jnp.clip(jnp.arange(t) - start[rid], 0, w - 1)
    lanes = mamba.SlotLanes(
        None, valid, count, fresh, rid, q, *live_slots(count))
    return (x, dt, a, bm, cm), lanes


def variant_fn(kind, steps, shape, block_h=0):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import mamba

    r, h, p, n = shape

    def once(states, ops, lanes):
        x, dt, a, bm, cm = ops
        if kind == "jnp":
            state = jax.lax.dynamic_index_in_dim(states, 0, 0, keepdims=False)
            y, new = mamba.ssd_slab(
                x, dt, a, bm, cm, state, 1.0 - lanes.fresh.astype(jnp.float32))
            held = (lanes.count > 0)[:, None, None, None]
            return y[lanes.rid, lanes.q], jax.lax.dynamic_update_index_in_dim(
                states, jnp.where(held, new, state), 0, 0)
        return mamba.ssd_slab_in_place(
            x, dt, a, bm, cm, states, 0, lanes, block_h=block_h)

    @jax.jit
    def run(ops, lanes):
        states = 0.1 * jax.random.normal(
            jax.random.PRNGKey(7), (1, r, h, p, n), jnp.float32)
        y0, s1 = once(states, ops, lanes)

        def body(_, carry):
            states, acc = carry
            y, states = once(states, ops, lanes)
            return states, acc + y

        states, acc = jax.lax.fori_loop(1, steps, body, (s1, y0))
        # One step's y and state (to compare), and what keeps the loop alive.
        return y0, s1[0], jnp.sum(acc) + jnp.sum(states[0, :, 0, 0])

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="64,52,32,8,0")
    ap.add_argument("--prefill", type=int, default=4)
    ap.add_argument("--heads", default="0,8,32,64")
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from areal_tpu.base import compilation_cache

    compilation_cache.enable()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        sys.exit(f"no TPU here ({platform}); --cpu-rehearsal for a toy run")
    r, w, h, g, p, n = (4, 4, 4, 1, 64, 128) if args.cpu_rehearsal else (
        64, 8, 64, 1, 64, 128)
    steps = 2 if args.cpu_rehearsal else args.steps
    lives = [min(int(v), r) for v in args.live.split(",")]
    heads = [int(v) for v in args.heads.split(",")]
    if args.cpu_rehearsal:
        lives, heads = sorted(set(lives), reverse=True)[:3], [0, 2]
    out = []
    for live in lives:
        ops, lanes = slab(r, w, h, g, p, n, live, min(args.prefill, live))
        want = None
        for kind, hb in [("jnp", 0)] + [("kernel", hb) for hb in heads]:
            fn = variant_fn(kind, steps, (r, h, p, n), hb)
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(ops, lanes))
            compile_s = time.perf_counter() - t0
            jax.block_until_ready(fn(ops, lanes))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(ops, lanes))
                times.append(time.perf_counter() - t0)
            ms = 1e3 * statistics.median(times) / steps
            held = lanes.valid[lanes.rid, lanes.q][:, None, None]
            y, s = jnp.where(held, got[0], 0.0), got[1]
            if want is None:
                want = (y, s)
            row = {
                "platform": platform, "kind": kind, "block_h": hb,
                "live": live, "prefill": min(args.prefill, live),
                "ms_a_step": ms, "compile_s": compile_s,
                "live_state_gb_per_s": (
                    2 * live * h * p * n * 4 / (ms * 1e-3) / 1e9),
                "y_max_abs_vs_jnp": float(jnp.max(jnp.abs(y - want[0]))),
                "y_max_abs": float(jnp.max(jnp.abs(want[0]))),
                "state_max_abs_vs_jnp": float(jnp.max(jnp.abs(s - want[1]))),
                "state_max_abs": float(jnp.max(jnp.abs(want[1]))),
            }
            out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_slab_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

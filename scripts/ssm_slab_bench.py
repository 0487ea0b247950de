#!/usr/bin/env python3
"""What one layer's short SSD chunk a slot costs on the chip, alone, at the
shape of `granite4hm-serving-waves`: one layer's buffer
`f32[1, 64, 64, 64, 128]` (64 slots, 64 heads of [64, 128]), slabs of W = 8
lanes.  Three forms of the same step, each from the operands as `ssm_ragged`
gathers them to the slab to the y of the stream's lanes, the D skip in
it:

- `jnp`: `mamba.ssd_slab` between a `dynamic_index_in_dim` and the
  `where(held, new, state)` write-back (off a TPU, on a mesh);
- `parent` (with `--parent <dir>`, the root of a `git archive` of a commit
  before PR 63): `mamba.slab_terms` over all R x W slab lanes as XLA
  fusions, then that commit's kernel for the state's part alone, then the
  scale and the sum of the two parts of y on the stream;
- `kernel`: `mamba.ssd_slab_in_place` -> `ops/pallas/ssm_slab.ssm_slab_step`
  with the chunk's terms made inside, for the live slots alone, at every
  `--heads` a grid step.

A case `live:prefill` of `--cases` is that many slots with a lane (the rest
hold none), the first `prefill` of them with W lanes and the others one:
the default is the cell's mean, 47 live slots, with one lane a slot and
with eight, then every slot live and none.

    chiprun -- python3 scripts/ssm_slab_bench.py --parent _checkout/parent

A variant is one jitted program that steps the buffer `--steps` times in a
`fori_loop` (the state made INSIDE the program and carried, as the serving
chunk carries its pool: a buffer handed in from outside keeps an entry
parameter's layout); a call's time is the median of `--reps` calls on the
host's clock after two warm calls, over `--steps`.  One JSON line a variant
on stdout and all of them in `chiprun_out/ssm_slab_bench.json`: milliseconds
a step, the GB/s of the live slots' state read once and written once, and
the largest distance of y and of the new state from a yardstick's beside
the largest entries — the yardstick is the `jnp` form with every product in
fp32 (`jnp_fp32`, first of a case; its time is no form's).  `--cpu-rehearsal` runs it here at 4 slots and 4
heads, interpreted: control flow only, no time worth reading.
"""
import argparse
import importlib.util
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
    # x | B | C as the conv leaves them: what the kernel reads in place.
    conv = jnp.concatenate(
        [v.reshape(r, w, -1) for v in (x, bm, cm)], axis=-1)
    d = 0.5 + jax.random.uniform(jax.random.PRNGKey(seed + 11), (h,))
    return (x, dt, a, bm, cm, conv, d), lanes


def parent_in_place(parent_slab):
    """The form before PR 63 over this tree's unchanged `slab_terms`: the
    terms as XLA fusions over the whole slab, `parent_slab`'s kernel for
    the state's part."""
    import jax.numpy as jnp

    from areal_tpu.models import mamba

    def step(x, dt, a, bm, cm, d, states, li, lanes, block_h=0):
        r, w, h, p = x.shape
        y, w_in, xw, s_keep = mamba.slab_terms(
            x, dt, a, bm, cm, 1.0 - lanes.fresh.astype(jnp.float32))
        states, y_raw = parent_slab.ssm_slab_step(
            states, li, lanes.live, lanes.n_live,
            jnp.swapaxes(cm, 1, 2), jnp.swapaxes(bm, 1, 2),
            xw.reshape(r, w, h * p), s_keep.reshape(r, h), block_h=block_h)
        y_raw = jnp.where(
            mamba._to_stream(lanes.valid, lanes)[:, None],
            mamba._to_stream(y_raw, lanes), 0.0).reshape(-1, *y.shape[2:])
        y = mamba._to_stream(y, lanes) + y_raw * mamba._to_stream(
            w_in, lanes)[..., None]
        y = y.reshape(-1, h, p) + d[:, None] * mamba._to_stream(x, lanes)
        return y, states

    return step


def variant_fn(kind, steps, shape, block_h=0, parent=None):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import mamba

    r, h, p, n = shape

    def once(states, ops, lanes):
        x, dt, a, bm, cm, conv, d = ops
        if kind.startswith("jnp"):
            state = jax.lax.dynamic_index_in_dim(states, 0, 0, keepdims=False)
            # `jnp_fp32`: every product in fp32, the yardstick the others'
            # distances are read from.
            with jax.default_matmul_precision(
                    "float32" if kind == "jnp_fp32" else "default"):
                y, new = mamba.ssd_slab(
                    x, dt, a, bm, cm, state,
                    1.0 - lanes.fresh.astype(jnp.float32))
            held = (lanes.count > 0)[:, None, None, None]
            y = mamba._to_stream(y, lanes) + d[:, None] * mamba._to_stream(
                x, lanes)
            return y, jax.lax.dynamic_update_index_in_dim(
                states, jnp.where(held, new, state), 0, 0)
        if parent:
            return parent(
                x, dt, a, bm, cm, d, states, 0, lanes, block_h=block_h)
        y, states = mamba.ssd_slab_in_place(
            conv, dt, a, d, states, 0, lanes, block_h=block_h)
        return y.reshape(-1, h, p), states

    @jax.jit
    def run(ops, lanes):
        states = 0.1 * jax.random.normal(
            jax.random.PRNGKey(7), (1, r, h, p, n), jnp.float32)
        y0, s1 = once(states, ops, lanes)

        def body(_, carry):
            states, acc = carry
            # dt hangs on the carry (plus a zero XLA cannot fold), so no
            # form's terms are loop-invariant and hoisted out of the loop.
            dt = ops[1] + 0.0 * states[0, 0, 0, 0, 0]
            y, states = once(states, (ops[0], dt) + ops[2:], lanes)
            return states, acc + y

        states, acc = jax.lax.fori_loop(1, steps, body, (s1, y0))
        # One step's y and state (to compare), and what keeps the loop alive.
        return y0, s1[0], jnp.sum(acc) + jnp.sum(states[0, :, 0, 0])

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="47:0,47:47,64:4,0:0")
    ap.add_argument("--heads", default="0")
    ap.add_argument("--parent", default=None)
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
    cases = [tuple(min(int(v), r) for v in c.split(":"))
             for c in args.cases.split(",")]
    heads = [int(v) for v in args.heads.split(",")]
    if args.cpu_rehearsal:
        cases, heads = [(4, 0), (3, 3), (0, 0)], [0, 2]
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_ssm_slab", os.path.join(
                args.parent, "areal_tpu/ops/pallas/ssm_slab.py"))
        parent_slab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent_slab)
        parent = parent_in_place(parent_slab)
    out = []
    for live, prefill in cases:
        prefill = min(prefill, live)
        ops, lanes = slab(r, w, h, g, p, n, live, prefill)
        want = None
        variants = [("jnp_fp32", 0), ("jnp", 0)] + (
            [("parent", 0)] if parent else []) + [
            ("kernel", hb) for hb in heads]
        for kind, hb in variants:
            fn = variant_fn(
                kind, steps, (r, h, p, n), hb,
                parent if kind == "parent" else None)
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
                "live": live, "prefill": prefill,
                "ms_a_step": ms, "compile_s": compile_s,
                "live_state_gb_per_s": (
                    2 * live * h * p * n * 4 / (ms * 1e-3) / 1e9),
                "y_max_abs_vs_fp32": float(jnp.max(jnp.abs(y - want[0]))),
                "y_max_abs": float(jnp.max(jnp.abs(want[0]))),
                "state_max_abs_vs_fp32": float(jnp.max(jnp.abs(s - want[1]))),
                "state_max_abs": float(jnp.max(jnp.abs(want[1]))),
            }
            out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_slab_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

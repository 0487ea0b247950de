#!/usr/bin/env python3
"""What Mamba-2's chunked (SSD) recurrence costs on the chip, alone, at the
micro-batch of `nemo3n-rollout64-512` and `granite4hm-serving-waves`: one
packed row `[1, 8192]` of 64 heads of 64 channels, a state of 128 columns,
chunks of 128, twelve segments of 642 tokens and pads behind them; B and C
in eight groups (`nemo3n`) and in one (granite) — the `jnp` form
(`mamba.ssd_chunked`, what the parent runs) beside the Pallas sweep
(`ops/pallas/ssd_chunk.ssd_chunk`), forward alone and forward + backward
(`jax.value_and_grad` of a weighted sum of y).  The sweep is called as the
mixer calls it: x | B | C side by side as the conv leaves them, the
gradient one array of that shape (split here for the comparison).

    chiprun -- python3 scripts/ssd_chunk_bench.py

A variant is one jitted program with the operands as ARGUMENTS; a call's
time is the median of `--reps` calls on the host's clock after two warm
calls.  With `--ops N` each variant's forward + backward is also traced
once and its N longest device operations are listed by name (the `jnp`
form's are the `[64, 64, 128, 128]` blocks' fusions).  One JSON line a
variant on stdout and all of them in `chiprun_out/ssd_chunk_bench.json`:
milliseconds a call and the largest distance of y and of each gradient from
the `jnp` form's, beside the largest entry of the `jnp` form's — and from
the `jnp` form with every product on fp32 operands (`Precision.HIGHEST`:
the yardstick both forms of the parent's precision are held to).
`--cpu-rehearsal` runs it here at 384 tokens and 8 heads, interpreted:
control flow only, no time worth reading.
"""
import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("y", "dx", "ddt", "dA", "dB", "dC")


def operands(s, h, g, p, n, seg_len, seed=0):
    """x, dt, A, B, C in the ranges the mixer makes them (dt after its
    softplus on [1e-3, 0.1]-ish, A in -(1, 16)), the cells' segment layout
    and the sum's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    seg = np.arange(s) // seg_len + 1
    seg[seg > s // seg_len] = 0  # what is left of the row is pads
    seg = jnp.asarray(seg[None], jnp.int32)
    x = jax.nn.silu(jax.random.normal(ks[0], (1, s, h, p)))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)) - 3.0)
    dt = jnp.where((seg > 0)[..., None], dt, 0.0)
    a = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    bm = jax.nn.silu(jax.random.normal(ks[3], (1, s, g, n)))
    cm = jax.nn.silu(jax.random.normal(ks[4], (1, s, g, n)))
    w = jax.random.normal(ks[5], (1, s, h, p))
    return (x, dt, a, bm, cm), seg, w


def variant_fn(kind, chunk, interpret=None, head_dim=0, groups=0):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models.mamba import _fill_pads, ssd_chunked
    from areal_tpu.ops.pallas.ssd_chunk import ssd_chunk

    def rule(x, dt, a, bm, cm, seg):
        seg = _fill_pads(seg)
        if kind == "fp32":
            with jax.default_matmul_precision("highest"):
                return ssd_chunked(x, dt, a, bm, cm, seg, chunk)[0]
        if kind == "jnp":
            return ssd_chunked(x, dt, a, bm, cm, seg, chunk)[0]
        # The mixer's call: x | B | C side by side, as the conv's output.
        conv = x
        return ssd_chunk(conv, dt, a, jnp.zeros(a.shape), seg, chunk,
                         head_dim, groups, interpret=interpret)

    def fwd(ops, seg, w):
        return (rule(*ops, seg),)

    def fwd_bwd(ops, seg, w):
        def loss(*ops):
            y = rule(*ops, seg)
            return jnp.sum(y * w), y

        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*ops)
        return (y, *grads)

    return jax.jit(fwd), jax.jit(fwd_bwd)


def ms_per_call(fn, args, reps):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls) * 1e3


def device_ops(fn, args, top):
    """One traced call -> its `top` longest device operations, ms."""
    import jax

    from benchmark import trace

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(fn(*args))
        reduced = trace.reduce(
            jax.profiler.ProfileData.from_file(trace.find_xplane(d)), 1)
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return {"busy_ms": round(reduced["busy_s"] * 1e3, 3),
            "ops_ms": [[name[:160], round(s * 1e3, 3)]
                       for name, s in ops[:top]]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default="8,1",
                    help="groups of B and C, comma list (8: nemo3n, 1: "
                         "granite)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--ops", type=int, default=12,
                    help="device operations listed a variant (0: no trace)")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    toy = args.cpu_rehearsal
    if not toy and jax.default_backend() != "tpu":
        raise SystemExit("ssd_chunk_bench: needs a TPU (or --cpu-rehearsal)")
    s, h, p, n, chunk, seg_len = (384, 8, 64, 128, 128, 150) if toy else (
        8192, 64, 64, 128, 128, 642)
    lines = []
    for g in (int(x) for x in args.groups.split(",")):
        g = min(g, h // 4) if toy else g
        ops, seg, w = operands(s, h, g, p, n, seg_len)
        first = None
        truth = [np.asarray(v, np.float32) for v in variant_fn(
            "fp32", chunk, interpret=toy or None)[1](ops, seg, w)]
        x, dt, a, bm, cm = ops
        conv = jnp.concatenate(
            [v.reshape(1, s, -1) for v in (x, bm, cm)], axis=-1)
        for kind in ("jnp", "kernel"):
            call = (ops, seg, w)
            if kind == "kernel":  # bm, cm: placeholders, B and C are in conv
                call = ((conv, dt, a, bm, cm), seg, w.reshape(1, s, -1))
            line = {"variant": kind, "groups": g,
                    "shape": [1, s, h, p], "state": n, "chunk": chunk,
                    "segments": s // seg_len,
                    "platform": jax.default_backend()}
            try:
                fwd, fwd_bwd = variant_fn(kind, chunk, toy or None, p, g)
                reps = 1 if toy else args.reps
                line["fwd_ms"] = round(ms_per_call(fwd, call, reps), 3)
                line["fwd_bwd_ms"] = round(
                    ms_per_call(fwd_bwd, call, reps), 3)
                got = [np.asarray(v, np.float32) for v in fwd_bwd(*call)]
                if kind == "kernel":  # d conv -> dx | dB | dC
                    di, gn = h * p, g * n
                    dconv = got[1]
                    got = [got[0], dconv[..., :di], got[2], got[3],
                           dconv[..., di: di + gn], dconv[..., di + gn:]]
                got = [v.reshape(u.shape) for v, u in zip(got, truth)]
                if first is None:
                    first = got
                    line["max_abs"] = {
                        k: float(np.max(np.abs(v)))
                        for k, v in zip(NAMES, got)}
                line["max_abs_vs_jnp"] = {
                    k: float(np.max(np.abs(v - u)))
                    for k, v, u in zip(NAMES, got, first)}
                line["max_abs_vs_fp32"] = {
                    k: float(np.max(np.abs(v - u)))
                    for k, v, u in zip(NAMES, got, truth)}
                if args.ops and not toy:
                    line["fwd_bwd_device"] = device_ops(
                        fwd_bwd, call, args.ops)
            except Exception as ex:  # noqa: BLE001 - a block Mosaic refuses
                line["error"] = f"{type(ex).__name__}: {ex}"[:400]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_chunk_bench.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What the Gated DeltaNet's chunked delta rule costs on the chip, alone,
at the micro-batch of `q3next-rollout64-512`: one packed row `[1, 8192]` of
32 value heads over 16 key heads of 128, eleven segments of 642 tokens and
pads behind them — the `jnp` form (`linear_attention.gated_delta_chunked`
on q and k repeated to 32 heads, what the parent runs) beside the Pallas
sweep (`ops/pallas/delta_chunk.gdn_chunk`), forward alone and forward +
backward (`jax.value_and_grad` of a weighted sum of o), the kernel at every
`--heads` a grid step, every `--groups` of them unrolled side by side in a
trip of the step's loop, and with the backward's states kept or rebuilt
(`--save 1,0`).

    chiprun -- python3 scripts/delta_chunk_bench.py

A variant is one jitted program with the operands as ARGUMENTS (closed
over, 100 MB of constants are baked into the program); a call's time is the
median of `--reps` calls on the host's clock after two warm calls.  One
JSON line a variant on stdout and all of them in
`chiprun_out/delta_chunk_bench.json`: milliseconds a call and the largest
distance of o and of each gradient from the `jnp` form's, beside the
largest entry of the `jnp` form's.  `--cpu-rehearsal` runs it here at 256
tokens and 4 heads, interpreted: control flow only, no time worth reading.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def operands(s, hk, hv, d, seg_len, seed=0):
    """q, k, v, g, beta as the mixer makes them (normalised q and k, g <= 0,
    beta in (0, 1)), the cell's segment layout and the sum's weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models.linear_attention import _l2norm

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = _l2norm(jax.random.normal(ks[0], (1, s, hk, d))) * d**-0.5
    k = _l2norm(jax.random.normal(ks[1], (1, s, hk, d)))
    v = jax.random.normal(ks[2], (1, s, hv, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, s, hv)))
    w = jax.random.normal(ks[5], (1, s, hv, d))
    seg = np.arange(s) // seg_len + 1
    seg[seg > s // seg_len] = 0  # what is left of the row is pads
    return (q, k, v, g, beta), jnp.asarray(seg[None], jnp.int32), w


def variant_fn(kind, rep, heads=0, group=0, save=True, interpret=None):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models.linear_attention import gated_delta_chunked
    from areal_tpu.ops.pallas.delta_chunk import gdn_chunk

    def rule(q, k, v, g, beta, seg):
        if kind == "jnp":
            q, k = (jnp.repeat(x, rep, axis=2) for x in (q, k))
            return gated_delta_chunked(q, k, v, g, beta, seg)[0]
        return gdn_chunk(q, k, v, g, beta, seg, block_h=heads, group=group,
                         save=save, interpret=interpret)

    def fwd(ops, seg, w):
        return (rule(*ops, seg),)

    def fwd_bwd(ops, seg, w):
        def loss(*ops):
            o = rule(*ops, seg)
            return jnp.sum(o * w), o

        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*ops)
        return (o, *grads)

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="32,8",
                    help="value heads a grid step, comma list")
    ap.add_argument("--groups", default="4,2,8",
                    help="of them a trip of the step's loop, comma list")
    ap.add_argument("--save", default="1,0")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    toy = args.cpu_rehearsal
    if not toy and jax.default_backend() != "tpu":
        raise SystemExit("delta_chunk_bench: needs a TPU (or --cpu-rehearsal)")
    s, hk, hv, d, seg_len = (256, 2, 4, 128, 100) if toy else (
        8192, 16, 32, 128, 642)
    ops, seg, w = operands(s, hk, hv, d, seg_len)
    heads = [min(int(h), hv) for h in args.heads.split(",")]
    groups = [int(g) for g in args.groups.split(",")]
    variants = [("jnp", 0, 0, True)] + [
        ("kernel", h, g, bool(int(sv)))
        for h in heads for g in groups if g <= h
        for sv in args.save.split(",")]
    lines, first = [], None
    for kind, h, g, save in variants:
        line = {"variant": kind, "heads_a_step": h, "heads_a_trip": g,
                "save": save,
                "shape": [1, s, hv, d], "segments": s // seg_len,
                "platform": jax.default_backend()}
        try:
            fwd, fwd_bwd = variant_fn(
                kind, hv // hk, h, g, save, toy or None)
            reps = 1 if toy else args.reps
            line["fwd_ms"] = round(ms_per_call(fwd, (ops, seg, w), reps), 3)
            line["fwd_bwd_ms"] = round(
                ms_per_call(fwd_bwd, (ops, seg, w), reps), 3)
            got = [np.asarray(x, np.float32) for x in fwd_bwd(ops, seg, w)]
            if first is None:
                first = got
                line["max_abs"] = {
                    n: float(np.max(np.abs(x))) for n, x in zip(NAMES, got)}
            line["max_abs_vs_jnp"] = {
                n: float(np.max(np.abs(x - y)))
                for n, x, y in zip(NAMES, got, first)}
        except Exception as ex:  # noqa: BLE001 - a block Mosaic refuses
            line["error"] = f"{type(ex).__name__}: {ex}"[:400]
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_chunk_bench.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()

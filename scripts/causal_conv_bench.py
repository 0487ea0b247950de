#!/usr/bin/env python3
"""What the mixers' depthwise causal conv + activation costs on the chip,
alone, at the micro-batch of the five cells whose mixers run it: one packed
row `[1, 8192]` of bf16 channels, twelve segments of 642 tokens and pads
behind them, at each cell's (channels, taps, bias, activation) — the `jnp`
form (`linear_attention.causal_conv` + bias + SiLU, what prefill, a mesh
and the parent run) beside the Pallas operator
(`ops/pallas/causal_conv.causal_conv_act`), forward alone and forward +
backward (`jax.value_and_grad` of a weighted sum of the result).

    chiprun -- python3 scripts/causal_conv_bench.py

A variant is one jitted program with the operands as ARGUMENTS; a call's
time is the median of `--reps` calls on the host's clock after two warm
calls, and beside it the GB/s that time makes of the operator's FLOOR — what
it must move with its interface unchanged: forward reads x in bf16 and
writes fp32 (6 B an element), backward reads the fp32 cotangent and x and
writes x's gradient (8 B) — as a share of the chip's 819 GB/s.  With
`--ops N` each variant's forward + backward is also traced once and its N
longest device operations are listed by name (the kernels' own
milliseconds: `causal_conv_fwd`, `causal_conv_bwd`).  One JSON line a
variant on stdout and all of them in `chiprun_out/causal_conv_bench.json`,
with the largest distance of the result and of each gradient from the `jnp`
form's beside the `jnp` form's largest entry.  `--blocks 256x512` forces a
(token block, channel block); `--cpu-rehearsal` runs it here at 400 tokens
and 256 channels, interpreted: control flow only, no time worth reading.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ssd_chunk_bench import device_ops, ms_per_call  # noqa: E402 - beside us

HBM_GBPS = 819.0
# cell -> (channels, taps, bias, activation): `benchmark/configs`' widths.
CELLS = {
    "olmoh": (11520, 4, False, "silu"),
    "q3next": (8192, 4, False, "silu"),
    "nemo3n": (6144, 4, True, "silu"),
    "granite4hm": (4352, 4, True, "silu"),
    "lfm2": (2048, 3, False, "identity"),
}
NAMES = ("out", "dx", "dtaps", "dbias")


def operands(s, c, kk, bias, seg_len, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    seg = np.arange(s) // seg_len + 1
    seg[seg > s // seg_len] = 0  # what is left of the row is pads
    x = jax.random.normal(ks[0], (1, s, c)).astype(jnp.bfloat16)
    taps = (jax.random.normal(ks[1], (kk, c)) * kk**-0.5).astype(jnp.bfloat16)
    b = (jax.random.normal(ks[2], (c,)) * 0.1).astype(
        jnp.bfloat16) if bias else None
    w = jax.random.normal(ks[3], (1, s, c))
    return (x, taps, b), jnp.asarray(seg[None], jnp.int32), w


def variant_fn(kind, act, interpret=None):
    import jax
    import jax.numpy as jnp

    from areal_tpu.models.linear_attention import causal_conv
    from areal_tpu.ops.pallas.causal_conv import causal_conv_act

    def op(x, taps, b, seg):
        if kind == "kernel":
            return causal_conv_act(x, taps, b, seg, act, interpret=interpret)
        pre = causal_conv(x, taps, seg)
        if b is not None:
            pre = pre + b.astype(jnp.float32)
        return jax.nn.silu(pre) if act == "silu" else pre

    def fwd(ops, seg, w):
        return (op(*ops, seg),)

    def fwd_bwd(ops, seg, w):
        def loss(x, taps, b):
            y = op(x, taps, b, seg)
            return jnp.sum(y * w), y

        argnums = (0, 1) if ops[2] is None else (0, 1, 2)
        (_, y), grads = jax.value_and_grad(
            loss, argnums=argnums, has_aux=True)(*ops)
        return (y, *grads)

    return jax.jit(fwd), jax.jit(fwd_bwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--ops", type=int, default=6,
                    help="device operations listed a variant (0: no trace)")
    ap.add_argument("--blocks", default="",
                    help="TOKENSxCHANNELS: force the kernels' block")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from areal_tpu.ops.pallas import causal_conv as kernel

    toy = args.cpu_rehearsal
    if not toy and jax.default_backend() != "tpu":
        raise SystemExit("causal_conv_bench: needs a TPU (or --cpu-rehearsal)")
    if args.blocks:
        bt, bc = (int(v) for v in args.blocks.split("x"))
        kernel.TOKEN_BLOCKS, kernel.CHANNEL_BLOCKS = (bt,), (bc, 128)
    s, seg_len = (400, 150) if toy else (8192, 642)
    lines = []
    for cell in args.cells.split(","):
        c, kk, bias, act = CELLS[cell]
        c = 256 if toy else c
        ops, seg, w = operands(s, c, kk, bias, seg_len)
        first = None
        for kind in ("jnp", "kernel"):
            line = {"cell": cell, "variant": kind, "shape": [1, s, c],
                    "taps": kk, "bias": bias, "act": act,
                    "blocks": list(kernel._blocks(s, c)),
                    "platform": jax.default_backend()}
            try:
                fwd, fwd_bwd = variant_fn(kind, act, toy or None)
                reps = 1 if toy else args.reps
                call = (ops, seg, w)
                line["fwd_ms"] = round(ms_per_call(fwd, call, reps), 3)
                line["fwd_bwd_ms"] = round(
                    ms_per_call(fwd_bwd, call, reps), 3)
                # The floor: 6 B an element forward, 8 B more backward.
                line["fwd_floor_share"] = round(
                    6 * s * c / (line["fwd_ms"] * 1e-3) / 1e9 / HBM_GBPS, 3)
                line["fwd_bwd_floor_share"] = round(
                    14 * s * c / (line["fwd_bwd_ms"] * 1e-3) / 1e9
                    / HBM_GBPS, 3)
                got = [np.asarray(v, np.float32) for v in fwd_bwd(*call)]
                if first is None:
                    first = got
                    line["max_abs"] = {
                        k: float(np.max(np.abs(v)))
                        for k, v in zip(NAMES, got)}
                line["max_abs_vs_jnp"] = {
                    k: float(np.max(np.abs(v - u)))
                    for k, v, u in zip(NAMES, got, first)}
                if args.ops and not toy:
                    line["fwd_bwd_device"] = device_ops(
                        fwd_bwd, call, args.ops)
            except Exception as ex:  # noqa: BLE001 - a block Mosaic refuses
                line["error"] = f"{type(ex).__name__}: {ex}"[:400]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/causal_conv_bench.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What a live flash tile costs on the chip: `flash_fwd`, `flash_dq` and
`flash_dkv` alone, at the benchmark cells' shapes, at every trip width the
kernels can take (`flash_attention._trip_blocks` forced to 1, 2, 4 blocks
and as the chooser picks), beside another checkout's kernels where one is
given (`--parent <dir>`: the root of a `git archive` of the parent commit).

    chiprun -- python3 scripts/flash_tile_bench.py --parent _checkout/parent

One JSON line a (shape, variant) on stdout and all of them in
`chiprun_out/flash_tile_bench.json`: a call's milliseconds on the host's
clock (`reps` calls, then `block_until_ready`), the same over the row's
live 128 x 128 tiles x heads (`packing.flash_tile_counts`: the yardstick
does not move with the trip) in microseconds, and the largest distance of
o, dq, dk, dv (over the largest element) from the parent's, whose products
are fp32 x fp32, and from the plain fp32 reference on the same bf16 inputs
where its [S, S] scores fit.
`--cpu-rehearsal` runs it here at 1/16 the lengths, interpreted: control
flow only, no time worth reading.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name -> (rows, S, q heads, kv heads, head_dim, window, sequences of a row)
SHAPES = {
    "q1p5b_longprompt_rows_24x8192x128":
        (2, 8192, 12, 2, 128, None, [2598, 1263, 1263, 740, 740, 384, 384]),
    "q1p5b_serving_row_12x8192x128": (1, 8192, 12, 2, 128, None, [210] * 39),
    "q1p5b_prefill_192x2560x128": (16, 2560, 12, 2, 128, None, [2534]),
    "mellum_row_full_32x8192x128":
        (1, 8192, 32, 4, 128, None, [4608, 3584]),
    "mellum_row_window_32x8192x128":
        (1, 8192, 32, 4, 128, 1024, [4608, 3584]),
    "mellum_prefill_full_256x4096x128": (8, 4096, 32, 4, 128, None, [4096]),
    "mellum_prefill_window_256x4096x128":
        (8, 4096, 32, 4, 128, 1024, [4096]),
    "glm_row_20x5120x256": (1, 5120, 20, 20, 256, None, [1200] * 4),
    "q3next_row_16x8192x256": (1, 8192, 16, 2, 256, None, [640] * 12),
    "q7b_rows_28x2048x128": (1, 2048, 28, 4, 128, None, [400] * 5),
}


def _load(root, name):
    path = os.path.join(root, "areal_tpu/ops/pallas/flash_attention.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--shapes", default=None, help="comma list; default all")
    ap.add_argument("--trips", default="1,2,4,0",
                    help="blocks a trip to force; 0 = as the chooser picks")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.engines.packing import flash_tile_counts
    from areal_tpu.ops.attention import packed_attention_reference
    from areal_tpu.ops.pallas import flash_attention as fa

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        sys.exit("no TPU here; --cpu-rehearsal runs the control flow")
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}),
          flush=True)

    variants = []
    if args.parent:
        variants.append(("parent", _load(args.parent, "parent_flash"), None))
    chooser = fa._trip_blocks
    for r in (int(x) for x in args.trips.split(",")):
        variants.append((f"trip_blocks_{r}" if r else "as_chosen", fa, r))

    names = args.shapes.split(",") if args.shapes else list(SHAPES)
    os.makedirs("chiprun_out", exist_ok=True)
    blk, out = 128, []
    for name in names:
        b, s, hq, hkv, d, window, lens = SHAPES[name]
        if not on_tpu:
            s = max(s // 16 // 128, 1) * 128
            lens = [n // 16 for n in lens]
            window = window and window // 16
            b = min(b, 2)
        rng = np.random.default_rng(s + hq)
        q, k, v, do = (
            jnp.asarray(rng.standard_normal((b * h, s, d)), jnp.bfloat16)
            for h in (hq, hkv, hkv, hq)
        )
        ids = np.repeat(np.arange(len(lens)) + 1, lens)[:s]
        seg_np = np.tile(np.pad(ids, (0, s - len(ids))), (b, 1))
        seg = jnp.asarray(seg_np, jnp.int32)
        live = flash_tile_counts(seg_np, window=window)[0] * hq
        scale = d ** -0.5
        sched = jax.jit(fa.live_schedule, static_argnums=(1, 2, 3, 4))(
            seg, blk, blk, True, window)

        def bhsd(x, h):  # [b*h, s, d] -> [b, s, h, d]
            return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

        f32 = [bhsd(x, h).astype(jnp.float32)
               for x, h in ((q, hq), (k, hkv), (v, hkv))]
        w = bhsd(do, hq).astype(jnp.float32)

        def ref_loss(q, k, v):
            o = packed_attention_reference(q, k, v, seg, window=window)
            return jnp.sum(o * w), o

        want = {}
        if b * hq * s * s * 4 <= 600 << 20:  # the dense [S, S] scores fit
            (_, o_ref), g_ref = jax.jit(
                jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
            )(*f32)
            want["ref"] = [o_ref] + list(g_ref)

        for label, mod, r in variants:
            if r is not None:
                # the forced trip, cut to a divisor of the row's blocks as
                # the chooser's own is
                mod._trip_blocks = (
                    (lambda n, *_a, _r=r: fa._largest_divisor(n, _r))
                    if r else chooser)
            kw = {} if window is None else {"window": window}

            @jax.jit
            def fwd(q, k, v):
                return mod._fwd(q, k, v, seg, sched, hq, scale, blk, blk,
                                True, **kw)

            o, lse = fwd(q, k, v)
            res = (q, k, v, o, lse, seg, sched)

            @jax.jit
            def bwd(res, do):
                return mod._bwd(scale, blk, blk, True, res, do, **kw)

            calls = {"fwd": lambda: fwd(q, k, v), "bwd": lambda: bwd(res, do)}
            if hasattr(mod, "_dq"):
                delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                                axis=-1, keepdims=True)
                a = (q, k, v, do, lse, delta, seg, sched)
                tail = (hq, scale, blk, blk, True, window)
                dq_fn = jax.jit(lambda *a: mod._dq(*a, *tail))
                dkv_fn = jax.jit(lambda *a: mod._dkv(*a, *tail))
                calls.update(dq=lambda: dq_fn(*a), dkv=lambda: dkv_fn(*a))
            line = {"shape": name, "variant": label, "live_tiles_x_heads": live}
            try:
                for what, fn in calls.items():
                    jax.block_until_ready(fn())
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        got = fn()
                    jax.block_until_ready(got)
                    ms = (time.perf_counter() - t0) / args.reps * 1e3
                    line[f"{what}_ms"] = round(ms, 4)
                    line[f"{what}_us_per_tile"] = round(ms * 1e3 / live, 4)
                dq, dk, dv = bwd(res, do)
                got = [bhsd(o, hq), bhsd(dq, hq), bhsd(dk, hkv), bhsd(dv, hkv)]
                got = [g.astype(jnp.float32) for g in got]
                if label == "parent":  # its products are fp32 x fp32
                    want["parent"] = got
                for against, outs in want.items():
                    for n, g, t in zip(("o", "dq", "dk", "dv"), got, outs):
                        line[f"{n}_err_vs_{against}"] = float(
                            jnp.max(jnp.abs(g - t)) / jnp.max(jnp.abs(t)))
            except Exception as e:  # noqa: BLE001 - a width Mosaic refuses
                line["error"] = repr(e)[:300]
            print(json.dumps(line), flush=True)
            out.append(line)
            with open("chiprun_out/flash_tile_bench.json", "w") as f:
                json.dump(out, f, indent=1)
        fa._trip_blocks = chooser


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What a call of `grouped_decode_matmul` costs on the chip, alone, at the
benchmark cells' expert shapes and at every tile the kernel could walk
them in (`grouped_matmul._call(..., tk, tn)` forced; `as_chosen` is what
`grouped_matmul.tiles` picks), beside another checkout's kernel where one
is given (`--parent <dir>`: the root of a `git archive` of the parent
commit), and — for the record — beside `jax.lax.ragged_dot` at the widths
whose decode keeps XLA's kernel (`ragged_tiles_badly` false).

    chiprun -- python3 scripts/grouped_tile_bench.py --parent _checkout/parent

A (shape, variant) is ONE program: the stacked [L x E, K, N] leaf is made
inside it (a parameter handed in from outside keeps an entry layout that
XLA re-lays in front of every call at 1,856 lanes), then a loop of calls,
each with its prologue (pad, `cumsum`, `associative_scan`, the `out[:r]`
slice) as a decode step pays it, each on the rows the call before it left
(no call is hoisted or merged), over the layers in turn and eight draws of
group sizes in turn.  The loop's bound is an argument: a call's time is the
difference of two bounds on the host's clock, so making the leaf is not in
it.  One JSON line a (shape, variant) on stdout and all of them in
`chiprun_out/grouped_tile_bench.json`: microseconds a call, the time the
touched experts' bytes take at the published bandwidth (`benchmark/
peaks.py`: 819 GB/s) as a share of it,
the grid's steps, and the largest distance of one call's result from the
first variant's over the rows its groups hold.
`--cpu-rehearsal` runs it here with 4 experts and 2 calls, interpreted:
control flow only, no time worth reading.

`--train` times `grouped_matmul` instead — the kernel of the programs over
packed rows (PR 49) — forward, dx and dw each alone at the three share
cells' slab rows, held groups and widths (TRAIN_SHAPES), at the tile its
rule picks and at the tiles forced beside it, with `jax.lax.ragged_dot` (and
its own dx and dw, by `jax.vjp`) beside the kernel; the three older MoE
cells' widths with `--train --record`.  A line's `mxu_share_pct` is the live
rows' 2 x rows x K x N operations at the published bf16 peak over the call's
time; lines go to `chiprun_out/grouped_train_bench.json`.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name -> (rows R, tokens T, experts E, layers L, K, N, touched, held rows,
#          [(tk, tn)] to force beside the parent's and the chooser's)
SHAPES = {
    # mellum2-coderl32-4k: 32 rows x top-8, 2 of 8 choices held here
    "mellum_up_10": (256, 32, 16, 4, 2304, 896, 10, 64, [
        (384, 128), (384, 896), (768, 896), (1152, 896), (2304, 896)]),
    "mellum_up_16": (256, 32, 16, 4, 2304, 896, 16, 64, [
        (384, 128), (384, 896), (1152, 896), (2304, 896)]),
    "mellum_down_10": (256, 32, 16, 4, 896, 2304, 10, 64, [
        (128, 384), (128, 2304), (896, 384), (896, 1152), (896, 2304)]),
    "mellum_down_16": (256, 32, 16, 4, 896, 2304, 16, 64, [
        (128, 384), (128, 2304), (896, 1152), (896, 2304)]),
    # nemo3n-rollout64-512: 64 rows x top-6, an eighth of the choices held
    "nemo_up_14": (384, 64, 16, 4, 2688, 1856, 14, 48, [
        (384, 1856), (896, 1856), (2688, 1856)]),
    "nemo_down_14": (384, 64, 16, 4, 1856, 2688, 14, 48, [
        (1856, 384), (1856, 896), (1856, 2688)]),
}
# The widths that keep XLA's kernel: olmoe (8 rows in the tail, every choice held),
# qwen3_next (64 of 512 held, top-10), glm (8 of 64 held, top-4).
RECORD = {
    "olmoe_up_2048x1024": (64, 8, 64, 3, 2048, 1024, 40, 64),
    "olmoe_down_1024x2048": (64, 8, 64, 3, 1024, 2048, 40, 64),
    "q3next_up_2048x512": (640, 64, 64, 4, 2048, 512, 46, 80),
    "q3next_down_512x2048": (640, 64, 64, 4, 512, 2048, 46, 80),
    "glm_up_2048x1536": (256, 64, 8, 6, 2048, 1536, 5, 32),
    "glm_down_1536x2048": (256, 64, 8, 6, 1536, 2048, 5, 32),
}
DRAWS = 8

# name -> (slab rows M, held rows, groups E, K, N, [(tm, tk, tn)] forced
#          beside the rule's for forward and dx, the same for dw): a
# micro-batch of 8,192 tokens through one expert layer of the cell — the slab
# is twice a balanced router's share (`transformer.expert_slab_rows`), half
# of it held.
TRAIN_SHAPES = {
    # mellum2-coderl32-4k: top-8, 16 of 64 experts held
    "mellum_up": (32768, 16384, 16, 2304, 896,
                  [(128, 2304, 896), (256, 2304, 896), (512, 2304, 896),
                   (512, 1152, 896), (1024, 2304, 896)],
                  [(128, 2304, 896), (256, 2304, 896), (512, 2304, 896),
                   (512, 1152, 896)]),
    "mellum_down": (32768, 16384, 16, 896, 2304,
                    [(128, 896, 2304), (256, 896, 2304), (512, 896, 2304),
                     (512, 896, 1152)],
                    [(128, 896, 2304), (256, 896, 2304), (512, 896, 2304)]),
    # nemo3n-rollout64-512: top-6, 16 of 128 held
    "nemo_up": (12288, 6144, 16, 2688, 1856,
                [(128, 2688, 1856), (256, 2688, 1856), (512, 2688, 1856),
                 (512, 896, 1856)],
                [(128, 2688, 1856), (256, 2688, 1856), (256, 896, 1856),
                 (512, 896, 1856)]),
    "nemo_down": (12288, 6144, 16, 1856, 2688,
                  [(128, 1856, 2688), (256, 1856, 2688), (512, 1856, 2688),
                   (512, 1856, 896)],
                  [(128, 1856, 2688), (256, 1856, 2688), (256, 1856, 896),
                   (512, 1856, 896)]),
    # lfm2-ctxrl32-4k: top-4, 8 of 32 held
    "lfm2_up": (16384, 8192, 8, 2048, 1792,
                [(128, 2048, 1792), (256, 2048, 1792), (512, 2048, 1792),
                 (512, 1024, 1792)],
                [(128, 2048, 1792), (256, 2048, 1792), (256, 1024, 1792),
                 (512, 1024, 1792)]),
    "lfm2_down": (16384, 8192, 8, 1792, 2048,
                  [(128, 1792, 2048), (256, 1792, 2048), (512, 1792, 2048),
                   (512, 896, 2048)],
                  [(128, 1792, 2048), (256, 1792, 2048), (256, 896, 2048),
                   (512, 896, 2048)]),
}
# The widths that keep `ragged_dot` in training too: olmoe (every expert
# held: all 65,536 pairs), qwen3_next (64 of 512, top-10), glm (8 of 64, top-4).
TRAIN_RECORD = {
    "olmoe_up": (65536, 65536, 64, 2048, 1024, [], []),
    "olmoe_down": (65536, 65536, 64, 1024, 2048, [], []),
    "q3next_up": (20480, 10240, 64, 2048, 512, [], []),
    "q3next_down": (20480, 10240, 64, 512, 2048, [], []),
    "glm_up": (8192, 4096, 8, 2048, 1536, [], []),
    "glm_down": (8192, 4096, 8, 1536, 2048, [], []),
}
TRAIN_DRAWS = 4


def _load(root):
    path = os.path.join(root, "areal_tpu/ops/pallas/grouped_matmul.py")
    spec = importlib.util.spec_from_file_location("parent_grouped", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _group_sizes(rng, e, t, touched, held):
    """[DRAWS, e] int32: `held` rows over `touched` experts picked at
    random, every touched expert with a row and none with more than t."""
    import numpy as np

    out = np.zeros((DRAWS, e), np.int32)
    for d in range(DRAWS):
        live = rng.choice(e, touched, replace=False)
        sizes = np.ones(touched, np.int64)
        for _ in range(held - touched):
            sizes[rng.choice(np.flatnonzero(sizes < t))] += 1
        out[d, live] = sizes
    return out


def _us_per_call(fn, args, lo, hi, reps):
    """Microseconds a call of the loop inside `fn(*args, calls)`: the
    difference of the median walls at the two bounds."""
    import jax

    took = {}
    for calls in (lo, hi):
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, calls))
            walls.append(time.perf_counter() - t0)
        took[calls] = sorted(walls)[len(walls) // 2]
    return (took[hi] - took[lo]) / (hi - lo) * 1e6


def _train_sizes(rng, e, held):
    """[TRAIN_DRAWS, e] int32: `held` rows over e groups as a router under
    a load-balancing loss leaves them — shares drawn around the mean, the
    fullest group about one and a half times it."""
    import numpy as np

    out = np.zeros((TRAIN_DRAWS, e), np.int32)
    for d in range(TRAIN_DRAWS):
        share = rng.dirichlet(np.full(e, 8.0))
        sizes = np.floor(share * held).astype(np.int64)
        sizes[0] += held - sizes.sum()
        out[d] = sizes
    return out


def train_bench(args, jax, jnp, np, gm, peak_flops, on_tpu):
    """One JSON line a (shape, operation, variant): see the module's
    docstring."""
    table = {**TRAIN_SHAPES, **TRAIN_RECORD}
    names = args.shapes.split(",") if args.shapes else list(
        TRAIN_RECORD if args.record else TRAIN_SHAPES)
    lo, hi = (1, 2) if not on_tpu else (int(c) for c in args.calls.split(","))
    out = []
    for name in names:
        m, held, e, k, n, forced, forced_dw = table[name]
        if not on_tpu:
            m, held, e, k, n = 64, 40, 4, 256, 232 if n % 128 else 128
            forced, forced_dw = [(16, 128, n)], [(16, 128, n)]
        rng = np.random.default_rng(k + n)
        sizes = jnp.asarray(_train_sizes(rng, e, held))
        xs0 = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        dy0 = jnp.asarray(rng.standard_normal((m, n)), jnp.bfloat16)
        # operation -> [(label, tile, call(xs, dy, w, sizes))]
        ops = {
            "fwd": [("ragged_dot", None, lambda xs, dy, w, gs: (
                jax.lax.ragged_dot(xs, w, gs)))],
            "dx": [("ragged_dot", None, lambda xs, dy, w, gs: jax.vjp(
                lambda x: jax.lax.ragged_dot(x, w, gs), xs)[1](dy)[0])],
            "dw": [("ragged_dot", None, lambda xs, dy, w, gs: jax.vjp(
                lambda w_: jax.lax.ragged_dot(xs, w_, gs), w)[1](dy)[0])],
        }
        for tile in [None, *forced]:
            label = "as_chosen" if tile is None else "w%dx%dx%d" % tile
            shown = tile or gm.matmul_tiles(m, k, n, 2)
            ops["fwd"].append((label, shown, lambda xs, dy, w, gs, _t=tile: (
                gm._matmul_call(xs, w, gs, False, _t))))
            ops["dx"].append((label, shown, lambda xs, dy, w, gs, _t=tile: (
                gm._matmul_call(dy, w, gs, True, _t))))
        for tile in [None, *forced_dw]:
            label = "as_chosen" if tile is None else "w%dx%dx%d" % tile
            shown = tile or gm.dw_tiles(m, k, n, 2)
            ops["dw"].append((label, shown, lambda xs, dy, w, gs, _t=tile: (
                gm._dw_call(xs, dy, gs, w.dtype, _t))))
        for op in (args.ops.split(",") if args.ops else ops):
            first = None
            for label, tile, call in ops[op]:
                if args.variants and label not in args.variants.split(","):
                    continue

                def program(xs, dy, sizes, calls, one=call):
                    w = jax.random.normal(
                        jax.random.PRNGKey(7), (e, k, n), jnp.bfloat16
                    ) * k**-0.5

                    def body(i, carry):
                        xs, seen = carry
                        y = one(xs, dy, w, sizes[i % TRAIN_DRAWS])
                        probe = y[(0,) * y.ndim].astype(jnp.float32)
                        return xs + (probe * 0).astype(xs.dtype), seen + probe

                    once = one(xs, dy, w, sizes[0])
                    return jax.lax.fori_loop(
                        0, calls, body, (xs, 0.0))[1], once

                line = {"shape": name, "op": op, "variant": label, "rows": m,
                        "held": held, "groups": e, "k": k, "n": n}
                if tile:
                    line["tile"] = list(tile)
                try:
                    fn = jax.jit(program)
                    _, once = jax.block_until_ready(fn(xs0, dy0, sizes, lo))
                    us = _us_per_call(
                        fn, (xs0, dy0, sizes), lo, hi, args.reps)
                    floor_us = 2 * held * k * n / peak_flops * 1e6
                    line.update(us_per_call=round(us, 1),
                                mxu_floor_us=round(floor_us, 1),
                                mxu_share_pct=round(100 * floor_us / us, 1))
                    # the rows the first draw holds (a weight gradient: all)
                    once = np.asarray(once.astype(jnp.float32))
                    if once.ndim == 2:
                        once = once[:int(sizes[0].sum())]
                    if first is None:
                        first = once
                    line["max_abs_vs_first"] = float(
                        np.max(np.abs(once - first)))
                except Exception as ex:  # noqa: BLE001 - a tile Mosaic refuses
                    line["error"] = repr(ex)[:300]
                print(json.dumps(line), flush=True)
                out.append(line)
                with open("chiprun_out/grouped_train_bench.json", "w") as f:
                    json.dump(out, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--calls", default="100,600",
                    help="the two loop bounds a call's time is taken between")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--shapes", default=None, help="comma list; default all")
    ap.add_argument("--variants", default=None,
                    help="comma list of labels (parent, as_chosen, "
                    "ragged_dot, w384x896, ...); default all")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--train", action="store_true",
                    help="`grouped_matmul` (forward, dx, dw) at the train "
                    "shapes instead of the decode kernel")
    ap.add_argument("--record", action="store_true",
                    help="with --train: the three older MoE cells' widths")
    ap.add_argument("--ops", default=None,
                    help="with --train: comma list of fwd, dx, dw")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.pallas import grouped_matmul as gm
    from benchmark import peaks

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        sys.exit("no TPU here; --cpu-rehearsal runs the control flow")
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}),
          flush=True)
    # the published bandwidth of the chip the times are from (a rehearsal
    # has no times worth a share: the v5e's row stands in)
    hbm_bytes_per_s = peaks.peaks_for(
        dev.device_kind if on_tpu else "TPU v5 lite")["hbm_bytes_per_s"]
    if args.train:
        os.makedirs("chiprun_out", exist_ok=True)
        peak = peaks.peaks_for(
            dev.device_kind if on_tpu else "TPU v5 lite")["bf16_flops"]
        if args.calls == "100,600":  # a call is milliseconds, not tens of us
            args.calls = "4,24"
        return train_bench(args, jax, jnp, np, gm, peak, on_tpu)
    parent = _load(args.parent) if args.parent else None
    lo, hi = (1, 2) if not on_tpu else (int(c) for c in args.calls.split(","))

    names = args.shapes.split(",") if args.shapes else [*SHAPES, *RECORD]
    os.makedirs("chiprun_out", exist_ok=True)
    out = []
    for name in names:
        r, t, e, n_layers, k, n, touched, held, *forced = (
            SHAPES.get(name) or RECORD[name])
        if not on_tpu:
            e, n_layers, touched = 4, 1, min(touched, 3)
            held = min(held, touched * t)
        rng = np.random.default_rng(k + n + touched)
        sizes = jnp.asarray(_group_sizes(rng, e, t, touched, held))
        xs0 = jnp.asarray(rng.standard_normal((r, k)), jnp.bfloat16)

        variants = []  # (label, (tk, tn), call(xs, w, sizes, layer))
        if name in RECORD:
            def ragged(xs, w, sizes, layer):
                # as `_grouped_rows` calls it in place: the stacked leaf,
                # group sizes zero outside the layer's experts
                every = jax.lax.dynamic_update_slice(
                    jnp.zeros((w.shape[0],), jnp.int32), sizes, (layer * e,))
                return jax.lax.ragged_dot(xs, w, every)

            variants.append(("ragged_dot", None, ragged))
        elif parent is not None:
            variants.append(("parent", None, lambda *a: (
                parent.grouped_decode_matmul.__wrapped__(*a, max_rows=t))))
        for tile in (forced[0] if forced else []):
            variants.append(("w%dx%d" % tile, tile, lambda *a, _t=tile: (
                gm._call(*a, t, *_t))))
        variants.append(("as_chosen", gm.tiles(k, n, r, 2), lambda *a: (
            gm.grouped_decode_matmul.__wrapped__(*a, max_rows=t))))

        if args.variants:
            variants = [v for v in variants if v[0] in args.variants.split(",")]
        first = None
        for label, tiles, call in variants:
            def program(xs, sizes, calls, one=call):
                w = jax.random.normal(
                    jax.random.PRNGKey(7), (n_layers * e, k, n), jnp.bfloat16
                ) * k**-0.5

                def body(i, carry):
                    xs, seen = carry
                    y = one(xs, w, sizes[i % DRAWS], i % n_layers)
                    probe = y[0, 0].astype(jnp.float32)
                    return xs + (probe * 0).astype(xs.dtype), seen + probe

                once = one(xs, w, sizes[0], jnp.int32(n_layers - 1))
                return jax.lax.fori_loop(0, calls, body, (xs, 0.0))[1], once

            line = {"shape": name, "variant": label, "rows": r, "k": k, "n": n,
                    "touched": touched}
            if tiles:
                line["tile"] = list(tiles)
                line["grid_steps"] = (k // tiles[0]) * (n // tiles[1]) * e
            try:
                fn = jax.jit(program)
                _, once = jax.block_until_ready(fn(xs0, sizes, lo))
                us = _us_per_call(fn, (xs0, sizes), lo, hi, args.reps)
                floor_us = touched * k * n * 2 / hbm_bytes_per_s * 1e6
                line.update(us_per_call=round(us, 2),
                            bytes_floor_us=round(floor_us, 2),
                            bytes_share_pct=round(100 * floor_us / us, 1))
                # the rows the first draw holds: past them `ragged_dot`
                # leaves what it likes, the kernel zeros
                once = np.asarray(once.astype(jnp.float32))[:int(sizes[0].sum())]
                if first is None:
                    first = once
                line["max_abs_vs_first"] = float(np.max(np.abs(once - first)))
            except Exception as ex:  # noqa: BLE001 - a tile Mosaic refuses
                line["error"] = repr(ex)[:300]
            print(json.dumps(line), flush=True)
            out.append(line)
            with open("chiprun_out/grouped_tile_bench.json", "w") as f:
                json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

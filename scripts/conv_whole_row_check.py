#!/usr/bin/env python3
"""The comparison ROADMAP C15 names, on the chip at a configuration's
published widths: the gradient program of `tfm.hidden_states` (full remat)
over ONE 8,192-token row of twelve segments of 642 tokens, in the form the
train engine takes there (`row_kernel=None`: on one TPU device the conv on
`causal_conv_act`, the recurrence on its sweep, the kinds' own compiler
options), against the SAME segments as twelve short rows of 768 on the
`jnp` forms — rows under 1,536 tokens never showed the fused shifted read
that got a window's first tokens wrong (PERF.md section 6, PR 59).  Per
form: the next-token log-probs over the whole row (mean and max |d|; the
64-token chunks with a token over 0.3, each with its largest |d| and the
number of its tokens over) and every leaf's gradient of sum(lp * w) (the
largest |d| over the short rows' largest entry), the conv's taps first.
The fault got a window's first tokens wrong and the recurrence carried
them on: a chunk of MANY wrong tokens, and the taps' gradient two orders
off.  A routed model's two programs also part where a rounding flips an
expert choice: single tokens, scattered, in either form (run `jnp` beside
`engine` to see the form's own share).

    chiprun -- python3 scripts/conv_whole_row_check.py CONFIG [FORM,...]

CONFIG: a name of `benchmark/configs` without `.json`; FORMs: `engine` (the
default: `row_kernel=None`), `jnp` (`row_kernel=False`; with `_conv_reads_
made_input`'s barrier where that predicate holds), `jnp_no_barrier` (the
predicate patched to False: the program C15's fault was found in).  One
JSON line a form, all of them in `chiprun_out/conv_whole_row_<CONFIG>.json`;
exit code 1 where a chunk holds `--wrong-tokens` (4) or more tokens over
`--max-abs` (0.3) or the conv's gradient leaves `--conv-rel` (0.5: the fault read 142, sound programs 0.03 to
0.22).  A reading is evidence only from a TPU run; `--toy` runs the control
flow here at 2 x 96 tokens on the CPU.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONV_LEAVES = ("la_conv", "ssm_conv", "ssm_conv_b", "sc_conv")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("forms", nargs="?", default="engine")
    ap.add_argument("--max-abs", type=float, default=0.3)
    ap.add_argument("--wrong-tokens", type=int, default=4)
    ap.add_argument("--conv-rel", type=float, default=0.5)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import linear_attention as la
    from areal_tpu.models import transformer as tfm
    from benchmark import files
    from benchmark import run as bench_run

    config = files.load_json("configs", args.config + ".json")
    if args.toy:
        config, _ = bench_run.toy(
            config, files.load_json("traffic", "rollout64-512.json"))
    elif jax.default_backend() != "tpu":
        raise SystemExit("conv_whole_row_check: needs a TPU (or --toy)")
    cfg = bench_run.model_config(config)
    params = tfm.init_params(cfg, jax.random.PRNGKey(
        config.get("benchmark", {}).get("weights_seed", 0)))
    s, seg_len, short, per_mb = (
        (192, 90, 96, 2) if args.toy else (8192, 642, 768, 6))
    n = s // seg_len
    rng = np.random.default_rng(7)
    segs = [rng.integers(0, min(cfg.vocab_size, 259), seg_len)
            for _ in range(n)]
    ws = [rng.normal(size=seg_len).astype(np.float32) for _ in range(n)]

    def program(form):
        options = {}
        for branch in tfm.branches_of(cfg).values():
            if branch.grad_options:
                options.update(branch.grad_options(cfg, form))

        def loss(p, tokens, seg, w):
            x, _ = tfm.hidden_states(
                p, cfg, tokens, seg, remat="full", row_kernel=form)
            lp = tfm.per_token_output(p, cfg, x, tokens, seg)
            return jnp.sum(lp * w), lp

        return jax.jit(
            jax.value_and_grad(loss, has_aux=True), compiler_options=options)

    def f32(g):
        return jax.tree.map(lambda x: np.asarray(x, np.float32), g)

    # The yardstick: short rows on the `jnp` forms, `per_mb` a micro-batch.
    fn = program(False)
    lp_short = np.zeros((n, seg_len), np.float32)
    g_short = None
    for lo in range(0, n, per_mb):
        t = np.zeros((per_mb, short), np.int32)
        sg = np.zeros((per_mb, short), np.int32)
        w = np.zeros((per_mb, short), np.float32)
        for r in range(min(per_mb, n - lo)):
            t[r, :seg_len] = segs[lo + r]
            sg[r, :seg_len] = 1
            w[r, :seg_len] = ws[lo + r]
        (_, lp), g = fn(params, jnp.asarray(t), jnp.asarray(sg),
                        jnp.asarray(w))
        rows = min(per_mb, n - lo)
        lp_short[lo: lo + rows] = np.asarray(lp, np.float32)[:rows, :seg_len]
        g = f32(g)
        g_short = g if g_short is None else jax.tree.map(np.add, g_short, g)
    del fn
    t = np.zeros((1, s), np.int32)
    sg = np.zeros((1, s), np.int32)
    w = np.zeros((1, s), np.float32)
    for j in range(n):
        at = slice(j * seg_len, (j + 1) * seg_len)
        t[0, at], sg[0, at], w[0, at] = segs[j], j + 1, ws[j]
    row = tuple(jnp.asarray(v) for v in (t, sg, w))
    want = np.zeros(s, np.float32)
    want[: n * seg_len] = lp_short.reshape(-1)
    idx = np.arange(s)
    valid = (idx < n * seg_len) & ((idx % seg_len) != seg_len - 1)
    predicate = la._conv_reads_made_input
    lines, failed = [], False
    for form in args.forms.split(","):
        la._conv_reads_made_input = (
            (lambda cfg: False) if form == "jnp_no_barrier" else predicate)
        jax.clear_caches()
        kernel = None if form == "engine" else False
        (_, lp), g = program(kernel)(params, *row)
        lp, g = np.asarray(lp, np.float32)[0], f32(g)
        d = np.where(valid, np.abs(lp - want), 0.0)
        chunks = d[: s // 64 * 64].reshape(-1, 64)
        by_chunk = chunks.max(axis=1)
        over = (chunks > args.max_abs).sum(axis=1)
        rels = {
            jax.tree_util.keystr(path): float(
                np.abs(a - b).max() / (np.abs(b).max() + 1e-30))
            for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(g)[0],
                jax.tree.leaves(g_short))
        }
        conv = {k: v for k, v in rels.items()
                if any(name in k for name in CONV_LEAVES)}
        stats = {}
        for branch in tfm.branches_of(cfg).values():
            if branch.train_stats:
                stats.update({k: float(v) for k, v in branch.train_stats(
                    cfg, 1, row[1], kernel).items() if "on_kernel" in k})
        line = {
            "config": args.config, "form": form,
            "platform": jax.default_backend(), "tokens": s, "segments": n,
            # `_conv_reads_made_input`'s, on the `jnp` conv alone
            "barrier": stats.get("linear_attn/conv_on_kernel") == 0.0
            and bool(la._conv_reads_made_input(cfg)),
            "forms": stats,
            "logprob_mean_abs": float(d[valid].mean()),
            "logprob_max_abs": float(d.max()),
            "chunks_over": [
                [int(i), round(float(by_chunk[i]), 2), int(over[i])]
                for i in np.nonzero(over)[0]],
            "conv_grad_rel": conv,
            "worst_grad_rel": sorted(
                rels.items(), key=lambda kv: -kv[1])[:4],
        }
        bad = bool((over >= args.wrong_tokens).any()) or any(
            v > args.conv_rel for v in conv.values())
        line["passed"] = not bad
        failed |= bad
        print(json.dumps(line), flush=True)
        lines.append(line)
    la._conv_reads_made_input = predicate
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/conv_whole_row_{args.config}.json", "w") as f:
        json.dump(lines, f, indent=1)
    return int(failed)


if __name__ == "__main__":
    sys.exit(main())

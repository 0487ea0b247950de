"""A rank's share under the grouped dispatch, at a cell's shapes: the slab
and its overflow against the full gather, and the gradient program's
log-probs against the forward-only program's and the plain reference's.

Three checks a benchmark cell cannot make (random weights route near balance,
so no cell trips the overflow; `benchmark/checks.reference_check` reads the
trainer through its forward-only program), one JSON line each:

  dispatch  `_experts_grouped` (the slab, `transformer._grouped_slabs`)
            against every pair through `_grouped_rows`, on one layer's
            leaves at the configuration's widths: a router as initialised
            (no trip: the forward must be the full gather's bit for bit)
            and one tilted toward the held experts until more than a slab
            of pairs is held here (the overflow runs: forward and every
            gradient within bf16 rounding of the full gather's).
  decode    the same two routers through a decode step's dispatch —
            `_experts_grouped(layer=...)` on STACKED leaves of two layers,
            `--rows` tokens (a block loop's forward: 64 rows x 4) — against
            every pair through `_grouped_rows` on the same leaves: no trip
            (bit-equality, or the distance: a bf16 scatter-add of a token's
            two or three held pairs may sum in another order on the chip)
            and tilted, both within bf16 rounding; and what the loop's
            counters would report.
  logprobs  one packed row through `hidden_states` + `per_token_output`
            twice — inside `jax.value_and_grad` under the trainer's remat
            policy, and forward only — and its first sequences through the
            configuration's plain reference: each program within the
            reference's TOLERANCE (the limits of `correct`) of the
            reference.  What the two programs differ by from each other
            is reported and held to `max_abs` alone: each rounds in bf16
            in its own order, so their mean distance is about root two
            times either's from the fp32 reference, with or without a slab.

On the chip through the chip tool, at the cells' sizes:
  python scripts/check_moe_slab.py --config glm-4.7-flash-l7-e8.json --rows 5120
  python scripts/check_moe_slab.py --config sdar-30b-a3b-chat-l8-e16.json \
      --rows 256 --checks decode
On the CPU the same code runs at whatever size fits (tier-1 runs it on a
toy share, tests/test_moe_share_slab.py); a CPU run says nothing of the
chip's rounding.  Exit code 1 if a check fails.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models import transformer as tfm

# bf16 keeps 8 bits: two sums of the same terms in another order differ by a
# few units in the last place of the largest partial sum.
ROUNDING = 2.0 ** -5


def _layer_leaves(cfg, key, tilt):
    """(x [T, D], one MoE layer's leaves) in the parameter dtype; `tilt` is
    added to the held experts' router scores through x's first column (the
    sigmoid router: through its choice bias)."""
    d, f, n, w = (cfg.hidden_dim, cfg.moe_intermediate_dim, cfg.n_experts,
                  cfg.router_width)
    ks = jax.random.split(key, 5)
    normal = lambda k, shape, scale: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * scale).astype(cfg.dtype)
    blk = {"router": normal(ks[0], (d, w), d ** -0.5),
           "wu": normal(ks[1], (n, d, f), d ** -0.5),
           "wd": normal(ks[2], (n, f, d), f ** -0.5)}
    if cfg.mlp_gated:
        blk["wg"] = normal(ks[3], (n, d, f), d ** -0.5)
    mine = slice(cfg.expert_offset, cfg.expert_offset + n)
    if cfg.moe_score_func == "sigmoid":
        blk["router_bias"] = jnp.zeros((w,), jnp.float32).at[mine].set(tilt)
    else:
        blk["router"] = blk["router"].at[0, mine].add(tilt)
    return ks[4], blk


def _full_gather(x, top_w, top_idx, one_hot, blk, cfg, layer=None):
    """The dispatch before the slab: every pair through `_grouped_rows`."""
    order = jnp.argsort(top_idx.reshape(-1), stable=True)
    sizes = jnp.sum(one_hot, axis=(0, 1)).astype(jnp.int32)
    return tfm._grouped_rows(x, top_w, order, sizes, blk, cfg, layer)


def dispatch_check(cfg, rows: int, tilt: float, seed: int = 0) -> dict:
    """The slab against the full gather on `rows` tokens of one layer."""
    key, blk = _layer_leaves(cfg, jax.random.PRNGKey(seed), tilt)
    x = jax.random.normal(key, (rows, cfg.hidden_dim), jnp.float32)
    x = x.at[:, 0].set(1.0).astype(cfg.dtype)
    cot = jax.random.normal(jax.random.fold_in(key, 1), x.shape).astype(x.dtype)
    experts = {n: blk[n] for n in tfm._expert_leaves(cfg)}

    @jax.jit
    def both(x, experts):
        top_w, top_idx, one_hot, _ = tfm._moe_route(x, blk, cfg)

        def through(dispatch):
            def f(x, top_w, experts):
                return dispatch(x, top_w, top_idx, one_hot, experts, cfg)

            out, vjp = jax.vjp(f, x, top_w, experts)
            return out, vjp(cot)

        return (through(tfm._experts_grouped), through(_full_gather),
                jnp.sum(one_hot))

    (new, dnew), (old, dold), held = both(x, experts)
    pairs = rows * cfg.n_experts_per_tok
    slab = tfm.expert_slab_rows(cfg, pairs)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    rel = lambda a, b: float(  # noqa: E731
        np.abs(f32(a) - f32(b)).max() / max(np.abs(f32(b)).max(), 1e-30))
    dx, dw = (rel(a, b) for a, b in zip(dnew[:2], dold[:2]))
    dexp = {n: rel(dnew[2][n], dold[2][n]) for n in experts}
    differ = int((f32(new) != f32(old)).sum())
    report = {
        "check": "dispatch", "rows": rows, "pairs": pairs, "slab": slab,
        "tilt": tilt, "held": int(held),
        "slabs_run": int(tfm.expert_slabs_run(slab, pairs, held)),
        "forward_elements_differ": differ, "forward_rel": rel(new, old),
        "dx_rel": dx, "dtop_w_rel": dw, "dexperts_rel": dexp,
    }
    tripped = report["slabs_run"] > 1
    report["ok"] = bool(
        slab < pairs
        and tripped == (tilt > 0)  # the tilt is there to trip the overflow
        and (tripped or differ == 0)
        and max(report["forward_rel"], dx, dw, *dexp.values()) <= ROUNDING
        and np.abs(f32(new)).max() > 0
    )
    return report


def decode_check(cfg, rows: int, tilt: float, seed: int = 0) -> dict:
    """A decode step's slab against every pair on the one path, on `rows`
    tokens and the second of two stacked layers."""
    from areal_tpu.models.branches import LoopStep

    key, blk = _layer_leaves(cfg, jax.random.PRNGKey(seed), tilt)
    x = jax.random.normal(key, (rows, cfg.hidden_dim), jnp.float32)
    x = x.at[:, 0].set(1.0).astype(cfg.dtype)
    stacked = {n: jnp.stack([blk[n][::-1], blk[n]])
               for n in tfm._expert_leaves(cfg)}

    @jax.jit
    def both(x, stacked):
        routed = tfm._moe_route(x, blk, cfg)[:3]
        args = (x, *routed, stacked, cfg, jnp.int32(1))
        return (tfm._experts_grouped(*args), _full_gather(*args),
                jnp.sum(routed[2], axis=(0, 1)).astype(jnp.int32))

    new, old, counts = both(x, stacked)
    pairs = rows * cfg.n_experts_per_tok
    slab = tfm.decode_slab_rows(cfg, pairs)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    report = {
        "check": "decode", "rows": rows, "pairs": pairs, "slab": slab,
        "tilt": tilt, "held": int(counts.sum()),
        "slabs_run": int(tfm.expert_slabs_run(slab, pairs, counts.sum())),
        "elements_differ": int((f32(new) != f32(old)).sum()),
        "rel": float(np.abs(f32(new) - f32(old)).max()
                     / max(np.abs(f32(old)).max(), 1e-30)),
    }
    counter = tfm.BRANCHES["moe"].counter
    report.update(counter.report(np.asarray(counter.step(
        counts[None], cfg, LoopStep(None, None, None, rows)), np.float64),
        cfg, {"blocks": stacked}))
    tripped = report["slabs_run"] > 1
    report["ok"] = bool(
        slab < pairs
        and tripped == (tilt > 0)  # the tilt is there to trip the overflow
        and report["rel"] <= ROUNDING
        and report["moe_rows_gathered"] == min(
            report["slabs_run"] * slab, pairs)
        and np.abs(f32(new)).max() > 0
    )
    return report


def logprob_check(cfg, ref, seq_lens, row_len: int, seed: int = 0,
                  n_reference: int = 2, remat="full") -> dict:
    """One packed row of `seq_lens` random sequences: the gradient
    program's log-probs, the forward-only program's, the reference's."""
    rng = np.random.default_rng(seed)
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.zeros((1, row_len), np.int32)
    seg, pos = np.zeros_like(tokens), np.zeros_like(tokens)
    off, seqs = 0, []
    for i, n in enumerate(seq_lens):
        seqs.append(rng.integers(1, cfg.vocab_size, n).astype(np.int32))
        tokens[0, off: off + n], seg[0, off: off + n] = seqs[-1], i + 1
        pos[0, off: off + n] = np.arange(n)
        off += n
    assert off <= row_len, (off, row_len)
    batch = tuple(jnp.asarray(a) for a in (tokens, seg, pos))

    def logprobs(p, tokens, seg, pos, remat):
        x, aux, counts = tfm.hidden_states(
            p, cfg, tokens, seg, positions=pos, remat=remat,
            with_moe_counts=True)
        return tfm.per_token_output(p, cfg, x, tokens, seg), aux, counts

    @jax.jit
    def forward_only(p, tokens, seg, pos):
        return logprobs(p, tokens, seg, pos, False)[0]

    @jax.jit
    def inside_grad(p, tokens, seg, pos):
        def loss(p):
            lp, aux, counts = logprobs(p, tokens, seg, pos, remat)
            return jnp.sum(jnp.where(seg > 0, lp, 0.0)) + aux, (lp, counts)

        (_, (lp, counts)), grads = jax.value_and_grad(loss, has_aux=True)(p)
        norm = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                   for g in jax.tree.leaves(grads))
        return lp, counts, jnp.sqrt(norm)

    fwd = np.asarray(forward_only(params, *batch), np.float32)[0]
    grad, counts, grad_norm = inside_grad(params, *batch)
    grad = np.asarray(grad, np.float32)[0]
    pairs = row_len * cfg.n_experts_per_tok
    slab = tfm.expert_slab_rows(cfg, pairs)
    diffs = {"grad_vs_forward": [], "grad_vs_reference": [],
             "forward_vs_reference": []}
    off = 0
    for i, seq in enumerate(seqs):
        n = len(seq)
        g, f = grad[off: off + n - 1], fwd[off: off + n - 1]
        diffs["grad_vs_forward"].append(np.abs(g - f))
        if i < n_reference:
            want = np.asarray(ref.next_token_logprobs(params, cfg, seq))
            diffs["grad_vs_reference"].append(np.abs(g - want))
            diffs["forward_vs_reference"].append(np.abs(f - want))
        off += n
    report = {
        "check": "logprobs", "row_len": row_len, "seq_lens": list(seq_lens),
        "slab": slab, "pairs": pairs,
        "slab_fill_max": float(np.asarray(counts).sum(-1).max() / slab),
        "grad_norm": float(grad_norm), "tolerance": dict(ref.TOLERANCE),
    }
    ok = slab < pairs and np.isfinite(report["grad_norm"])
    for name, d in diffs.items():
        d = np.concatenate(d)
        report[f"{name}_mean_abs"] = float(d.mean())
        report[f"{name}_max_abs"] = float(d.max())
        ok = ok and bool(
            np.isfinite(d).all() and d.max() <= ref.TOLERANCE["max_abs"]
            and (name == "grad_vs_forward"
                 or d.mean() <= ref.TOLERANCE["mean_abs"]))
    report["ok"] = bool(ok)
    return report


def main():
    from benchmark import files
    from benchmark import run as brun

    p = argparse.ArgumentParser()
    p.add_argument("--config", action="append", required=True,
                   help="a file of benchmark/configs (repeatable)")
    p.add_argument("--rows", type=int, default=8192,
                   help="tokens in the packed row")
    p.add_argument("--tilt", type=float, default=2.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checks", default="dispatch,logprobs",
                   help="of dispatch, decode, logprobs")
    args = p.parse_args()
    checks = set(args.checks.split(","))
    ok = True
    for name in args.config:
        config = files.load_json("configs", name)
        cfg = brun.model_config(config)
        reports = [
            check(cfg, args.rows, tilt, args.seed)
            for which, check in (("dispatch", dispatch_check),
                                 ("decode", decode_check))
            if which in checks for tilt in (0.0, args.tilt)]
        if "logprobs" in checks:
            ref = files.load_module(
                "references", config["benchmark"]["reference"])
            lens = [args.rows // 5] * 4 + [args.rows // 10]  # 90% packed
            reports.append(logprob_check(cfg, ref, lens, args.rows, args.seed))
        for r in reports:
            print(json.dumps({"config": name, "backend": jax.default_backend(),
                              **r}), flush=True)
            ok = ok and r["ok"]
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()

"""The second readings of `benchmark/configs/dots3-note-prev-l5-e8-h8.json`'s
tolerances, on the chip at the published widths, over one random sequence
of the cell's compared length and weights as the cell draws them (the
configuration's `weights_seed`, bfloat16):

(1) the plain reference with ONE part of the mathematics wrong
(`references.dots3_note.FAULTS`: the three assumption controls — no latent
rescale, the gate on the un-normed input, no rope on the indexer — the
dense read, and the rest) or a precision lower (`LOWER_PRECISION`: latent
rows and index keys rounded to 8 bits, the router's scores to bfloat16,
together and apart), against the reference proper: mean and max |log-prob
difference| over all tokens and over the last 256; for the lower
precisions also `rows_readings` of what their caches would hold and the
share of selections that flip;
(2) the PROGRAM itself (`tfm.forward`, bfloat16) as it is — a first reading
— and under the controls a configuration can state (`latent_rescale`
off, `index_topk` 0: the dense read), each against the reference proper.

    chiprun -- python3 scripts/dots3_controls.py [n_tokens [fault ...]]

Writes chiprun_out/dots3_controls.json (with faults named: only those, to
chiprun_out/dots3_controls_faults.json); prints one line a control with the
limits beside it; exit code 0 when every control is refused by a limit and
the program proper by none.  A reading is evidence only from a TPU run."""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import transformer as tfm  # noqa: E402
from benchmark import files  # noqa: E402
from benchmark.references import dots3_note as ref  # noqa: E402
from benchmark.run import model_config  # noqa: E402

TAIL = 256  # the cell compares a sequence's last 256 tokens


def _diffs(got, want):
    d = np.abs(np.asarray(got, np.float64) - want)
    return {"mean_abs": float(d.mean()), "max_abs": float(d.max()),
            "tail_mean_abs": float(d[-TAIL:].mean()),
            "tail_max_abs": float(d[-TAIL:].max())}


def _refused(readings, rows=None):
    out = [k for k in ("mean_abs", "max_abs")
           if readings["tail_" + k] > ref.TOLERANCE[k]]
    if rows is not None:
        out += ref.rows_problems(rows, ref.ROWS_TOLERANCE)
    return out


def _selections(params, cfg, padded, **control):
    """(log-probs, every layer's kept rows, the full layers' selections)."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        ref._hidden_and_kept(
            params, cfg, jnp.asarray(padded), control.get("fault"),
            control.get("lower"),
            flips=lambda l, u, allowed: chosen.append(np.asarray(allowed)))
    got, kept = ref._next_token_logprobs(params, cfg, padded, **control)
    return got, kept, chosen


def _program_logprobs(params, cfg, tokens):
    @jax.jit
    def run(params, tok):
        x, _ = tfm.hidden_states(
            params, cfg, tok[None], jnp.ones((1, tok.shape[0]), jnp.int32))
        return tfm.per_token_output(
            params, cfg, x, tok[None], jnp.ones((1, tok.shape[0]), jnp.int32))

    return np.asarray(run(params, jnp.asarray(tokens)), np.float32)[0]


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 13312
    only = sys.argv[2:]  # faults alone, by name; none: every control
    config = files.load_json("configs", "dots3-note-prev-l5-e8-h8.json")
    cfg = model_config(config)
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = np.random.default_rng(64).integers(0, 259, n).astype(np.int32)
    padded = ref._padded(tokens)
    t0 = time.monotonic()
    want, kept, chosen = _selections(params, cfg, padded)
    want = want[: n - 1]
    print(f"reference proper, {n} tokens: {time.monotonic() - t0:.1f}s",
          flush=True)
    out = {"platform": jax.default_backend(), "n_tokens": n,
           "tolerance": {**ref.TOLERANCE, "rows": ref.ROWS_TOLERANCE}}
    ok = True

    def report(name, readings, problems, must_refuse=True):
        nonlocal ok
        out[name] = {**readings, "refused_by": problems}
        ok = ok and bool(problems) == must_refuse
        print(name, readings, "REFUSED by" if problems else "inside",
              problems, flush=True)

    k = cfg.index_topk
    for name in () if only else ("lower", "lower:cache", "lower:router"):
        got, low_kept, low_chosen = _selections(
            params, cfg, padded, lower=name)
        layers = [(np.arange(n), rows[:n], None if keys is None else keys[:n])
                  for rows, keys in low_kept]
        rows = ref.rows_readings(layers, kept, cfg)
        pairs = [ref.flips_between(jnp.asarray(a), jnp.asarray(b), k, n)
                 for a, b in zip(low_chosen, chosen)]
        total = max(len(chosen) * (n - k), 1)
        rows["select_flips"] = sum(a for a, _ in pairs) / total
        rows["select_keys_flipped"] = sum(b for _, b in pairs) / (total * k)
        readings = {**_diffs(got[: n - 1], want), **rows}
        report(name, readings, _refused(readings, rows),
               must_refuse=name != "lower:router")
    everywhere = np.arange(n)
    for name in only or ref.FAULTS:
        got, wrong = ref._next_token_logprobs(params, cfg, padded, fault=name)
        rows = ref.rows_readings(
            [(everywhere, r[:n], None if i is None else i[:n])
             for r, i in wrong], kept, cfg)
        rows["select_keys_flipped"] = 0.0  # not read for a fault
        readings = {**_diffs(got[: n - 1], want), **rows}
        report("fault:" + name, readings, _refused(readings, rows))
    # The program itself, bfloat16, and under the controls a configuration
    # states.
    for name, change in () if only else (
        ("program", {}),
        ("program:no_rescale", dict(latent_rescale=False)),
        ("program:dense_read", dict(index_topk=0)),
    ):
        got = _program_logprobs(
            params, dataclasses.replace(cfg, **change), tokens)
        readings = _diffs(got[: n - 1], want)
        report(name, readings, _refused(readings), must_refuse=bool(change))
    os.makedirs("chiprun_out", exist_ok=True)
    name = "dots3_controls_faults.json" if only else "dots3_controls.json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

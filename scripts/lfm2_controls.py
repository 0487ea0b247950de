"""The second readings of `benchmark/configs/lfm2-8b-a1b-e8.json`'s
tolerances, on the chip at the published widths: the plain reference with
ONE part of the mathematics wrong (`references.lfm2_moe.FAULTS`) or a
precision lower (`lower`), against the reference proper, over one sequence
of the cell's compared length — mean and max |log-prob difference|, and the
relative error of what a cache would keep (the conv layers' last two gated
inputs, the attention layer's roped K and V).  Weights as the cell draws
them (the configuration's `weights_seed`, bfloat16).

    chiprun -- python3 scripts/lfm2_controls.py [n_tokens]

Writes chiprun_out/lfm2_controls.json; prints one line a control with the
limits beside it.  A reading is evidence only from a TPU run."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import transformer as tfm  # noqa: E402
from benchmark import files  # noqa: E402
from benchmark.references import lfm2_moe as ref  # noqa: E402
from benchmark.run import model_config  # noqa: E402


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2763
    config = files.load_json("configs", "lfm2-8b-a1b-e8.json")
    cfg = model_config(config)
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = np.random.default_rng(48).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    padded = ref._padded(tokens)
    want, left = ref._next_token_logprobs(params, cfg, padded)
    want = want[: n - 1]
    out = {"platform": jax.default_backend(), "n_tokens": n,
           "tolerance": {**ref.TOLERANCE, **ref.ROWS_TOLERANCE}}
    for kind, names in (("fault", ref.FAULTS),
                        ("lower", ("lower", "lower:router", "lower:cache"))):
        for name in names:
            got, rows = ref._next_token_logprobs(
                params, cfg, padded, **{kind: name})
            d = np.abs(got[: n - 1] - want)
            # What the control's cache would hold, as `rows_readings`
            # reads a program's: tails at the last tokens, K/V of every one.
            layers = [
                r[n - (cfg.sconv_kernel - 1): n] if mixer == "C" else r[:n]
                for mixer, r in zip(cfg.window_pattern, rows)
            ]
            out[name] = {
                "mean_abs": float(d.mean()), "max_abs": float(d.max()),
                **ref.rows_readings(cfg, layers, left, n),
            }
            refused = [k for k, v in out[name].items()
                       if v > out["tolerance"][k]]
            print(name, out[name], "REFUSED by" if refused else "inside",
                  refused, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lfm2_controls.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

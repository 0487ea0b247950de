"""The second readings of `benchmark/configs/olmo-hybrid-7b-l4-v8.json`'s
tolerances, on the chip at the published widths: the plain reference with
ONE part of the mathematics wrong (`references.olmo_hybrid.FAULTS`) or a
precision lower (`LOWER_PRECISION`: S and the gates rounded to bfloat16,
together and apart), against the reference proper, over one sequence of the
cell's compared length — mean and max |log-prob difference| and, for the
lower precisions, `state_readings` of the S they end on; then the SYSTEM's
static program with its decode step's state rounded to bfloat16, which
`check_state` has to refuse.  Weights as the cell draws them (the
configuration's `weights_seed`, bfloat16).

    chiprun -- python3 scripts/olmoh_controls.py [n_tokens]

Writes chiprun_out/olmoh_controls.json; prints one line a control with the
limits beside it; exit code 0 when the rounded state is refused.  A reading
is evidence only from a TPU run."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import linear_attention  # noqa: E402
from areal_tpu.models import transformer as tfm  # noqa: E402
from benchmark import files  # noqa: E402
from benchmark.references import olmo_hybrid as ref  # noqa: E402
from benchmark.run import model_config  # noqa: E402


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 642
    config = files.load_json("configs", "olmo-hybrid-7b-l4-v8.json")
    cfg = model_config(config)
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = np.random.default_rng(59).integers(0, 259, n).astype(np.int32)
    padded = np.zeros(-(-n // ref.PAD_TO) * ref.PAD_TO, np.int32)
    padded[:n] = tokens
    want, (state, tail, _) = ref._next_token_logprobs(
        params, cfg, padded, None, n)
    want = want[: n - 1]
    out = {"platform": jax.default_backend(), "n_tokens": n,
           "tolerance": {**ref.TOLERANCE, "state": ref.STATE_TOLERANCE}}

    def report(name, readings, problems):
        out[name] = {**readings, "refused_by": problems}
        print(name, readings, "REFUSED by" if problems else "inside",
              problems, flush=True)

    for kind, names in (
        ("fault", ref.FAULTS),
        ("lower", ("bfloat16", "bfloat16:state", "bfloat16:gates")),
    ):
        for name in names:
            got, (low_state, low_tail, _) = ref._next_token_logprobs(
                params, cfg, padded, **{kind: name, "n_valid": n})
            d = np.abs(got[: n - 1] - want)
            readings = {"mean_abs": float(d.mean()), "max_abs": float(d.max())}
            problems = [k for k, v in readings.items() if v > ref.TOLERANCE[k]]
            if kind == "lower":
                more = ref.state_readings(low_state, low_tail, state, tail)
                readings.update(more)
                problems += ref.state_problems(more, ref.STATE_TOLERANCE)
            report(name, readings, problems)

    # The system proper, then with the state its decode step writes rounded.
    readings, problems = ref.check_state(params, cfg, tokens, state, tail)
    report("system", readings, problems)
    inner = linear_attention.linear_attn_step

    def rounded(h, blk, c, states, tails, li, *kernel):
        y, states, tails = inner(h, blk, c, states, tails, li, *kernel)
        return y, jax.lax.reduce_precision(states, 8, 7), tails

    linear_attention.linear_attn_step = rounded
    jax.clear_caches()
    readings, refused = ref.check_state(params, cfg, tokens, state, tail)
    report("system_state_bf16", readings, refused)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/olmoh_controls.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0 if refused and not problems else 1


if __name__ == "__main__":
    sys.exit(main())

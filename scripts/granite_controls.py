"""The second readings of `benchmark/configs/granite-4.0-h-micro-l10.json`'s
tolerances, on the chip at the published widths.  (1) The plain reference
with a precision lower (`lower`: the state S, dt and the decay rounded to
bfloat16 at every step, together and one alone) against the reference
proper over one sequence of the cell's compared length: mean and max
|log-prob difference|, and `state_readings` of the control's S.  (2) The
SERVING PLANE itself (`references.granitemoehybrid.check_generator`: 96
requests over 64 slots) as it is — the first readings — and with the ragged
recurrence's new state rounded to bfloat16 before it is written back (the
control the state limit has to refuse): in BOTH forms of the state's part,
the `jnp` `mamba.ssd_slab` and the Pallas kernel `ssm_slab.ssm_slab_step`
that takes its place on a TPU backend (its buffer rounded as it comes out).
Weights as the cell draws them (the configuration's `weights_seed`,
bfloat16).

    chiprun -- python3 scripts/granite_controls.py [n_tokens]

Writes chiprun_out/granite_controls.json; prints one line a reading with
the limits beside it.  A reading is evidence only from a TPU run."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import mamba  # noqa: E402
from areal_tpu.models import transformer as tfm  # noqa: E402
from areal_tpu.ops.pallas import ssm_slab  # noqa: E402
from benchmark import files  # noqa: E402
from benchmark.references import granitemoehybrid as ref  # noqa: E402
from benchmark.references.qwen3_next import state_readings  # noqa: E402
from benchmark.run import model_config  # noqa: E402


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 194
    config = files.load_json("configs", "granite-4.0-h-micro-l10.json")
    cfg = model_config(config)
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = np.random.default_rng(53).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    padded = ref._padded(tokens)
    cpu = jax.default_backend() == "cpu"
    state_tol = ref.STATE_TOLERANCE_FP32 if cpu else ref.STATE_TOLERANCE
    want, state, tail = ref._next_token_logprobs(params, cfg, padded, None, n)
    out = {"platform": jax.default_backend(), "n_tokens": n,
           "tolerance": {**ref.TOLERANCE, **state_tol}}
    for lower in ("bfloat16", "bfloat16:state", "bfloat16:gates"):
        got, s, t = ref._next_token_logprobs(params, cfg, padded, lower, n)
        d = np.abs(got[: n - 1] - want[: n - 1])
        out[lower] = {"mean_abs": float(d.mean()), "max_abs": float(d.max()),
                      **state_readings(s, t, state, tail)}
        print("reference,", lower, out[lower], flush=True)

    readings, problems = ref.check_generator(params, cfg, tokens)
    out["serving_plane"] = {**readings, "problems": problems}
    print("serving plane", readings, problems or "inside", flush=True)

    slab, step = mamba.ssd_slab, ssm_slab.ssm_slab_step

    def rounded(*args):
        y, new = slab(*args)
        return y, jax.lax.reduce_precision(new, 8, 7)

    def rounded_step(*args, **kw):
        states, y = step(*args, **kw)
        return jax.lax.reduce_precision(states, 8, 7), y

    mamba.ssd_slab, ssm_slab.ssm_slab_step = rounded, rounded_step
    try:
        readings, problems = ref.check_generator(params, cfg, tokens)
    finally:
        mamba.ssd_slab, ssm_slab.ssm_slab_step = slab, step
    out["serving_plane_state_bf16"] = {**readings, "problems": problems}
    print("serving plane, state rounded to bfloat16", readings,
          "REFUSED by" if problems else "INSIDE (the limit does not hold)",
          problems, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/granite_controls.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0 if problems else 1


if __name__ == "__main__":
    sys.exit(main())

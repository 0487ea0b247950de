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
the limits beside it.  A reading is evidence only from a TPU run.

(3) `--scope-split [--seed N]`: ONE traced run of
`granite4hm-serving-waves` (`benchmark.run` unchanged, `--trace 1`) whose
reduced trace is also written, by operation, to
chiprun_out/granite_scope_split.json: every device operation under
`gen/serving_chunk/.../layer/ssm` in milliseconds an inner step, summed by
the mixer's scopes (`in_proj`, `ssm_ragged/conv`, `ssm_ragged/ssd_scan`,
`out_norm_proj`) and, inside `ssd_scan`, the kernel `ssm_slab_step` apart
from the `jnp` operations around it (the gathers to and from the slab, the
decays and `keep`, the D skip; before PR 63 `slab_terms`' fusions too), each
by its HLO name — what PERF.md section 5 item 0g quotes."""
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


def scope_split(argv):
    """`--scope-split`: the cell traced, the mixer's operations dumped."""
    from benchmark import run as bench_run
    from benchmark.metrics import _ssmd

    seed = argv[argv.index("--seed") + 1] if "--seed" in argv else "6300001"
    check_run, runs = bench_run.checks.check_run, []
    # The harness hands its `Run` (steps, reduced trace) to the checks.
    bench_run.checks.check_run = lambda run: runs.append(run) or check_run(run)
    rehearsal = ["--cpu-rehearsal"] if "--cpu-rehearsal" in argv else []
    rc = bench_run.main([
        "--workload", "granite4hm-serving-waves", "--seed", seed,
        "--seconds", "45", "--trace", "1"] + rehearsal)
    run = runs[-1]
    if not run.trace:  # a CPU rehearsal's trace has no device planes
        print("scope split: no device trace to split", flush=True)
        return rc
    inner = _ssmd.inner_steps(run) * run.trace["traced_steps"]
    ops = {
        name: 1e3 * s / inner
        for name, s in run.trace["op_seconds_scoped"].items()
        if "gen/serving_chunk" in name and "/layer/ssm" in name}
    parts = ("in_proj", "ssm_ragged/conv", "ssm_ragged/ssd_scan",
             "out_norm_proj")
    by_part = {
        part: sum(ms for name, ms in ops.items() if f"/{part}" in name)
        for part in parts}
    scan = {n: ms for n, ms in ops.items() if "/ssm_ragged/ssd_scan" in n}
    kernel = sum(ms for n, ms in scan.items() if "ssm_slab_step" in n)
    out = {
        "seed": int(seed), "inner_steps_traced": inner,
        "layer_ssm_ms": sum(ops.values()), "by_scope_ms": by_part,
        "ssd_scan_kernel_ms": kernel,
        "ssd_scan_around_kernel_ms": by_part["ssm_ragged/ssd_scan"] - kernel,
        "pool_last_step": {
            k: v for k, v in run.steps[-1].get("pool", {}).items()
            if k.startswith("ssm_") or k == "chunks"},
        "ssd_scan_ops_ms": dict(sorted(scan.items(), key=lambda kv: -kv[1])),
        "other_ops_ms": dict(sorted(
            ((n, ms) for n, ms in ops.items() if n not in scan),
            key=lambda kv: -kv[1])),
    }
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/granite_scope_split.json", "w") as f:
        json.dump(out, f, indent=1)
    print("scope split, ms an inner step:", json.dumps(
        {k: v for k, v in out.items() if not k.endswith("ops_ms")}),
        flush=True)
    for name, ms in list(out["ssd_scan_ops_ms"].items())[:24]:
        print(f"  {ms:8.4f}  {name}", flush=True)
    return rc


def main():
    if "--scope-split" in sys.argv:
        return scope_split(sys.argv[1:])
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

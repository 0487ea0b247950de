"""The second readings of `benchmark/configs/sdar-30b-a3b-chat-l8-e16.json`'s
tolerances, on the chip at the published widths, THROUGH THE HARNESS'S OWN
CHECK: one run of the cell `sdar-rollout64-512` by `benchmark.run`, as the
driver runs it, whose warm-up step's `checks.reference_check` is made once
with the reference proper (the run's own verdict) and once more a control
with `references.sdar_moe.next_token_logprobs` standing in with ONE part of
its mathematics wrong (`FAULTS`: causal in the clean stream, the masked
block seeing its own clean tokens, the shifted read, blocks of 8, no q/k
norm, top-k not renormalised) or a precision lower (`LOWER_PRECISION`: the
router's probabilities in bfloat16, the cached K and V in e4m3, together and
apart) — against the SAME rollout: the timed generator's returned log-probs
and the trainer's recomputed ones, 1,024 response tokens.  A control is
REFUSED where `reference_check` itself reports `ok` False: by the mean or
the max of a comparison, or — a lower precision — by the rows the
generator's cache holds (`check_generator`, which then hands back NaN).
The controls hold the warm-up step's generate request for about a quarter
of an hour, so the master's wait for a request's batch (600 s) is
lengthened for this run.

    chiprun -- python3 scripts/sdar_controls.py [--seed N] [control ...]

(`--cpu-rehearsal`: the same at toy size here, to debug the script; the
controls are then held to the bf16 limits of a trainer in bfloat16, which
refuse little.)

Writes chiprun_out/sdar_controls.json (every report, and for a lower
precision the rows' readings); prints one line a control; exit code 0 when
the run is `correct`, every control but the ones named below as under the
system's own bf16 is refused, and those are not.  A reading is evidence
only from a TPU run."""
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.system.buffer import SequenceBuffer  # noqa: E402
from benchmark import checks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.references import sdar_moe as ref  # noqa: E402

CELL = "sdar-rollout64-512"
LOWER = (ref.LOWER_PRECISION, "lower:cache", "lower:router")
CONTROLS = tuple(f"fault:{f}" for f in ref.FAULTS) + LOWER
# Below the system's own bf16 activations on the chip (as PR 32, 38, 40 and
# 44 found of a router in bfloat16): refused on the CPU under the `fp32`
# bounds (tests/test_sdar.py), by no limit here.
BELOW_BF16 = ("lower:router",)


def _refused_by(report):
    """The readings of a `reference_check` report outside their limits."""
    out = []
    for who in ("generator", "trainer", "gen_vs_trainer"):
        for stat in ("mean_abs", "max_abs"):
            value = report[f"{who}_{stat}"]
            if value != value:  # NaN: `check_generator` refused the rows
                return ["rows"]
            if value > ref.TOLERANCE[stat]:
                out.append(f"{who}_{stat}")
    return out


def main(argv):
    seed, wanted, more = "68", [], []
    while argv:
        arg = argv.pop(0)
        if arg == "--seed":
            seed = argv.pop(0)
        elif arg == "--cpu-rehearsal":
            more.append(arg)
        else:
            wanted.append(arg)
    wanted = wanted or list(CONTROLS)
    out = {"tolerance": {**ref.TOLERANCE, "rows": ref.ROWS_TOLERANCE}}
    inner = checks.reference_check

    def with_controls(obs, rollout):
        report = out["proper"] = inner(obs, rollout)
        proper = ref.next_token_logprobs
        for name in wanted:
            kw = ({"fault": name[len("fault:"):]} if name.startswith("fault:")
                  else {"lower": name})
            ref.next_token_logprobs = functools.partial(proper, **kw)
            try:
                out[name] = inner(obs, rollout)
            finally:
                ref.next_token_logprobs = proper
            print(f"[controls] {name} {out[name]}", file=sys.stderr,
                  flush=True)
        out["rows"] = {str(low): found[0] for _, low, found in ref._CHECKED}
        return report

    checks.reference_check = with_controls
    wait = SequenceBuffer.get_batch_for_rpc
    SequenceBuffer.get_batch_for_rpc = (
        lambda self, rpc, timeout=None: wait(self, rpc, None))
    rc = bench_run.main([
        "--workload", CELL, "--seed", seed, "--seconds", "45", "--trace", "0",
        *more])
    ok = rc == 0 and out.get("proper", {}).get("ok") is True
    for name in ["proper"] + wanted:
        report = out.get(name)
        if report is None:
            ok = False
            continue
        report["refused_by"] = _refused_by(report)
        refused = not report["ok"]
        must = name != "proper" and name not in BELOW_BF16
        ok = ok and refused == must
        print(name, "REFUSED by" if refused else "inside", report["refused_by"],
              {k: v for k, v in report.items() if k.endswith("_abs")},
              flush=True)
    print("rows", out.get("rows"), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "sdar_controls.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

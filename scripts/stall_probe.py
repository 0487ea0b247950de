"""Run one benchmark cell and keep what the program said about its steps.

    python3 scripts/stall_probe.py --workload <cell> --seed <n> \
        --seconds 45 --trace 0            (benchmark.run's own arguments)
    python3 scripts/stall_probe.py --span-cost

The program keeps a step ledger, a host watch and a flight ring in every
run, with no switch (areal_tpu/base/tracer.py); the benchmark's result
line shows them only in a traced run, and only as shares.  This runs
`benchmark.run.main` unchanged, in this process, and then writes
`chiprun_out/stall/<cell>_<seed>_t<trace>.json`: per timed step the
benchmark's own `wall_s` and host watch beside the program's `host/*`,
`time/*`, `hbm/*` and `*/perf/self_s` stats, the warm-up step's the same
(its `hbm/*` hold the account of the peak), the ledger's closed steps
(seconds and self seconds per span name, the programs' rows, the `hbm`
record with its rises), the newest HBM marks, the harness's own
`memory_peak_bytes`, the nine `hbm_*` readers of `benchmark/metrics/`
(no `BENCHMARK.json` entry carries them yet) and every `host_pause` and
`slow_step` flight event, whole.  PERF.md section 6 (PR 36, PR 66) has
the tables made of it.

`--span-cost` times a span (tracing as `AREAL_TRACE` says) and one reading
of the host watch, on this host, and runs nothing else.
"""
import json
import os
import sys
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KEEP = ("host/", "time/", "hbm/")
HBM_READERS = (
    "hbm_weights_gb", "hbm_moments_gb", "hbm_cache_gb", "hbm_other_live_gb",
    "hbm_code_gb", "hbm_step_temp_gb", "hbm_peak_before_step_gb",
    "hbm_unaccounted_gb", "hbm_peak_rise_in_window_gb",
)


def kept(stats):
    return {
        k: v for k, v in stats.items()
        if k.startswith(KEEP) or k.endswith("perf/self_s")
    }


def span_cost():
    import jax  # noqa: F401  (a span opens an inert profiler annotation)

    from areal_tpu.base import tracer

    def one():
        with tracer.span("x", cat="compute", a=1):
            pass

    tracer.configure(role="probe")  # AREAL_TRACE decides, as in a trial
    n = 200000
    out = {
        "tracing": tracer.enabled(),
        "span_us": min(timeit.repeat(one, number=n, repeat=5)) / n * 1e6,
    }
    if hasattr(tracer, "close_step"):
        reads = []
        for i in range(20):
            t0 = time.perf_counter()
            tracer.close_step(i)
            reads.append(time.perf_counter() - t0)
        out["close_step_ms_median"] = sorted(reads)[len(reads) // 2] * 1e3
        out["host_read_ms"] = (
            tracer.step_ledger()[-1]["host"]["read_s"] * 1e3
        )
        out["threads"] = len(os.listdir("/proc/self/task"))
    print(json.dumps(out), flush=True)


def main():
    if "--span-cost" in sys.argv:
        return span_cost()
    from areal_tpu.base import tracer
    from benchmark import run as bench

    runs = []
    inner = bench.checks.check_run
    bench.checks.check_run = lambda run: (runs.append(run), inner(run))[1]
    rc = bench.main(sys.argv[1:])
    (run,) = runs
    steps = []
    for s in run.steps:
        steps.append({
            "wall_s": s["wall_s"],
            "bench_host": s["host"],
            "bench_spans": s["spans"],
            "stats": kept(s["stats"]),
        })
    out = {
        "cell": run.cell_name, "seed": run.seed, "traced": run.traced,
        "steps": steps,
        "warmup": kept((run.warmup or {}).get("stats", {})),
        "memory_peak_bytes": run.peak_bytes,
        "hbm_readers": {
            name: bench.files.load_module("metrics", name).read(run)
            for name in HBM_READERS
        },
        "hbm_marks": getattr(tracer, "hbm_marks", list)()[-256:],
        # (a program from before PR 36 has no ledger: the probe then keeps
        # the benchmark's own record alone)
        "ledger": getattr(tracer, "step_ledger", list)(),
        "flight": [
            e for e in tracer.flight_events()
            if e["kind"] in ("host_pause", "slow_step")
        ],
    }
    d = os.path.join("chiprun_out", "stall")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(
        d, f"{run.cell_name}_{run.seed}_t{int(run.traced)}.json"
    )
    with open(path, "w") as f:
        json.dump(out, f)
    print(f"[stall_probe] wrote {path}", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""Run ONE cell of the benchmark once, in this process.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the trial the way `apps/quickstart.py ppo-math` does
(`experiments.common.build_ppo_math` + `apps.main.run_experiment_inproc`:
master, in-process worker pool, PPO actor interface, the math verifier,
generator, trainer, weight hand-back), runs one warm-up step, then times
WHOLE steps on the benchmark's own clock until `--seconds` have passed
since the first timed step began (the step in flight finishes; at least
two timed steps).  The last line of stdout is one JSON object.

Three keys are data, for a cell whose step is not fixed by its shapes (a
MoE decode step takes as long as the experts its rows touch, so its pace
follows the weights drawn and the tokens sampled, and it drifts as the
updates move the router; PERF.md section 6, PR 30):

  * `"timed_steps": N` in `workloads/<cell>.json`: the window closes
    after N timed steps, or on the clock, whichever comes first, so every
    run, and a faster program beside its parent, times the same N points
    of the drift.  Without the key the clock alone decides.
  * `"weights_seed": n` in the `benchmark` group of `configs/<config>.json`
    is the trial's seed (the program has one: weights and sampling), as a
    deployed model is one set of weights.  Without the key it is `--seed`.
  * `"traffic_seed": n` in `workloads/<cell>.json` draws the cell's rows
    whatever `--seed` is.  Without the key `--seed` draws them: which row
    gets which length, the operands, the filler.  A cell with both seeds
    fixed runs the same computation under every `--seed`.

The program is driven as `chip_smoke.py` drives it (bf16 master + Adam,
lr 1e-4, two minibatches, micro-batches of 8,192 tokens, a zero value
baseline without advantage normalisation so that the verifier's constant
-5 on a random model still gives a gradient) and otherwise with its
defaults.  No cell sets an engine option or an `AREAL_*` variable.

Everything the benchmark observes it observes from outside, through the
`inspect(master, stage)` hook: it wraps each worker's `handle_request`
with its own clock (one span per request: fetch, generate, reward,
train, param_sync), keeps the rollout batch `generate` returned, puts a
loader that cycles the traffic file's fixed batches in the shuffling
one's place, watches the host for pauses (a ticking thread, the garbage
collector's callbacks), and wraps the master's step-stats logger, which
is also how it stops the run — no option of the program is involved.

What a metric reader (`metrics/<name>.py`, `read(run)`) may rely on is
the `Run` record below; of `run.trace` (traced run only) the keys
`reduce_trace` lists.  A cell is added by files alone (`benchmark/files.py`)
and an entry in `BENCHMARK.json`; where it reports `gen_tokens_per_s` or
another metric whose entry carries a `workloads` list (the decode and
serving metrics), it appends its name to that list.

Without a TPU, or with fewer chips than the cell asks for, this exits
non-zero and prints no result.  `--cpu-rehearsal` runs the same code at
toy size on the CPU to debug the benchmark itself; it says `platform=cpu`
and prints no result line.
"""

import time

T_START = time.monotonic()  # process start, as near as Python lets us

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from benchmark import checks, files  # noqa: E402
from benchmark.tokenizer import ByteTokenizer  # noqa: E402

# What `chip_smoke.py`'s plan sets, and nothing else.
PLAN = dict(
    lr=1e-4,
    n_minibatches=2,
    mb_tokens=8192,
    master_dtype="bfloat16",
)
MAX_STEPS = 100000  # the stop comes from the clock, not from this
TRACE_SKIP = 1  # steady steps before the profiler starts (traced run)
TRACE_STEPS = 2  # steady steps under the profiler
EOS_FIXTURE = 257  # `CharTokenizer`'s EOS id, for traffic with a reachable EOS
GEN_COUNTERS = (
    "decode_compiles", "prefill_dispatches", "lanes_dispatched",
    "lanes_live", "lanes_slack", "dead_live_lanes", "cache_copy_bytes",
    "serving_lane_budget",
)


def log(msg):
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# The run's record: what the metric readers and the checks are handed.
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    cell_name: str
    cell: dict
    config: dict
    traffic: dict
    model_cfg: object
    chips: int
    device_kind: str
    peaks: dict
    seed: int
    traced: bool
    # One dict per TIMED step: wall_s (benchmark clock, end of the step
    # before to end of this one), stats (the program's step stats), spans
    # ({label: seconds}, benchmark clock around each worker request),
    # gen and pool (generator counters and `last_pool_stats` after its
    # generate call), pack (the train engine's `last_pack_stats`),
    # prompt_lens / seq_lens (per sequence, counted from the rollout batch
    # the generator returned), host (HostWatch.take(): seconds the garbage
    # collector ran and seconds this process's threads were not scheduled).
    steps: list = dataclasses.field(default_factory=list)
    warmup: dict = None  # the warm-up step, same record without wall_s
    setup_s: float = 0.0
    peak_bytes: int = 0
    compiles_in_window: int = 0
    programs: list = dataclasses.field(default_factory=list)
    trace: dict = None  # reduce_trace() below, traced run only
    reference: dict = None  # checks.reference_check() report
    handback: dict = None  # checks.handback_check() report

    # -- helpers the readers share ----------------------------------------
    def total(self, fn):
        return float(sum(fn(s) for s in self.steps))

    def span_median(self, label):
        vals = [s["spans"][label] for s in self.steps if label in s["spans"]]
        return statistics.median(vals) if vals else None

    def gen_tokens(self, step):
        return sum(step["seq_lens"]) - sum(step["prompt_lens"])


# --------------------------------------------------------------------------
# Building the trial
# --------------------------------------------------------------------------


def toy(config, traffic):
    """Toy sizes for --cpu-rehearsal: same files, same routes, tiny work.
    The seven keys every dense config has are shrunk here; whatever else
    a family sizes itself by (experts and their width, a `head_dim` key,
    windows, ranks) the config file shrinks itself, in the `toy` dict of
    its `benchmark` group, which is applied last and so may also override
    the seven."""
    config = dict(
        config, hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
        max_position_embeddings=1024,
    )
    config.update(config["benchmark"].get("toy", {}))
    config["benchmark"] = dict(config["benchmark"], param_dtype="float32")
    pl = dict(traffic["prompt_len"])
    for k in ("lo", "hi", "median"):
        if k in pl:
            pl[k] = max(24, pl[k] // 16)
    traffic = dict(
        traffic, prompt_len=pl,
        max_new_tokens=max(4, traffic["max_new_tokens"] // 64),
        dataset_max_length=max(64, traffic["dataset_max_length"] // 8),
    )
    return config, traffic


def model_config(config):
    """The program's ModelConfig for a config file, through the reader the
    program uses for a published checkpoint's config.json."""
    from areal_tpu.models.hf.registry import HF_FAMILIES

    cfg = HF_FAMILIES[config["model_type"]].config_from_hf(config)
    return dataclasses.replace(
        cfg, param_dtype=config["benchmark"]["param_dtype"]
    )


def build_plan(run, rows, tok, fileroot):
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.base.topology import ParallelConfig
    from areal_tpu.experiments.common import PPOMathConfig, build_ppo_math
    from areal_tpu.system.master import ExperimentSaveEvalControl

    traffic = run.traffic
    layout = run.config["benchmark"]["layout"]
    overrides = {}
    if layout.get("actor_parallel"):
        overrides["actor_parallel"] = ParallelConfig.from_str(
            layout["actor_parallel"]
        )
    if layout.get("gen_parallel"):
        overrides["gen_parallel"] = ParallelConfig.from_str(
            layout["gen_parallel"]
        )
    cfg = PPOMathConfig(
        experiment_name="benchmark",
        trial_name=run.cell_name,
        actor=ModelAbstraction("random", {"config": run.model_cfg}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows,
             "max_length": traffic["dataset_max_length"]},
        ),
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(
            n=traffic["group"], max_new_tokens=traffic["max_new_tokens"],
            temperature=1.0,
        ),
        optimizer=OptimizerConfig(lr=PLAN["lr"], warmup_steps_proportion=0.0),
        ppo_kwargs={
            "n_minibatches": PLAN["n_minibatches"], "kl_ctl": 0.0,
            "disable_value": False, "adv_norm": False,
        },
        mb_spec=MicroBatchSpec(max_tokens_per_mb=PLAN["mb_tokens"]),
        batch_size=traffic["n_prompts"],
        # An epoch is the traffic's cycle of batches (one, by default:
        # every step the same prompts, a fixed amount of work).  The
        # batches come from FixedBatches, not from the program's loader.
        total_train_epochs=MAX_STEPS,
        ctrl=ExperimentSaveEvalControl(),
        fileroot=fileroot,
        train_backend_args={"master_dtype": PLAN["master_dtype"]},
        seed=trial_seed(run.config, run.seed),
        **overrides,
    )
    return build_ppo_math(cfg, tok)


def traffic_rows(cell, traffic, seed):
    """The cell's rows from the traffic mix's generator: drawn from the
    cell's own `traffic_seed` where its file has one, else from `--seed`."""
    generator = files.load_module("traffic", traffic["generator"])
    return generator.generate(traffic, cell.get("traffic_seed", seed))


def trial_seed(config, seed):
    """The one seed the program takes (weights and sampling): the
    configuration's own draw where its speed depends on the weights
    (`weights_seed`, module docstring), else `--seed`."""
    return int(config["benchmark"].get("weights_seed", seed))


# --------------------------------------------------------------------------
# Observation from outside: spans, counters, the window, the profiler
# --------------------------------------------------------------------------


class FixedBatches:
    """Stands in for the worker's shuffling loader: the traffic file's
    batches in a fixed cycle — by default ONE batch, every step the same,
    its rows in order of prompt length whatever the seed.  The loader
    reshuffles each epoch from the trial's seed, and the train engine's
    packed shapes follow the row order: a reshuffled step brought new
    gradient programs into the window, and another seed's order a train
    step 1.5% slower (PERF.md, Findings, PR 22).  Traffic with `batches`
    > 1 offers what a real run does, a new batch in the order drawn every
    step, and pays for it inside the window."""

    def __init__(self, dataset, batch_size, row_ids):
        from areal_tpu.api.data_api import SequenceSample

        by_id = {s.ids[0]: s for s in (dataset[j] for j in range(len(dataset)))}
        items = [by_id[q] for q in row_ids]
        self.batches = []
        for i in range(0, len(items), batch_size):
            batch = items[i: i + batch_size]
            if len(items) == batch_size:  # the one batch of every step
                batch.sort(
                    key=lambda s: (s.seqlens["packed_prompts"][0][0], s.ids[0])
                )
            self.batches.append(SequenceSample.gather(batch))
        self.n_served = 0

    def __iter__(self):
        return self

    def __next__(self):
        self.n_served += 1
        return self.batches[(self.n_served - 1) % len(self.batches)]


class HostWatch:
    """What stalls a step from the host's side, on the benchmark's clock:
    seconds the garbage collector ran (`gc.callbacks`), and seconds a
    thread that sleeps TICK at a time woke later than LATE — the process
    was not scheduled, or one thread kept the interpreter lock."""

    TICK, LATE = 0.02, 0.1

    def __init__(self):
        self.gc_s = self.gc_max_s = self.late_s = self.late_max_s = 0.0
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        threading.Thread(target=self._tick, daemon=True).start()

    def _on_gc(self, phase, info):
        now = time.monotonic()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            self.gc_s += now - self._gc_t0
            self.gc_max_s = max(self.gc_max_s, now - self._gc_t0)

    def _tick(self):
        last = time.monotonic()
        while True:
            time.sleep(self.TICK)
            now = time.monotonic()
            late = now - last - self.TICK
            if late > self.LATE:
                self.late_s += late
                self.late_max_s = max(self.late_max_s, late)
            last = now

    def take(self):
        """Totals since the last call."""
        keys = ("gc_s", "gc_max_s", "late_s", "late_max_s")
        out = {k: getattr(self, k) for k in keys}
        for k in keys:
            setattr(self, k, 0.0)
        return out


class Observer:
    """Hooks the built trial.  One instance per run."""

    def __init__(self, run, seconds, trace_dir, row_ids):
        self.run = run
        self.seconds = seconds
        self.timed_steps = run.cell.get("timed_steps")  # None: the clock
        self.trace_dir = trace_dir
        self.row_ids = row_ids  # the traffic's rows, in the order generated
        self.host = HostWatch()
        self.events = {}  # in-window jax.monitoring durations, by event
        self.spans = []  # (label, t0, t1) since the last step boundary
        self.rollout = None  # the last batch generate() returned
        self.t_window = None
        self.window_open = False
        self.t_last = None
        self.n_logged = 0
        self.tracing = False
        self.compiles = 0
        self.setup_compiles = []  # seconds of each backend compile or load
        self.gen_sums_before = None  # checks.tree_sums, end of the warm-up
        self.master = None

    # -- wiring ------------------------------------------------------------
    def __call__(self, master, stage):
        if stage == "built":
            self.on_built(master)
        else:
            self.on_done(master)

    def on_built(self, master):
        import jax.monitoring

        self.master = master
        self.models, self.interfaces = {}, {}
        for w in master.pool.workers:
            self._wrap_worker(w)
            for key, m in w.models.items():
                self.models[key.split("@")[0]] = m
                self.interfaces[key.split("@")[0]] = w.interfaces[key]
        gen_if = self.interfaces["actor_gen"]
        inner_generate = gen_if.generate

        def generate(model, sample, mb_spec):
            out = inner_generate(model, sample, mb_spec)
            self.rollout = out
            if self.n_logged == 0 and self.run.reference is None:
                # Warm-up step, after generation and before the update:
                # generator and trainer hold the same weights, the ones
                # these log-probabilities were sampled under.
                self.run.reference = checks.reference_check(self, out)
            return out

        gen_if.generate = generate
        inner_log = master.stats_logger.log

        def log_step(step, stats):
            inner_log(step, stats)
            self.on_step_end(dict(stats))

        master.stats_logger.log = log_step
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.window_open:
            self.events[event] = self.events.get(event, 0.0) + duration
        if "backend_compile" not in event:
            return
        if self.window_open:
            self.compiles += 1
            log(f"compilation inside the window ({duration:.2f}s), after "
                f"{self.n_logged} steps, last request "
                f"{[s[0] for s in self.spans[-1:]]}")
        elif self.t_window is None:
            self.setup_compiles.append(duration)

    def _wrap_worker(self, worker):
        import jax

        worker.dataloaders = [
            FixedBatches(ds, worker.config.batch_size, self.row_ids)
            for ds in worker.datasets
        ]
        inner = worker.handle_request

        def handle_request(req):
            label = req["type"]
            if label == "mfc":
                label = (f"{req['model_name'].split('@')[0]}:"
                         f"{req['interface_type']}")
            elif "model_name" in req or "dst" in req:
                label += ":" + str(
                    req.get("dst") or req["model_name"]
                ).split("@")[0]
            t0 = time.monotonic()
            if self.tracing:
                with jax.profiler.TraceAnnotation(f"bench:{label}"):
                    out = inner(req)
            else:
                out = inner(req)
            self.spans.append((label, t0, time.monotonic()))
            if self.n_logged == 0:  # warm-up: where does the memory go
                log(f"  after {label}: HBM in use "
                    f"{bytes_in_use() / 1e9:.2f} GB")
            return out

        worker.handle_request = handle_request

    # -- step boundaries ----------------------------------------------------
    def on_step_end(self, stats):
        import jax

        gen = self.models["actor_gen"].engine
        train = self.models["actor"].engine
        if self.n_logged == 0:
            # End of the warm-up step, before the clock below is read, so
            # set-up pays: what the generator holds as the window opens.
            # The weight check after the window wants every matrix moved
            # from here, and finds this tree's program in the compile
            # cache.
            t0, b0 = time.monotonic(), bytes_in_use()
            self.gen_sums_before = checks.tree_sums(gen.get_params())
            b1 = bytes_in_use()
            checks.drop_programs()  # or its executable sits in the peak
            # What tracing that program left for the collector goes now:
            # on four chips it brought a full collection of 80 ms into
            # the first timed step (chip runs, PR 25).
            gc.collect()
            log(f"weight sums of {len(self.gen_sums_before)} generator "
                f"leaves in {time.monotonic() - t0:.3f}s; HBM in use "
                f"{b0} before, {b1} with the program loaded, "
                f"{bytes_in_use()} after dropping it")
        now = time.monotonic()
        self.n_logged += 1
        spans, self.spans = self.spans, []
        out = self.rollout
        lens = [l for g in out.seqlens["packed_input_ids"] for l in g]
        bounds = out.cu_seqlens("packed_input_ids")
        pmask = out.data["prompt_mask"]
        record = {
            "stats": stats,
            "spans": {},
            "host": self.host.take(),
            "gen": {k: int(getattr(gen, k)) for k in GEN_COUNTERS},
            "pool": dict(gen.last_pool_stats),
            "pack": dict(train.last_pack_stats),
            "seq_lens": [int(l) for l in lens],
            "prompt_lens": [
                int(pmask[bounds[i]: bounds[i + 1]].sum())
                for i in range(len(lens))
            ],
        }
        for label, t0, t1 in spans:
            record["spans"][label] = record["spans"].get(label, 0.0) + t1 - t0
        if self.n_logged == 1:
            # End of the warm-up step: the window opens here.
            self.run.warmup = record
            self.run.setup_s = now - T_START
            self.t_window = self.t_last = now
            self.window_open = True
            big = [round(d, 1) for d in self.setup_compiles if d >= 1.0]
            log(f"set-up {self.run.setup_s:.1f}s (warm-up step "
                f"{stats['time/step_s']:.1f}s; {len(self.setup_compiles)} "
                f"programs compiled or loaded since build in "
                f"{sum(self.setup_compiles):.1f}s, those over 1s: {big}); "
                f"window opens; HBM in use {bytes_in_use() / 1e9:.2f} GB")
        else:
            record["wall_s"] = now - self.t_last
            self.t_last = now
            self.run.steps.append(record)
            log(f"timed step {len(self.run.steps)}: {record['wall_s']:.3f}s "
                f"{ {k: round(v, 3) for k, v in record['spans'].items()} } "
                f"host { {k: round(v, 3) for k, v in record['host'].items()} }")
        n_timed = self.n_logged - 1
        if self.run.traced:
            if n_timed == TRACE_SKIP:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                self.tracing = True
                with jax.profiler.TraceAnnotation("bench:window"):
                    pass  # marks the window's start on the trace's clock
            elif n_timed == TRACE_SKIP + TRACE_STEPS:
                with jax.profiler.TraceAnnotation("bench:window"):
                    pass  # and its end
                self.tracing = False
                jax.profiler.stop_trace()
                self.stop()  # a traced run ends with its traced steps
            return
        if n_timed == self.timed_steps or (
            n_timed >= 2 and now - self.t_window >= self.seconds
        ):
            self.stop()

    def stop(self):
        """End the master's loop after this step: its loop runs while
        `step_info.global_step < total_steps`, and advances `step_info`
        right after the logger returns."""
        m = self.master
        m.step_info = dataclasses.replace(
            m.step_info, global_step=m._total_steps - 1
        )
        self.window_open = False
        self.run.compiles_in_window = self.compiles
        self.run.peak_bytes = peak_bytes(self.run.chips)
        slow = {k: round(v, 3) for k, v in self.events.items() if v >= 0.05}
        log(f"jax.monitoring durations inside the window, 0.05 s and over: "
            f"{slow}")

    def on_done(self, master):
        gen = self.models["actor_gen"].engine
        self.run.programs = sorted(
            sig[0] if isinstance(sig[0], str) else "static"
            for sig in gen._gen_fns
        )
        self.run.handback = checks.handback_check(
            self.models["actor"].engine, gen, self.gen_sums_before
        )
        log(f"weight check: {self.run.handback}")


def reduce_trace(path, chips):
    """`Run.trace` from a trace directory or an `.xplane.pb`: the profile
    read once and reduced twice.  Every key of
    `trace.reduce` as it was (`window_s`, `busy_s`, `busy_by_device`,
    `n_events`, `op_seconds`, `idle_seconds`, `loop_seconds`), then what
    the program's own names add (`program_trace.reduce`: `program_spans`,
    `busy_by_bench_span`, `idle_by_program_span`, `scope_seconds`,
    `kernel_seconds`, `op_seconds_scoped`), `traced_steps`, and
    `breakdown` from the second: the first's operation names and idle
    labels with the scope and phase, and the program span, as suffixes.
    A program without the names gives empty dicts and bare names."""
    from jax.profiler import ProfileData

    from benchmark import program_trace
    from benchmark import trace as trace_mod

    if os.path.isdir(path):
        path = trace_mod.find_xplane(path)
    profile = ProfileData.from_file(path)
    out = trace_mod.reduce(profile, chips)
    out.update(
        program_trace.reduce(profile, program_trace.op_paths(path), chips)
    )
    out["traced_steps"] = TRACE_STEPS
    return out


def bytes_in_use():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)


def peak_bytes(chips):
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:chips]
    ]
    return int(max(peaks))


# --------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy-size run on the CPU to debug the benchmark; no result line",
    )
    args = ap.parse_args(argv)
    cell, config, traffic = files.load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        seconds = float(files.benchmark_json()["run_seconds"])
    if args.cpu_rehearsal:
        config, traffic = toy(config, traffic)
        if cell["chips"] > 1:
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={cell['chips']}",
            )

    import jax

    backend = jax.default_backend()
    if args.cpu_rehearsal:
        if backend != "cpu":
            raise SystemExit("--cpu-rehearsal wants JAX_PLATFORMS=cpu, "
                             f"found backend {backend!r}")
    elif backend != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, but jax.default_backend() is "
            f"{backend!r}.  Nothing was run."
        )
    if len(jax.devices()) < cell["chips"]:
        raise SystemExit(
            f"benchmark: cell {args.workload!r} needs {cell['chips']} chips, "
            f"found {len(jax.devices())}.  Nothing was run."
        )
    if cell["chips"] != config["benchmark"]["layout"]["chips"]:
        raise SystemExit("cell and config disagree about the chips")
    if cell.get("timed_steps", 2) < 2:
        raise SystemExit("a cell's timed_steps is two or more")

    import logging

    from areal_tpu.apps.main import run_experiment_inproc
    from areal_tpu.base import compilation_cache
    from areal_tpu.base import logging as areal_logging

    # The program logs every step's whole stats dict at INFO; this
    # process's log is the benchmark's to keep short.
    areal_logging.getLogger().setLevel(logging.WARNING)
    from benchmark import peaks as peaks_mod

    cache_dir = compilation_cache.enable()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    log(f"platform={device['platform']} kind={device['kind']!r} "
        f"devices={device['count']} cell={args.workload} seed={args.seed} "
        f"trial_seed={trial_seed(config, args.seed)} "
        f"traffic_seed={cell.get('traffic_seed', args.seed)} "
        f"timed_steps={cell.get('timed_steps')} "
        f"seconds={seconds} trace={args.trace} cache={cache_dir} "
        f"({len(os.listdir(cache_dir))} entries)")
    run = Run(
        cell_name=args.workload, cell=cell, config=config, traffic=traffic,
        model_cfg=model_config(config), chips=cell["chips"],
        device_kind=dev.device_kind,
        peaks=(peaks_mod.peaks_for(dev.device_kind)
               if backend == "tpu" else None),
        seed=args.seed, traced=bool(args.trace),
    )
    rows = traffic_rows(cell, traffic, args.seed)
    # Past the vocabulary no sampled token is EOS (benchmark/tokenizer.py).
    tok = ByteTokenizer(
        eos_token_id=EOS_FIXTURE if traffic.get("eos_reachable")
        else run.model_cfg.vocab_size
    )
    with tempfile.TemporaryDirectory(prefix="benchmark_") as tmp:
        trace_dir = os.path.join(tmp, "trace")
        obs = Observer(run, seconds, trace_dir, [r["query_id"] for r in rows])
        plan = build_plan(run, rows, tok, os.path.join(tmp, "trial"))
        run_experiment_inproc(plan, tokenizer=tok, inspect=obs)
        if run.traced:
            t0 = time.monotonic()
            if args.cpu_rehearsal:
                log("a CPU trace has no device planes; nothing to reduce")
            else:
                run.trace = reduce_trace(trace_dir, run.chips)
                log(f"trace reduced in {time.monotonic() - t0:.1f}s: "
                    f"{run.trace['n_events']} device events, longest "
                    f"outermost loops {run.trace['loop_seconds']}, device "
                    f"busy inside each bench span "
                    f"{ {k: round(float(v), 3) for k, v in run.trace['busy_by_bench_span'].items() if v} }"
                    f", breakdown "
                    f"{json.dumps(run.trace['breakdown'])}")

    problems = checks.check_run(run)
    for p in problems:
        log(f"CHECK FAILED: {p}")
    metrics = {}
    for entry in files.metrics_for(args.workload, run.traced):
        value = files.load_module("metrics", entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {
                "value": float(value), "unit": entry["unit"]
            }
    n_seqs = traffic["n_prompts"] * traffic["group"]
    failed = sum(
        n_seqs for s in run.steps if s["stats"]["actor_train/quarantined"]
    )
    device["memory_peak_bytes"] = run.peak_bytes
    result = {
        "correct": not problems,
        "attempted": n_seqs * len(run.steps),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    log(f"{len(run.steps)} timed steps, walls "
        f"{[round(s['wall_s'], 3) for s in run.steps]}; reference "
        f"{run.reference}; programs {run.programs}; total "
        f"{time.monotonic() - T_START:.1f}s")
    if args.cpu_rehearsal:
        log("CPU REHEARSAL, not evidence about the chip; would print: "
            + json.dumps(result))
        log("rehearsal finished; no result line (platform=cpu)")
        return 0 if not problems else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

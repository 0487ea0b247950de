"""Find a cell's files by the names in BENCHMARK.json — the harness names
no cell, no model, no traffic mix and no metric itself.

    workloads/<cell>.json      config, traffic, chips, expected route,
                               and optionally `timed_steps` (the window
                               closes after that many timed steps or on
                               the clock, whichever comes first) and
                               `traffic_seed` (draws the rows whatever
                               `--seed` is)
    configs/<config>.json      the model's published keys + a "benchmark"
                               group (reference, layout, reduced, assumed,
                               and optionally `toy`: key overrides for
                               --cpu-rehearsal beyond the dense keys
                               `run.toy` shrinks itself — experts, a
                               `head_dim` key, windows; `weights_seed`:
                               the trial's seed whatever `--seed` is,
                               where the model's speed follows its draw)
    traffic/<traffic>.json     parameters of one traffic mix; its
                               "generator" key names traffic/<generator>.py
    references/<reference>.py  the architecture's plain fp32 forward pass
    metrics/<metric>.py        one reader: read(run) -> number or None
                               (`run`: `benchmark.run.Run`; its `trace`
                               keys are listed at `run.reduce_trace`;
                               `metrics/_program.py` has the shared
                               readers of scopes, kernels and counters)

A new cell also appends its name to the `workloads` list of every
BENCHMARK.json metric that has one and that it reports
(`gen_tokens_per_s`, the decode and serving metrics): `metrics_for` hands
a cell only the entries without a list or with its name in it.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    with open(path) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name):
    """(cell, config, traffic) dicts of the cell called `name`."""
    cell = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    return cell, config, traffic


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, e.g. ("metrics", "step_s")."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def metrics_for(cell_name, traced):
    """The metric entries of BENCHMARK.json this cell reports in this
    kind of run: end-to-end ones untraced, per-layer ones traced."""
    spec = benchmark_json()
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [
        m for m in entries
        if "workloads" not in m or cell_name in m["workloads"]
    ]

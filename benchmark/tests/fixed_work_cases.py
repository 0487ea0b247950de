"""The three keys that make a cell whose step follows its weights and its
tokens run the same computation every time (`benchmark/run.py`:
`timed_steps` and `traffic_seed` in the cell's file, `weights_seed` in the
configuration's), and that they touch no other cell: the arguments the
four dense cells build their trial with, pinned as the parent of PR 30
built them.

Not collected under its own name: `test_any_block.py` imports these cases,
and `tests/test_benchmark_harness.py` imports that module's into tier-1
(a benchmark PR may edit no file outside `benchmark/`)."""

import dataclasses
import hashlib
import json
import os
import re

import pytest

from benchmark import files
from benchmark import run as run_mod
from benchmark.tests.test_harness import rehearse
from benchmark.tokenizer import ByteTokenizer

__all__ = [  # what `import *` carries into tier-1: the cases, nothing else
    "test_a_dense_cell_builds_with_the_arguments_its_parent_built_with",
    "test_the_seed_draws_weights_and_rows_unless_the_files_fix_them",
    "test_the_window_closes_on_the_cells_count_or_on_the_clock",
]

DATA = os.path.join(os.path.dirname(__file__), "data")
SPEC = files.benchmark_json()
DENSE = [w["name"] for w in SPEC["workloads"]
         if "weights_seed" not in files.load_cell(w["name"])[1]["benchmark"]]
FIXED = [w["name"] for w in SPEC["workloads"] if w["name"] not in DENSE]
SEEDS = (3, 2_500_000_011)


def canon(x):
    """A value as JSON: of a dataclass the fields that differ from their
    defaults (a field the program adds later, with a default, changes
    nothing here), a callable by its result's digest."""
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        out = {}
        for f in dataclasses.fields(x):
            default = (f.default_factory() if f.default_factory
                       is not dataclasses.MISSING else f.default)
            if getattr(x, f.name) != default:
                out[f.name] = canon(getattr(x, f.name))
        return {type(x).__name__: out}
    if isinstance(x, dict):
        return {str(k): canon(v) for k, v in sorted(x.items())}
    if isinstance(x, (list, tuple)):
        return [canon(v) for v in x]
    if callable(x):
        return {"returns": digest(x())}
    return x if isinstance(x, (int, float, str, bool, type(None))) else repr(x)


def digest(x):
    return hashlib.sha256(
        json.dumps(canon(x), sort_keys=True).encode()).hexdigest()[:16]


def pinned_form(cfg):
    out = canon(cfg)
    info = out["PPOMathConfig"]["reward_interface_args"]
    info["id2info"] = digest(info["id2info"])  # the traffic's rows, by id
    return out


def build_args(cell_name, seed, monkeypatch):
    """(the PPOMathConfig `build_plan` hands the program, the traffic's
    rows) for a cell at its real size, nothing built."""
    from areal_tpu.experiments import common

    cell, config, traffic = files.load_cell(cell_name)
    run = run_mod.Run(
        cell_name=cell_name, cell=cell, config=config, traffic=traffic,
        model_cfg=run_mod.model_config(config), chips=cell["chips"],
        device_kind="none", peaks=None, seed=seed, traced=False,
    )
    rows = run_mod.traffic_rows(cell, traffic, seed)
    tok = ByteTokenizer(eos_token_id=run.model_cfg.vocab_size)
    monkeypatch.setattr(common, "build_ppo_math", lambda cfg, tok: cfg)
    return run_mod.build_plan(run, rows, tok, "fileroot"), rows


@pytest.mark.parametrize("cell", DENSE)
def test_a_dense_cell_builds_with_the_arguments_its_parent_built_with(
        cell, monkeypatch):
    """`data/dense_build_args.json` was written by this function from the
    parent's `benchmark/` (commit 0a4d806); `id2info` and the dataset's
    rows are the traffic's, digested."""
    with open(os.path.join(DATA, "dense_build_args.json")) as f:
        pinned = json.load(f)
    cfg, _ = build_args(cell, SEEDS[1], monkeypatch)
    assert pinned_form(cfg) == pinned[cell]
    assert "timed_steps" not in files.load_cell(cell)[0]


@pytest.mark.parametrize("cell", DENSE[:1] + FIXED)
def test_the_seed_draws_weights_and_rows_unless_the_files_fix_them(
        cell, monkeypatch):
    cell_file, config, _ = files.load_cell(cell)
    (a, rows_a), (b, rows_b) = (
        build_args(cell, s, monkeypatch) for s in SEEDS)
    if cell in FIXED:  # the same computation under every --seed
        assert a.seed == b.seed == config["benchmark"]["weights_seed"]
        assert rows_a == rows_b and rows_a[0]["query_id"].startswith(
            f"s{cell_file['traffic_seed']}-")
    else:
        assert (a.seed, b.seed) == SEEDS and "traffic_seed" not in cell_file
        assert [r["prompt"] for r in rows_a] != [r["prompt"] for r in rows_b]
        assert sorted(len(r["prompt"]) for r in rows_a) == sorted(
            len(r["prompt"]) for r in rows_b)  # the same work, other rows
    assert a.dataset.args["dataset_builder"]() == rows_a


def seeds_and_walls(cell, seconds):
    """A CPU rehearsal (`--seed 3`): the seeds and the count its first log
    line names, and the walls of its timed steps."""
    proc = rehearse(files.ROOT, cell, seconds=seconds)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (first,) = re.findall(
        r"trial_seed=(\d+) traffic_seed=(\d+) timed_steps=(\w+)", proc.stderr)
    walls = json.loads(
        re.findall(r"timed steps, walls (\[[^\]]*\])", proc.stderr)[-1])
    return first, walls


@pytest.mark.parametrize("cell", DENSE[:1] + FIXED)
def test_the_window_closes_on_the_cells_count_or_on_the_clock(cell):
    cell_file, config, _ = files.load_cell(cell)
    n = cell_file.get("timed_steps")
    if n:  # far from the clock: the count decides, and the draws are the files'
        (trial, traffic, said), walls = seeds_and_walls(cell, seconds=600)
        assert (int(trial), int(traffic), int(said)) == (
            config["benchmark"]["weights_seed"], cell_file["traffic_seed"], n)
        assert len(walls) == n and sum(walls) < 600
    else:  # as before: the first step to end past the clock is the last
        (trial, traffic, said), walls = seeds_and_walls(cell, seconds=1)
        assert (int(trial), int(traffic), said) == (3, 3, "None")
        assert len(walls) >= 2
        assert sum(walls) >= 0.999 and (
            len(walls) == 2 or sum(walls[:-1]) < 1.001)

"""What lets the harness take a block it has not met: the weight check
over whatever leaves a tree has, FLOPs and bytes from the config's
structure, `toy()` driven by the config file, and the readers of the
program's scopes: no subprocess, seconds.  Also carries the cases of
`fixed_work_cases.py` into tier-1 (the import at the end)."""

import os
import types

import numpy as np
import pytest

from benchmark import checks, files, peaks
from benchmark import run as run_mod
from benchmark.metrics import (
    _program, head_share, train_apply_s, train_bwd_s, train_fwd_s,
    train_recompute_s,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "scoped_v5e.xplane.pb")
TINY = os.path.join(DATA, "tiny_v5e.xplane.pb")


# ---------------------------------------------------------------- weights


def tree(seed=0, dtype="bfloat16", bias=False):
    """A MoE-shaped tree with no `bq`: layer-stacked leaves under
    `blocks`, expert leaves with four dimensions, an untied head."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.05, dtype)

    blocks = {
        "ln1": jnp.ones((2, 32), dtype), "wq": w(2, 32, 64),
        "router": w(2, 32, 8), "wg": w(2, 8, 32, 16), "wd": w(2, 8, 16, 32),
    }
    if bias:
        blocks["bq"] = jnp.zeros((2, 64), dtype)
    return {"embed": w(128, 32), "blocks": blocks,
            "final_ln": jnp.ones((32,), dtype), "lm_head": w(32, 128)}


def place(params, how):
    """The tree on the 8-device mesh, sharded over its first axis that
    divides by 8 (`how` = "first") or its last (`how` = "last")."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    assert len(jax.devices()) == 8
    mesh = Mesh(np.asarray(jax.devices()), ("x",))

    def put(x):
        axes = [a for a in range(x.ndim) if x.shape[a] % 8 == 0]
        spec = [None] * x.ndim
        if axes:
            spec[axes[0] if how == "first" else axes[-1]] = "x"
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))

    return jax.tree.map(put, params)


def edit(params, path, fn):
    """A copy of the tree with fn(numpy leaf) in one leaf's place."""
    import jax
    import jax.numpy as jnp

    def one(p, x):
        if jax.tree_util.keystr(p) != path:
            return x
        return jnp.asarray(fn(np.array(x)), x.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


def flip_one_bit(a):
    a.view(np.uint16)[3, 5] ^= 1 << 2
    return a


def swap_two_rows(a):
    a[[7, 90]] = a[[90, 7]]
    return a


EMBED, WG = "['embed']", "['blocks']['wg']"
MATMUL = {EMBED, "['lm_head']", "['blocks']['wq']", "['blocks']['router']",
          WG, "['blocks']['wd']"}


def test_equal_trees_under_two_shardings_have_equal_sums():
    a, b = place(tree(), "first"), place(tree(), "last")
    assert a["blocks"]["wg"].sharding != b["blocks"]["wg"].sharding
    sums = checks.tree_sums(a)
    assert sums == checks.tree_sums(b) and len(sums) == 8
    assert checks.matmul_leaves(a) == MATMUL  # no norm scale, no bias
    moved = checks.tree_sums(place(tree(seed=1), "last"))
    assert checks.handback_problems(sums, checks.tree_sums(b), moved,
                                    MATMUL) == []


@pytest.mark.parametrize("path,damage,in_plain_sum", [
    (EMBED, flip_one_bit, True),
    (EMBED, swap_two_rows, False),  # only the position-weighted sum sees it
])
def test_one_flipped_bit_and_two_swapped_rows_show(path, damage, in_plain_sum):
    good = checks.tree_sums(place(tree(), "first"))
    bad = checks.tree_sums(place(edit(tree(), path, damage), "last"))
    assert (good[path][0] != bad[path][0]) == in_plain_sum
    assert good[path][1] != bad[path][1]
    before = checks.tree_sums(tree(seed=1))
    problems = checks.handback_problems(good, bad, before, MATMUL)
    assert len(problems) == 1 and "1 of 8 leaves: ['embed']" in problems[0]


def test_a_tree_that_never_moved_and_a_leaf_on_one_side_are_named():
    sums = checks.tree_sums(tree())
    problems = checks.handback_problems(sums, sums, dict(sums), MATMUL)
    assert len(problems) == 1 and "did not move" in problems[0]
    assert all(p in problems[0] for p in MATMUL)
    # One matrix frozen (its sums as before), the rest moved.
    before = dict(checks.tree_sums(tree(seed=1)), **{WG: sums[WG]})
    (problem,) = checks.handback_problems(sums, sums, before, MATMUL)
    assert problem.endswith("weights in: " + WG)
    with_bias = checks.tree_sums(tree(bias=True))
    (problem,) = checks.handback_problems(
        with_bias, sums, checks.tree_sums(tree(seed=1)), MATMUL)
    assert "one side only: ['blocks']['bq'] (trainer)" in problem
    assert "no weight sums" in checks.handback_problems(
        sums, sums, None, MATMUL)[0]


def test_the_check_between_two_engines_casts_as_the_hand_back_does():
    """The trainer keeps bf16 masters, the CPU generator computes in fp32:
    compared after the hand-back's cast, on the device, scalars only."""
    import jax
    import jax.numpy as jnp

    class Engine:
        def __init__(self, params):
            self.params = params

        def get_params(self):
            return self.params

    train = Engine(place(tree(), "first"))
    gen = Engine(place(jax.tree.map(lambda x: x.astype(jnp.float32), tree()),
                       "last"))
    before = checks.tree_sums(place(tree(seed=1, dtype="float32"), "last"))
    with jax.transfer_guard_device_to_host("disallow"):
        report = checks.handback_check(train, gen, before)
    assert report["ok"] and report["leaves"] == 8 and not report["problems"]
    gen.params = edit(gen.params, WG, lambda a: a + (a == a.max()))
    report = checks.handback_check(train, gen, before)
    assert not report["ok"] and WG in report["problems"][0]


# ------------------------------------------------------------------ peaks

OLMOE = types.SimpleNamespace(  # allenai/OLMoE-1B-7B-0125-Instruct
    hidden_dim=2048, head_dim=128, n_q_heads=16, n_kv_heads=16,
    intermediate_dim=1024, n_layers=16, vocab_size=50304, n_experts=64,
    n_experts_per_tok=8, moe_intermediate_dim=1024,
)


@pytest.mark.parametrize("config,params", [
    ("qwen2.5-math-1.5b", 1_543_569_408),
    ("r1-distill-qwen-7b-l8", 2_409_365_504),
])
def test_dense_configs_count_what_they_counted(config, params):
    cfg = run_mod.model_config(files.load_json("configs", f"{config}.json"))
    h, f, d = cfg.hidden_dim, cfg.intermediate_dim, cfg.head_dim
    dense = cfg.n_layers * (
        h * (cfg.n_q_heads + 2 * cfg.n_kv_heads) * d + cfg.n_q_heads * d * h
        + 3 * h * f
    ) + h * cfg.vocab_size
    assert peaks.matmul_params(cfg) == dense == params
    assert peaks.weight_bytes(cfg) == peaks.weight_bytes(cfg, rows=64) == 2 * dense
    kv = peaks.kv_bytes_per_token(cfg)
    assert peaks.decode_step_bytes(cfg, [100.5, 200]) == 2 * dense + kv * 300.5


def test_olmoe_counts_active_experts_and_the_experts_a_step_touches():
    attn, expert, router = 4 * 2048 * 2048, 3 * 2048 * 1024, 2048 * 64
    assert peaks.attn_params(OLMOE) == attn == 16_777_216
    assert peaks.mlp_params(OLMOE) == 8 * expert + router == 50_462_720
    assert attn + peaks.mlp_params(OLMOE) == 67_239_936  # 67.2 M a layer
    assert peaks.matmul_params(OLMOE) == 16 * 67_239_936 + 2048 * 50304
    assert peaks.experts_expected(OLMOE, 8) == pytest.approx(42.0, abs=0.01)
    assert peaks.experts_expected(OLMOE, 64) == pytest.approx(64.0, abs=0.02)
    assert peaks.experts_expected(OLMOE, 1) == pytest.approx(8.0)
    head = 2048 * 50304
    assert peaks.weight_bytes(OLMOE, rows=1) == pytest.approx(
        2 * peaks.matmul_params(OLMOE))  # one row: its own k experts
    assert peaks.weight_bytes(OLMOE, rows=8, experts_touched=64) == 2 * (
        16 * (attn + router + 64 * expert) + head)
    assert peaks.weight_bytes(OLMOE, rows=8) == pytest.approx(2 * (
        16 * (attn + router + 42.009 * expert) + head), rel=1e-6)
    # The layer's own arithmetic: three ragged matmuls over T k rows are
    # the FLOPs; at 8 rows the expert weights are the bytes.
    t = 8
    parts = peaks.moe_layer_parts(OLMOE, t)
    assert set(parts) == {"router", "gather", "gate_up", "down", "scatter"}
    assert parts["gate_up"][0] + parts["down"][0] == 2 * t * 8 * expert
    assert peaks.moe_layer_flops(OLMOE, t) == 2 * t * (
        8 * expert + router + 8 * 2048)
    weights = 2 * (peaks.experts_expected(OLMOE, t) * expert + router)
    assert weights < peaks.moe_layer_bytes(OLMOE, t) < 1.01 * weights
    assert peaks.moe_layer_bytes(OLMOE, t, experts_touched=8) < weights / 4


# -------------------------------------------------------------------- toy

TRAFFIC = {"prompt_len": {"lo": 96, "hi": 160}, "max_new_tokens": 1024,
           "dataset_max_length": 256}


def test_toy_shrinks_the_dense_keys_then_what_the_config_says():
    config = files.load_json("configs", "qwen2.5-math-1.5b.json")
    assert "toy" not in config["benchmark"]
    small, traffic = run_mod.toy(config, TRAFFIC)
    assert (small["hidden_size"], small["intermediate_size"],
            small["num_hidden_layers"], small["vocab_size"]) == (64, 128, 2, 512)
    assert small["benchmark"]["param_dtype"] == "float32"
    assert traffic["max_new_tokens"] == 16 and traffic["prompt_len"]["lo"] == 24
    moe = dict(config, num_local_experts=64, head_dim=128, benchmark=dict(
        config["benchmark"],
        toy={"num_local_experts": 4, "head_dim": 16, "intermediate_size": 32},
    ))
    small, _ = run_mod.toy(moe, TRAFFIC)
    assert (small["num_local_experts"], small["head_dim"],
            small["intermediate_size"], small["hidden_size"]) == (4, 16, 32, 64)
    assert moe["num_local_experts"] == 64  # the file's dict is not edited


# ---------------------------------------------------------------- readers

READERS = (train_fwd_s, train_recompute_s, train_bwd_s, train_apply_s,
           head_share)


def test_scope_readers_on_a_recorded_trace():
    trace = run_mod.reduce_trace(SCOPED, 1)
    run = types.SimpleNamespace(trace=trace)
    fwd, rec, bwd, apply, head = (r.read(run) for r in READERS)
    assert rec == pytest.approx(fwd, rel=0.25) and bwd > fwd > 0
    assert 0 < apply < fwd and 0 < head < 100
    # Seconds PER STEP of the two traced steps, and nothing counted twice.
    assert trace["traced_steps"] == 2
    assert 2 * (fwd + rec + bwd + apply) <= trace["busy_s"]
    assert 2 * (fwd + rec + bwd + apply) > 0.9 * trace["busy_by_bench_span"][
        "actor:train_step"]
    assert _program.scope_share(run, "train/grad", "layer/mlp") == pytest.approx(
        100 * 2 * _program.scope_seconds(run, "layer/mlp") / trace["busy_s"])
    assert _program.scope_seconds(run, "layer/no_such_scope") is None
    # The breakdown keeps the operation's name and grows a suffix.
    top = trace["breakdown"]["device_ops"][0][0]
    assert top.partition(" @")[0] in trace["op_seconds"] and " @train/" in top
    assert any("/" in label for label, _ in trace["breakdown"]["idle_gaps"])


def test_scope_readers_say_nothing_without_scopes_or_without_a_trace():
    bare = types.SimpleNamespace(trace=run_mod.reduce_trace(TINY, 1))
    assert bare.trace["scope_seconds"] == {} and bare.trace["busy_s"] > 0
    for run in (bare, types.SimpleNamespace(trace=None)):
        for reader in READERS:
            assert reader.read(run) is None, reader.__name__


# ------------------------------------------------------- the fixed work
# The cases of `fixed_work_cases.py` (a cell's `timed_steps` and
# `traffic_seed`, a configuration's `weights_seed`, the dense cells' build
# arguments) reach tier-1 through this module, which
# `tests/test_benchmark_harness.py` imports whole; two of them are CPU
# rehearsals, 20 s each.

from benchmark.tests.fixed_work_cases import *  # noqa: E402,F401,F403

"""The cases of `tests/test_grouped_matmul_programs.py` that lower or compile
for a described v5e with no chip attached (Mosaic and XLA:TPU for real,
seconds to tens of seconds a program), in a file of their own since PR 62: `--dist loadfile`
hands a file to one worker, and that file with these was 232 s of a run
that six workers otherwise end in 750.  What they share with it they
import from it."""

import importlib.util
import os
import re

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from benchmark import program_trace
from tests.test_grouped_matmul_programs import TOUCHED, _big


# ------------------------------------- an expert layer compiled for a v5e


# ------------------------------------------------------ the set-up pins
# (traced and lowered for the described device: no compile)


@pytest.fixture(scope="module")
def lowering_check():
    """`scripts/lowering_check.py`: a configuration's gradient program
    traced and lowered for a described device."""
    spec = importlib.util.spec_from_file_location(
        "lowering_check", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "lowering_check.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", TOUCHED)
def test_a_gradient_program_lowers_each_kernel_once_and_shares_the_block(
        name, v5e_chips, lowering_check, monkeypatch):
    """What a warm set-up pays on every start is tracing and lowering (the
    compile cache's key is made from the lowered module), so the kernels
    are held to COUNTS of the text lowered for a TPU, not to a clock: a
    unit's unrolled expert layers call ONE function of the module per
    pass over them (`_kernel_rows` under `jit`), which was traced once
    (its visit tables made twice: the rows' and the groups'); at most 8
    distinct `grouped_matmul*` kernel bodies (six by design: forward, dx,
    dw at the up and at the down shape), each lowered to a Mosaic module
    once; and a text at most 1.3 times `ragged_dot`'s.  PR 49, traced and
    lowered at every one of 96 sites: 2.0 to 3.2 times the text, + 93% of
    a warm set-up (the driver's runs)."""
    from areal_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tables = []
    inner = gm._visits
    monkeypatch.setattr(
        gm, "_visits", lambda *a: (tables.append(a[1:]), inner(*a))[1])
    cfg = _big(name)
    # a row length of its own (the tables are a jitted function of the
    # sizes' shape and the slab's rows alone)
    length = 1920 - 128 * TOUCHED.index(name)
    _, text = lowering_check.lowered(cfg, 1, length, v5e_chips[0], None)
    _, plain = lowering_check.lowered(cfg, 1, length, v5e_chips[0], False)
    # (none where another test of this process made a slab's of these rows)
    assert len(tables) in (0, 2), tables
    assert "ragged_dot" in plain and "grouped_matmul" not in plain
    assert len(text) <= 1.3 * len(plain), (len(text), len(plain))
    bodies = {k: v for k, v in lowering_check.kernel_bodies(text).items()
              if k.startswith("grouped_matmul")}
    assert set(bodies) == {
        "grouped_matmul", "grouped_matmul_dx", "grouped_matmul_dw"}
    assert sum(bodies.values()) <= 8, bodies
    # every function that holds the block is called from each of the
    # unit's expert layers, and the kernels' call sites are a few a body
    # (one a pass: JAX clones a lowered kernel, it does not lower it
    # again), where each matmul of each layer had its own
    calls = re.findall(r"call @(_kernel_rows[\w.]*)\(", text)
    layers = cfg.plan.in_unit("moe")
    assert calls and all(
        calls.count(f) % layers == 0 for f in set(calls)), calls
    sites = sum(
        line.count("tpu_custom_call") for line in text.splitlines()
        if "grouped_matmul" in line)
    assert sites <= 3 * sum(bodies.values()), (sites, bodies)


@pytest.mark.parametrize("name", TOUCHED)
def test_an_expert_layer_compiles_for_v5e_with_ragged_dot_in_the_loop_alone(
        name, v5e_chips, monkeypatch):
    """Mosaic and XLA:TPU for real: one expert layer of the cell (the
    published widths, 1,024 tokens — an eighth of the cells' micro-batch,
    two slabs still: the compile's seconds follow the tokens, 36 against
    9, while the kernels' blocks, the counts below and the two ratios
    (1.035 / 0.998 / 0.941 and 1.012 / 1.026 / 0.929 here, 1.039 / 1.000 /
    0.925 and 0.991 / 1.031 / 0.970 at 8,192: PR 62) do not) forward and
    backward under the remat policy the trainer runs.  With the kernel
    the first slab's forward, remat's forward, dx and dw of each of the
    experts' two or three matrices are calls of `grouped_matmul.py`'s
    kernels under the caller's scope, `ragged-dot` is left in the
    later-slab loop alone, and the program's temporaries and code are held
    to those with `ragged_dot` — the parent's program."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _big(name)
    one = SingleDeviceSharding(v5e_chips[0])
    tokens = 1024
    assert tfm.expert_slab_rows(
        cfg, tokens * cfg.n_experts_per_tok) < tokens * cfg.n_experts_per_tok

    def placed(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    e, d, f = cfg.n_experts, cfg.hidden_dim, cfg.moe_intermediate_dim
    blk = {"router": placed((d, cfg.router_width)),
           "wu": placed((e, d, f)), "wd": placed((e, f, d))}
    if cfg.mlp_gated:
        blk["wg"] = placed((e, d, f))
    if cfg.moe_score_func == "sigmoid":
        blk["router_bias"] = placed((cfg.router_width,), jnp.float32)
    plain = {k: v for k, v in cfg.__dict__.items() if k != "shared_expert_dim"}
    cfg = ModelConfig(**plain) if cfg.shared_expert_dim else cfg

    def compiled(kernel):
        @jax.checkpoint
        def layer(h, blk):
            with jax.named_scope("train/grad"):
                return tfm._mlp_moe(h, blk, cfg, kernel=kernel)[0]

        def loss(h, blk):
            return jnp.sum(layer(h, blk).astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            placed((1, tokens, d)), blk).compile()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with_kernel, with_ragged = compiled(True), compiled(False)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = with_kernel.as_text()
    # what is left of `ragged-dot` is the later-slab loop's (its custom
    # calls carry no scope to say so: fewer of them than the parent's)
    assert 0 < text.count("%ragged-dot") < with_ragged.as_text().count(
        "%ragged-dot")
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%grouped_matmul" in line.split(" = ")[0]]
    matrices = len(tfm._expert_leaves(cfg))
    kinds = [re.match(r"\s*%(grouped_matmul(?:_d[xw])?)[.\d]* =", c).group(1)
             for c in calls]
    # dx and dw of every matrix once (the first slab's); the forward as
    # often as a gradient reads an activation (the remat's).
    assert kinds.count("grouped_matmul_dx") == matrices
    assert kinds.count("grouped_matmul_dw") == matrices
    assert kinds.count("grouped_matmul") >= matrices - 1
    # ... each under the caller's scope and phase, as the benchmark's trace
    # reader takes them from a compiled operation's `op_name`
    read = [program_trace.scope_of(
        re.search(r'op_name="([^"]+)"', c).group(1)) for c in calls]
    for (scope, _), kind in zip(read, kinds):
        assert re.fullmatch(
            r"train/grad/layer/mlp/experts/w\d+x\d+x\d+/" + kind,
            scope), scope
    assert {phase for _, phase in read} == {"recompute", "bwd"}
    # Temporaries: within 6% of `ragged_dot`'s (the scheduler's buffer
    # assignment lands 5% over or 4% under at nemotron's widths by what
    # the kernels ask Mosaic for, 4% over at mellum's with `ragged-dot`'s
    # own workspace still in the loop; in the cells `peak_hbm_gb` reads
    # at or under the parent's: PERF.md section 6, PR 50).
    assert (with_kernel.memory_analysis().temp_size_in_bytes
            <= 1.06 * with_ragged.memory_analysis().temp_size_in_bytes)
    # ... and the CODE no larger than 1.1 x `ragged_dot`'s: a whole-matrix
    # product unrolled in Mosaic cost 0.5 MiB a kernel, which eight loaded
    # gradient programs turned into + 5.5% of `peak_hbm_gb` (PR 49).
    assert (with_kernel.memory_analysis().generated_code_size_in_bytes
            <= 1.1 * with_ragged.memory_analysis().generated_code_size_in_bytes)

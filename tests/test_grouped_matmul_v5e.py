"""The cases of `tests/test_grouped_matmul.py` that compile for a described
v5e with no chip attached (Mosaic and XLA:TPU for real, seconds to tens of
seconds a program), in a file of their own since PR 62: `--dist loadfile`
hands a file to one worker, and that file with these was 219 s of a run
that six workers otherwise end in 750.  What they share with it they
import from it."""

import re

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from areal_tpu.ops.pallas import grouped_matmul as gm
from benchmark import files, program_trace
from benchmark import run as bench_run
from tests.test_grouped_matmul import SHAPES


# ----------------------------------------- the decode loop compiled for v5e


def _kernel_scopes(text):
    """The scope `benchmark/program_trace.py` gives each call of the
    kernel in a compiled program's text."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%grouped_decode_matmul" in line.split(" = ")[0]]
    return [program_trace.scope_of(
        re.search(r'op_name="([^"]+)"', c).group(1))[0] for c in calls]


def test_the_scope_around_the_call_states_the_tile(v5e_chips, monkeypatch):
    """`.../experts/w384x896/grouped_decode_matmul`: an outer scope names
    the step's tile, the kernel's own name (what `flash_time_share` and
    the ledger's breakdown key on) stays — read as the benchmark's trace
    reader reads a compiled operation's `op_name`."""
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r, t, e, k, n = SHAPES["mellum_up"]
    one = SingleDeviceSharding(v5e_chips[0])

    def experts(xs, w, sizes, layer):
        with jax.named_scope("gen/decode_step/layer/mlp/experts"):
            return gm.grouped_decode_matmul(xs, w, sizes, layer, max_rows=t)

    text = jax.jit(experts).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        for shape, dtype in (((r, k), jnp.bfloat16), ((e, k, n), jnp.bfloat16),
                             ((e,), jnp.int32), ((), jnp.int32))
    )).compile().as_text()
    assert "%grouped_decode_matmul" in text
    assert _kernel_scopes(text) == [
        "gen/decode_step/layer/mlp/experts/w384x896/grouped_decode_matmul"]


def test_mellums_decode_loop_compiles_for_v5e_with_the_leaves_in_place(
        v5e_chips, monkeypatch):
    """Mosaic and XLA:TPU for real, at the cell's size (32 rows, 4,608
    slots, four layers, the published widths): the twelve calls an
    iteration compile at [384, 896] and [896, 2,304] tiles, under scopes
    that say so, and read the stacked [4, 16, 2304, 896] / [4, 16, 896,
    2304] leaves where they lie — no copy, transpose or re-layout of a
    leaf or of a layer's experts anywhere in the program (both minor
    dimensions are whole lanes: there is nothing to re-lay)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(
        files.load_json("configs", "mellum2-12b-a2.5b-l4-e16.json"))
    b, sp, st = 32, 4096, 4608
    one = SingleDeviceSharding(v5e_chips[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(placed, jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0))))
    rows = placed(jax.ShapeDtypeStruct((b,), jnp.int32))

    def loop(params, tok, plen):
        cache = tfm.init_kv_cache(big, b, st, dtype=jnp.bfloat16)

        def body(state):
            step, tok, cache = state
            logits, cache = tfm.decode_step(
                params, big, tok, plen + step, cache, sp + step, sp - plen,
                experts_in_place=True)
            return step + 1, jnp.argmax(logits, -1).astype(jnp.int32), cache

        return jax.lax.while_loop(
            lambda s: s[0] < 512, body, (0, tok, cache))[1]

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(loop).lower(params, rows, rows).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "%ragged-dot" not in text  # the TPU's own pick at these widths
    scopes = _kernel_scopes(text)
    assert len(scopes) == 3 * len(big.plan.unit)  # wg, wu, wd a layer
    assert all(s.startswith("gen/decode_step/layer/mlp/experts/w")
               for s in scopes)
    assert sorted(s.split("/")[-2] for s in scopes) == sorted(
        ["w384x896", "w384x896", "w896x2304"] * len(big.plan.unit))
    leaves = ("2304,896]", "896,2304]")
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if any(s in line.split(" = ")[-1].split("(")[0] for s in leaves)
        and any(f" {op}(" in line for op in ("copy", "transpose", "bitcast-convert"))
    ]
    assert not copies, copies[:3]

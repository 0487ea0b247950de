"""The cases of `tests/test_nemotron_h.py` that compile for a described
v5e with no chip attached (Mosaic and XLA:TPU for real, seconds to tens of
seconds a program), in a file of their own since PR 62: `--dist loadfile`
hands a file to one worker, and that file with these was 250 s of a run
that six workers otherwise end in 750.  What they share with it they
import from it."""

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from benchmark import files
from benchmark import run as bench_run
from tests.test_nemotron_h import CONFIG


# ------------------------------------------- the decode loop compiled for v5e


@pytest.mark.parametrize("expert_kernel", [False, True],
                         ids=["ragged_dot", "grouped_decode_matmul"])
def test_the_decode_loop_compiles_for_v5e_without_a_copy_of_the_state(
        v5e_chips, monkeypatch, expert_kernel):
    """XLA:TPU for real, at the cell's size (64 rows, a 768-slot window,
    nine layers, the published widths): the loop reads and writes the
    stacked fp32 state AS IT LIES — one fusion a Mamba layer that updates
    the layer's slice through the loop's `dynamic-update-slice`, no copy or
    re-layout of the state or of a layer's part of it (what would make a
    Pallas step kernel this family's to write: ISSUE 40).  With the Pallas
    grouped matmul in the ragged kernels' place (what a TPU backend takes
    at these widths) Mosaic compiles it at [2,688, 1,856] and [1,856,
    2,688].  Either way the stacked expert leaves are not copied inside
    the loop: XLA lays the `wu` parameter out with 2,688 minor (1,856 is
    14.5 lanes) and re-lays it ONCE in front of the loop."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    b, sp, st = 64, 256, 768
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
                experts_in_place=True, expert_kernel=expert_kernel)
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
    h, p, n = big.ssm_n_heads, big.ssm_head_dim, big.ssm_state_dim
    shapes = (f"f32[{big.n_ssm_layers},{b},{h},{p},{n}]", f"f32[{b},{h},{p},{n}]")
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if any(s in line.split(" = ")[-1].split("(")[0] for s in shapes)
        and (" copy(" in line or " transpose(" in line)
    ]
    assert not copies, copies[:3]
    updates = [line for line in text.splitlines()
               if shapes[0] in line and "dynamic-update-slice(" in line]
    assert len(updates) == big.n_ssm_layers
    assert "layer/ssm/ssm_step" in text
    assert ("%grouped_decode_matmul" in text) == expert_kernel
    assert ("%ragged-dot" in text) == (not expert_kernel)
    leaves = ("2688,1856]", "1856,2688]")
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if any(s in line.split(" = ")[-1].split("(")[0] for s in leaves)
        and (" copy(" in line or " transpose(" in line)
    ]
    assert len(copies) <= 1, copies[:3]  # the one in front of the loop

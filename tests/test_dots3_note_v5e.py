"""The case of `tests/test_dots3_note.py` that compiles for a described
v5e with no chip attached (Mosaic and XLA:TPU for real, about a minute),
in a file of its own as the other families' are since PR 62: `--dist
loadfile` hands a file to one worker."""

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from benchmark import files
from benchmark import run as bench_run

CONFIG = "dots3-note-prev-l5-e8-h8.json"
ROW = 13_312  # the cell's longest row: one sequence
# `memory_analysis().temp_size_in_bytes` as this compile read it (PR 64:
# 4,437,567,488, with the selection a [13312, 13312] mask held whole and
# with it made and used a block at a time alike: the program's peak is not
# in the attention).  Beside 11.13 GB of train state the chip's 15.75 GB
# leave 4.6.
_GRAD_TEMP_BYTES = 4_437_567_488


@pytest.fixture(scope="module")
def v5e_chip():
    """A device of a described v5e host to compile for (libtpu is
    installed here; no chip is attached).  Built inside the fixture, never
    at import: only the worker that runs this file may load the TPU's
    library."""
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def test_the_gradient_program_compiles_for_v5e_beside_its_state(
        v5e_chip, monkeypatch):
    """The one compile that sizes the cell: the gradient of the stack over
    one packed row of 13,312 tokens at every published width, `remat="full"`
    as the train engine has it.  Its temporaries are pinned (a hundredth of
    room): `peak_hbm_gb` reads 15.4 of 15.75 on the chip, so what grows
    them has to show here, before a chip call.  No value of the program
    holds the row's length on two axes as a choice (`pred`) or a score
    (`f32`): the selection is made and used a block of queries at a time,
    and kept for the backward pass as words of 32 queries."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    chip = SingleDeviceSharding(v5e_chip)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, sharding=chip),
        shapes)
    row = jax.ShapeDtypeStruct((1, ROW), jnp.int32, sharding=chip)

    def loss(p, tokens, seg):
        x, aux = tfm.hidden_states(p, big, tokens, seg, remat="full")
        return jnp.sum(x.astype(jnp.float32)) + aux

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.grad(loss)).trace(
            params, row, row).lower().compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= _GRAD_TEMP_BYTES * 1.01, temp
    text = compiled.as_text()
    assert f"u32[1,4,{ROW}]" in text  # a block's selection, packed
    for square in (f"pred[1,{ROW},{ROW}]", f"f32[1,{ROW},{ROW}]",
                   f"pred[{ROW // 128},1,128,{ROW}]"):  # whole, or stacked
        assert square not in text, square

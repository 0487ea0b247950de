"""The cases of `tests/test_ssd_chunk_kernel.py` that compile for a described
v5e with no chip attached (Mosaic and XLA:TPU for real, seconds to tens of
seconds a program), in a file of their own since PR 62: `--dist loadfile`
hands a file to one worker, and that file with these was 202 s of a run
that six workers otherwise end in 750.  What they share with it they
import from it."""

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import mamba
from areal_tpu.models import transformer as tfm


# ------------------------------------------- compiled for a described v5e


@pytest.mark.parametrize("config", [
    "nemotron-3-nano-30b-a3b-l9-e16.json", "granite-4.0-h-micro-l10.json"],
    ids=["nemo3n_eight_groups", "granite_one_group"])
def test_a_mixers_gradient_compiles_for_v5e_with_the_scan_on_its_kernels(
        v5e_chips, monkeypatch, config):
    """Mosaic and XLA:TPU for real, one Mamba layer at a cell's published
    widths over the cell's micro-batch (one packed row of 8,192 tokens),
    differentiated: the recurrence is `ssd_chunk_fwd` and `ssd_chunk_bwd`
    under `layer/ssm/ssd_scan`, with no `while` left under that scope (the
    `jnp` form's scan over chunks) and nothing there shaped like a chunk's
    [128, 128] blocks or like x turned to [.., 64 heads, 64] — such a turn
    is a copy of 134 MB.  Prefill's mixer (`with_state`) compiles with no
    kernel of the scan."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from benchmark import files
    from benchmark import run as bench_run

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", config))
    assert mamba.ssd_kernel_form(big)
    chip = SingleDeviceSharding(v5e_chips[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    blk = jax.tree.map(placed, jax.eval_shape(lambda: {
        k: v[0].astype(jnp.bfloat16) for k, v in tfm.init_params(
            big, jax.random.PRNGKey(0))["blocks"].items()
        if k in mamba.SSM_LEAVES}))
    h = placed(jax.ShapeDtypeStruct((1, 8192, big.hidden_dim), jnp.bfloat16))
    seg = placed(jax.ShapeDtypeStruct((1, 8192), jnp.int32))

    def loss(blk, h, seg):
        return jnp.sum(mamba.ssm_forward(h, blk, big, seg).astype(jnp.float32))

    def prefill(blk, h, seg):
        return mamba.ssm_forward(h, blk, big, seg, with_state=True)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.grad(loss, (0, 1))).trace(
            blk, h, seg).lower().compile().as_text()
        prefill_text = jax.jit(prefill).trace(
            blk, h, seg).lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # `jvp(layer/ssm)/ssd_scan`: autodiff's wrappers cut a scope's path.
    under = [
        line for line in text.replace("jvp(", "").replace(
            "transpose(", "").replace(")", "").splitlines()
        if "layer/ssm/ssd_scan" in line]
    assert len(under) > 20
    kernels = sorted(
        line.split('op_name="')[1].split('"')[0].split("/")[-2]
        for line in under if "tpu_custom_call" in line)
    assert kernels == ["ssd_chunk_bwd", "ssd_chunk_fwd"], kernels
    assert not [line[:120] for line in under if " while(" in line]
    for shape in (",128,128]", "8192,64,64]", ",64,128,64,128]"):
        assert not [line[:160] for line in under if shape in line], shape
    # x is read where the conv left it, and dx | dB | dC leave as one
    # array: no slice, pad or concatenation of a row's width of fp32
    wide = f"f32[1,8192,{big.ssm_inner_dim}]"
    moved = [line[:160] for line in under if wide in line.split(" = ")[-1][:40]
             and any(f" {op}(" in line for op in ("slice", "pad", "concatenate"))]
    assert not moved, moved
    assert "tpu_custom_call" not in prefill_text

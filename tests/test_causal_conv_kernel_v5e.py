"""A mixer's gradient with its conv on the Pallas operator `causal_conv_act`,
compiled for a described v5e with no chip attached (Mosaic and XLA:TPU for
real) at the widths of the four cells whose mixers run it — in a file of
its own, as `tests/test_ssd_chunk_kernel_v5e.py`: `--dist loadfile` hands a
file to one worker."""

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import linear_attention as la
from areal_tpu.models import mamba
from areal_tpu.models import transformer as tfm


CELLS = {  # cell -> (configuration, the kind's module, leaves, forward, scope)
    "olmoh": ("olmo-hybrid-7b-l4-v8.json", la, "LINEAR_LEAVES",
              "linear_attn_forward", "layer/linear_attn/conv"),
    "q3next": ("qwen3-next-80b-a3b-l4-e64.json", la, "LINEAR_LEAVES",
               "linear_attn_forward", "layer/linear_attn/conv"),
    "nemo3n": ("nemotron-3-nano-30b-a3b-l9-e16.json", mamba, "SSM_LEAVES",
               "ssm_forward", "layer/ssm/conv"),
    "granite4hm": ("granite-4.0-h-micro-l10.json", mamba, "SSM_LEAVES",
                   "ssm_forward", "layer/ssm/conv"),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_mixers_gradient_compiles_for_v5e_with_the_conv_on_its_kernels(
        v5e_chips, monkeypatch, cell):
    """One mixer layer at a cell's published widths over the cell's
    micro-batch (one packed row of 8,192 tokens), differentiated: the conv
    is `causal_conv_fwd` and `causal_conv_bwd` under the mixer's `conv`
    scope and nothing else of a row's width is left there (the `jnp`
    form's pads, selects and reductions); with the conv held on the `jnp`
    form (the recurrence on its sweep either way) the same program needs
    more temporaries, not fewer; prefill's mixer (`with_state`) compiles
    with no kernel of the conv."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from benchmark import files
    from benchmark import run as bench_run

    config, module, leaves, forward, scope = CELLS[cell]
    forward = getattr(module, forward)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", config))
    chip = SingleDeviceSharding(v5e_chips[0])

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    blk = jax.tree.map(placed, jax.eval_shape(lambda: {
        k: v[0].astype(jnp.bfloat16) for k, v in tfm.init_params(
            big, jax.random.PRNGKey(0))["blocks"].items()
        if k in getattr(module, leaves)}))
    h = placed(jax.ShapeDtypeStruct((1, 8192, big.hidden_dim), jnp.bfloat16))
    seg = placed(jax.ShapeDtypeStruct((1, 8192), jnp.int32))

    def loss(blk, h, seg):
        return jnp.sum(forward(h, blk, big, seg).astype(jnp.float32))

    def prefill(blk, h, seg):
        return forward(h, blk, big, seg, with_state=True)

    def compiled(fn):
        return jax.jit(fn).trace(blk, h, seg).lower().compile()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        on = compiled(jax.grad(loss, (0, 1)))
        prefill_text = compiled(prefill).as_text()
        with monkeypatch.context() as m:
            m.setattr(la, "conv_kernel_form", lambda *a, **kw: False)
            m.setattr(mamba, "conv_kernel_form", lambda *a, **kw: False)
            off = compiled(jax.grad(loss, (0, 1)))
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()

    def under(text):  # `jvp(layer/ssm)/conv`: autodiff cuts a scope's path
        return [
            line for line in text.replace("jvp(", "").replace(
                "transpose(", "").replace(")", "").splitlines()
            if scope + "/" in line or scope + '"' in line]

    kernels = sorted(
        line.split('op_name="')[1].split('"')[0].split("/")[-2]
        for line in under(on.as_text()) if "tpu_custom_call" in line)
    assert kernels == ["causal_conv_bwd", "causal_conv_fwd"], kernels
    for name in ("causal_conv_fwd", "causal_conv_bwd"):
        assert name not in off.as_text() and name not in prefill_text
    # no fp32 array of the row's width is made under the conv's scope but
    # the kernels' own result
    channels = big.linear_conv_dim if module is la else big.ssm_conv_dim
    wide = f"f32[1,8192,{channels}]"
    made = [line[:160] for line in under(on.as_text())
            if wide in line.split(" = ")[-1][:40]
            and "tpu_custom_call" not in line]
    assert not made, made
    assert len([line for line in under(off.as_text()) if wide in line]) > 3
    temps = {k: c.memory_analysis().temp_size_in_bytes
             for k, c in (("kernel", on), ("jnp", off))}
    print(f"{cell}: gradient of one mixer layer, temporaries "
          f"{temps['kernel'] / 1e6:.1f} MB with the conv on its kernels, "
          f"{temps['jnp'] / 1e6:.1f} MB on the jnp form")
    assert temps["kernel"] <= temps["jnp"], temps

"""The cases of `tests/test_qwen3_next.py` that compile for a described
v5e with no chip attached (Mosaic and XLA:TPU for real, seconds to tens of
seconds a program), in a file of their own since PR 62: `--dist loadfile`
hands a file to one worker, and that file with these was 288 s of a run
that six workers otherwise end in 750.  What they share with it they
import from it."""

import dataclasses

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import linear_attention as la
from areal_tpu.models import transformer as tfm
from areal_tpu.parallel import sharding
from benchmark import files
from benchmark import run as bench_run


# ------------------------------------------- the decode loop compiled for v5e


_Q3NEXT = ("qwen3-next-80b-a3b-l4-e64.json", (3, 32, 128, 128))
# Heads that are no whole 128-lane tiles (PR 60): the same kernel, a block a
# head's own [96, 192], the stack in the shape it always had.
_OLMOH = ("olmo-hybrid-7b-l4-v8.json", (3, 30, 96, 192))


@pytest.mark.parametrize("config,heads,mode", [
    pytest.param(*_Q3NEXT, "d1", id="d1"),
    pytest.param(*_Q3NEXT, "d2", id="d2"),
    pytest.param(*_Q3NEXT, "f2", id="f2"),
    pytest.param(*_OLMOH, "d1", id="96x192-d1"),
    pytest.param(*_OLMOH, "d2", id="96x192-d2"),
])
def test_the_decode_loop_compiles_for_v5e_with_the_state_stepped_in_place(
        v5e_chips, monkeypatch, config, heads, mode):
    """Mosaic and XLA:TPU for real, at the cells' size (64 rows, a
    768-slot window, one period, the published widths of `q3next-` and
    `olmoh-rollout64-512`), on one chip and with the rows spread over two
    (`shard_map`, data or fsdp): each of the three Gated DeltaNet layers
    steps its tiles of the stacked fp32 state through the Pallas kernel
    `gdn_delta_step` under `layer/linear_attn/delta_step` — the stack is
    the kernel's operand AND its result, so the loop holds no
    `dynamic-update-slice` on the state and no copy, re-layout or gather of
    the stack, of a device's part of it or of a layer's part (an alias that
    did not take would show as a copy of 402 MB an iteration, 566 at 96 x
    192, whose 192 columns lie in HBM as 256 lanes)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import BATCH_AXES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(files.load_json("configs", config))
    b, sp, st = 64, 256, 768
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, v5e_chips[: pc.world_size])

    def placed(x, spec):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding.named(mesh, spec))

    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    params = jax.tree.map(placed, shapes, sharding.param_pspecs(shapes))
    rows = placed(jax.ShapeDtypeStruct((b,), jnp.int32), P(BATCH_AXES))
    # What the engine hands the decode step (`_row_kernel`), and whether
    # the expert leaves can be read in place (not where fsdp splits them,
    # and not in a model that has none).
    row_kernel = None if pc.world_size == 1 else mesh
    in_place = tfm.expert_leaves_in_place(big, params["blocks"])
    assert in_place == (big.is_moe and mode != "f2")

    def loop(params, tok, plen):
        cache = tfm.init_kv_cache(big, b, st, dtype=jnp.bfloat16)

        def body(state):
            step, tok, cache = state
            logits, cache = tfm.decode_step(
                params, big, tok, plen + step, cache, sp + step, sp - plen,
                experts_in_place=in_place, row_kernel=row_kernel)
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
    n, hv = big.n_linear_layers, big.linear_n_v_heads
    dk, dv = big.linear_k_head_dim, big.linear_v_head_dim
    assert (n, hv, dk, dv) == heads
    here = b // pc.world_size  # a device's rows
    stack = f"f32[{n},{here},{hv},{dk},{dv}]"
    shapes = {stack} | {
        f"f32[{r},{hv},{dk},{dv}]" for r in (b, here)} | {
        f"f32[{n},{b},{hv},{dk},{dv}]"}
    calls = [line for line in text.splitlines()
             if "%gdn_delta_step" in line.split(" = ")[0]]
    assert len(calls) == n, len(calls)
    for line in calls:
        assert "tpu_custom_call" in line and stack in line.split(" = ")[1]
        scope = line.split('op_name="')[1].split('"')[0]
        assert "gen/decode_step" in scope
        assert "layer/linear_attn/delta_step/" in scope
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if any(s in line.split(" = ")[-1].split("(")[0] for s in shapes)
        and (" copy(" in line or " transpose(" in line
             or " all-gather(" in line)
    ]
    assert not copies, copies[:3]
    updates = [line.strip()[:160] for line in text.splitlines()
               if any(s in line for s in shapes)
               and "dynamic-update-slice(" in line]
    assert not updates, updates[:3]


# `memory_analysis().temp_size_in_bytes` of the same program on the `jnp`
# form (`chunk_kernel_form` False, what the commit before the kernel
# compiled), as a compile of it read (PR 62; 8,367,263,744 with the 64
# experts in, as PR 52 read it and PR 62 read it again).
_GRAD_TEMP_BYTES_ON_THE_JNP_FORM = 7_096_478_208


def _grad_on_the_sweep(big, v5e_chips):
    """Compile `big`'s gradient program at the cells' micro-batch for a
    described v5e and hold it to the rule on its Pallas sweep in every
    Gated DeltaNet layer -> (the chip's sharding, the params' shapes on it,
    the program's temporary bytes)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(v5e_chips[0])
    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16, sharding=chip),
        shapes)
    row = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)

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
    under = [line for line in compiled.as_text().splitlines()
             if "layer/linear_attn/delta_rule" in line]
    kernels = {}
    for line in under:
        if "tpu_custom_call" in line:
            scope = line.split('op_name="')[1].split('"')[0]
            phase = ("recompute" if "rematted_computation" in scope
                     else "bwd" if "transpose(" in scope else "fwd")
            name = scope.split("/")[-2]
            kernels[name, phase] = kernels.get((name, phase), 0) + 1
    n = big.n_linear_layers
    assert kernels == {
        ("gdn_chunk_fwd", "fwd"): n, ("gdn_chunk_fwd", "recompute"): n,
        ("gdn_chunk_bwd", "bwd"): n}, kernels
    assert not [line[:120] for line in under if " while(" in line]
    assert "InvertDiagBlocksLowerTriangular" not in compiled.as_text()
    return chip, params, compiled.memory_analysis().temp_size_in_bytes


def test_heads_of_96_by_192_compile_for_v5e_on_the_sweep_as_whole_tiles(
        v5e_chips, monkeypatch):
    """`olmoh-rollout64-512`'s gradient program (PR 59): 30 heads of 96 x
    192 are no whole 128-lane tiles, so `gdn_chunk` runs them as 32 of 128
    x 256 on zero columns — Mosaic takes the blocks (a grid step's v, o and
    carried S at twice q3next's width) and the program's temporaries are
    well under what the `jnp` form asked the chip for and was REFUSED (9.18
    GB to reserve beside 8.08 in use, my chip run, PR 59; this compile read
    9.51 GB for that form and 5.11 for this: 4.16 before the projection's
    output was held behind a barrier for the conv,
    `linear_attention._conv_reads_made_input`)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = bench_run.model_config(
        files.load_json("configs", "olmo-hybrid-7b-l4-v8.json"))
    assert la.chunk_kernel_form(big)
    _, _, temp = _grad_on_the_sweep(big, v5e_chips)
    assert temp <= 5_400_000_000, temp


def test_the_gradient_program_compiles_for_v5e_with_the_rule_on_its_kernels(
        v5e_chips, monkeypatch):
    """Mosaic and XLA:TPU for real, at the cell's micro-batch (one packed
    row of 8,192 tokens, the published widths, `remat="full"` as the train
    engine has it): each of the three Gated DeltaNet layers runs its
    chunked delta rule on the Pallas sweep — `gdn_chunk_fwd` in the forward
    and in the recomputed forward, `gdn_chunk_bwd` in the backward, all
    under `layer/linear_attn/delta_rule` — with no `while` and no
    `InvertDiagBlocksLowerTriangular` left under that scope (the `jnp`
    form's two loops of 128 trips and its solve), and the program's
    temporaries are not above the `jnp` form's.  Prefill keeps the `jnp`
    form: its lowered text holds no kernel of the rule.

    The period's four mixers are the published ones; a dense MLP stands
    in the 64 experts' place (PR 62).  Nothing here is asserted of the
    experts, whose `ragged-dot`s were 16 of this compile's 35 seconds,
    and the cell's expert layer compiles for a v5e in the decode loops
    above."""
    from areal_tpu.ops.pallas import delta_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = dataclasses.replace(
        bench_run.model_config(
            files.load_json("configs", "qwen3-next-80b-a3b-l4-e64.json")),
        n_experts=0, n_experts_per_tok=0)
    chip, params, temp = _grad_on_the_sweep(big, v5e_chips)
    assert temp <= _GRAD_TEMP_BYTES_ON_THE_JNP_FORM, temp

    # Prefill (64 rows, a 256-slot prompt window): `with_state`, so the
    # `jnp` form.
    monkeypatch.setattr(delta_chunk, "gdn_chunk", None)
    prompts = jax.ShapeDtypeStruct((64, 256), jnp.int32, sharding=chip)

    def prefill(p, tokens, seg):
        cache = tfm.init_kv_cache(big, 64, 768, dtype=jnp.bfloat16)
        return tfm.prefill(p, big, tokens, seg, cache)[0]

    text = jax.jit(prefill).trace(params, prompts, prompts).lower().as_text()
    assert "gdn_chunk" not in text

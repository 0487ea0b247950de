"""Which programs take the Pallas grouped matmul (`ops/pallas/
grouped_matmul.grouped_matmul`, PR 50) and which keep `jax.lax.ragged_dot`:
the choice by backend, mesh and expert widths; the three older MoE
configurations' gradient program and prefill traced on a TPU backend are
the parent's, text for text; a mesh of two devices keeps `ragged_dot`; what
the kernels cost a program BEFORE the compile cache is asked, as counts of
the lowered text (the set-up pins); and Mosaic and XLA:TPU for real on an
expert layer's forward and backward at the three touched configurations'
widths and slabs."""
import hashlib
import re

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from benchmark import files
from benchmark import run as bench_run

OLDER = ("olmoe-1b-7b-0125-l3", "qwen3-next-80b-a3b-l4-e64",
         "glm-4.7-flash-l7-e8")
TOUCHED = ("mellum2-12b-a2.5b-l4-e16", "nemotron-3-nano-30b-a3b-l9-e16",
           "lfm2-8b-a1b-e8")


def _big(name):
    return bench_run.model_config(files.load_json("configs", name + ".json"))


@pytest.mark.parametrize("name", OLDER + TOUCHED)
def test_the_choice_is_the_backend_and_the_experts_widths(name, monkeypatch):
    """None asks what the code can see: off a TPU `ragged_dot`; on one, the
    kernel where XLA's ragged kernel tiles the expert's [in, out] badly
    ([2304, 896], [2688, 1856], [2048, 1792]) and `ragged_dot` at
    [2048, 1024], [2048, 512] and [2048, 1536]; a bool is a bool."""
    cfg = _big(name)
    assert tfm.expert_kernel_choice(cfg, None) is False  # this host's CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm.expert_kernel_choice(cfg, None) is (name in TOUCHED)
    assert tfm.expert_kernel_choice(cfg, False) is False
    assert tfm.expert_kernel_choice(cfg, True) is True


def _program_text(cfg, program, **more):
    """The traced program over packed rows at the published widths (shapes
    alone: no weight is made), memory addresses in the text blanked."""
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((2, 1024), jnp.int32)
    if program == "grad":
        def loss(p, tok, seg):
            x, aux = tfm.hidden_states(p, cfg, tok, seg, remat="full", **more)
            return jnp.sum(x.astype(jnp.float32)) + aux

        text = str(jax.make_jaxpr(jax.grad(loss))(params, ints, ints))
    else:
        def fill(p, tok, seg):
            cache = tfm.init_kv_cache(cfg, 2, 1152, dtype=jnp.bfloat16)
            return tfm.prefill(p, cfg, tok, seg, cache, **more)

        text = str(jax.make_jaxpr(fill)(params, ints, ints))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


# sha256 of `_program_text` at the parent of PR 49 (a084ae5; jax 0.9.0) with
# `jax.default_backend` patched to "tpu" as below.
_PARENT_TEXTS = {
    ("olmoe-1b-7b-0125-l3", "grad"):
        "058cd4480fba78587756cede33bd1de23a4a59ff8e195685cd56f149447bf037",
    ("olmoe-1b-7b-0125-l3", "prefill"):
        "c8e654f4d10cff65884ddaf9cece8753a34243f3a73c4cb9acde491281d21547",
    ("qwen3-next-80b-a3b-l4-e64", "grad"):
        "b66e1cb2c51d54802837f851e57d08940e52446bddfb3951938092f37351666f",
    ("qwen3-next-80b-a3b-l4-e64", "prefill"):
        "2c1abe59cf2894e2c3da97997bd9f76b02488de4fc2a2206449f19647e10471f",
    ("glm-4.7-flash-l7-e8", "grad"):
        "997d528e71131eec7d909e47707788f2fe537d1f21a582d3c35e72f2283d2773",
    ("glm-4.7-flash-l7-e8", "prefill"):
        "0b6f713208849bb0c9828d92cc90717f80de6d8d053546c313ea099b85135fd5",
}


@pytest.mark.parametrize("name,program", sorted(_PARENT_TEXTS))
def test_the_older_moe_cells_trace_to_the_parents_program(
        name, program, monkeypatch):
    """On a TPU backend, at the cells' own widths: `ragged_dot` as it was
    and not one `grouped_matmul`, the whole traced text the parent's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the gradient program as a train engine on one device traces it
    more = {"expert_kernel": None} if program == "grad" else {}
    cfg = _big(name)
    if program == "grad" and cfg.n_linear_layers:
        # Since PR 52 a Gated DeltaNet layer's chunked rule is the Pallas
        # sweep `gdn_chunk` on a TPU backend: the text as traced there
        # holds it (and `ragged_dot` as it was); on the `jnp` form of the
        # rule the whole text is still the parent's.
        text = _program_text(cfg, program, **more)
        assert "gdn_chunk" in text
        assert "ragged_dot" in text and "grouped_matmul" not in text
        more["row_kernel"] = False
    text = _program_text(cfg, program, **more)
    assert "ragged_dot" in text and "grouped_matmul" not in text
    assert "gdn_chunk" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TEXTS[
        (name, program)]


@pytest.mark.parametrize("name", TOUCHED)
def test_the_touched_cells_gradient_program_traces_the_kernel_alone(
        name, monkeypatch):
    """The three share cells on a TPU backend: every expert matmul of the
    gradient program's first slab (forward, the remat's forward, dx, dw) is
    a kernel of `grouped_matmul.py`, and `ragged_dot` is left in the
    later-slab loop alone; `expert_kernel=False` — what an engine on a mesh
    passes — traces `ragged_dot` alone; and the forward-only programs keep
    `ragged_dot` whatever the widths (prefill, `forward`: their text, and
    so what a generator samples, is the parent's)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _big(name)
    before = tfm.expert_matmuls_traced()
    text = _program_text(cfg, "grad", expert_kernel=None)
    calls, on_kernel = (
        b - a for a, b in zip(before, tfm.expert_matmuls_traced()))
    assert 0 < on_kernel < calls
    for kernel in ("grouped_matmul", "grouped_matmul_dx", "grouped_matmul_dw"):
        assert f"name={kernel}\n" in text, kernel
    for program, more in (("grad", {"expert_kernel": False}), ("grad", {}),
                          ("prefill", {})):
        plain = _program_text(cfg, program, **more)
        assert "ragged_dot" in plain and "grouped_matmul" not in plain
        if program == "grad":  # the loop's are all that is left of them
            assert 0 < text.count("ragged_dot") < plain.count("ragged_dot")


def _badly_tiled_toy() -> ModelConfig:
    """Two experts of [64, 1152]: 1,152 is nine lanes, over 1,024 and no
    multiple of 512 (`ragged_tiles_badly`)."""
    return ModelConfig(
        n_layers=1, hidden_dim=64, n_q_heads=2, n_kv_heads=2, head_dim=32,
        intermediate_dim=128, vocab_size=64, param_dtype="float32",
        n_experts=2, n_experts_per_tok=2, moe_intermediate_dim=1152,
    )


@pytest.mark.parametrize("mode", ["d1", "d2", "f2"])
def test_a_mesh_of_two_devices_keeps_ragged_dot(mode, monkeypatch):
    """The train engine's gradient program on a TPU backend at widths the
    rule takes: `grouped_matmul` on one device, `ragged_dot` where the mesh
    has two (the kernel is one device's program) — and the step's counters
    say which."""
    from areal_tpu.api.model_api import FinetuneSpec
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.ops import functional as F

    cfg = _badly_tiled_toy()
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    engine = TrainEngine(
        cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)), mesh,
        ftspec=FinetuneSpec(1, 8, 8))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ints = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    batch = {
        "tokens": ints, "segment_ids": ints, "positions": ints,
        "prompt_mask": jax.ShapeDtypeStruct((2, 128), jnp.bool_),
    }
    text = str(jax.make_jaxpr(engine._get_grad_fn(F.sft_loss)[0])(
        engine.params, batch, jax.ShapeDtypeStruct((), jnp.float32)))
    calls, on_kernel = engine._expert_matmuls
    assert calls > 0
    if mode == "d1":
        assert on_kernel == calls
        assert "grouped_matmul" in text and "ragged_dot" not in text
    else:
        assert on_kernel == 0
        assert "ragged_dot" in text and "grouped_matmul" not in text

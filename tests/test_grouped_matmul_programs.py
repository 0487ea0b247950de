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
import importlib.util
import os
import re

import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from benchmark import files, program_trace
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


# ------------------------------------------------------ the set-up pins


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
        name, v5e_chip, lowering_check, monkeypatch):
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
    _, text = lowering_check.lowered(cfg, 1, length, v5e_chip, None)
    _, plain = lowering_check.lowered(cfg, 1, length, v5e_chip, False)
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


# ------------------------------------- an expert layer compiled for a v5e


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


@pytest.mark.parametrize("name", TOUCHED)
def test_an_expert_layer_compiles_for_v5e_with_ragged_dot_in_the_loop_alone(
        name, v5e_chip, monkeypatch):
    """Mosaic and XLA:TPU for real: one expert layer of the cell (the
    published widths, a micro-batch of 8,192 tokens, its slab) forward and
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
    one = SingleDeviceSharding(v5e_chip)
    tokens = 8192
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

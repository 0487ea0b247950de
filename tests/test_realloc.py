"""Parameter reallocation between 3D layouts: round-trip equality.

Mirrors the reference's tests/comm/test_param_realloc.py (reallocation
between different (dp, mp, pp) layouts must preserve values exactly) on the
8-virtual-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.parallel import realloc, sharding


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _assignment(sh):
    return realloc._device_assignment(sh)


@pytest.fixture
def device_puts(monkeypatch):
    """Every (leaf, destination) pair handed to `jax.device_put` while the
    test runs — the host route's only door."""
    seen = []
    inner = jax.device_put

    def spy(x, device=None, *a, **k):
        dsts = device if isinstance(device, (list, tuple)) else None
        for i, leaf in enumerate(jax.tree.leaves(x)):
            seen.append((leaf, dsts[i] if dsts else device))
        return inner(x, device, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize(
    "src,dst,route",
    [
        ("d1f4m2", "d8", "on_device"),
        ("d8", "d1f2m2s2", "on_device"),
        ("d1f2m4", "d2f2m2", "on_device"),
        ("d1f4", "d1m4", "on_device"),  # the colocated f4 -> m4 hand-back
        ("d1f2", "d1m2", "on_device"),
        ("d1m2", "d1f4m2", "put"),  # 2-device layout -> 8-device layout
    ],
)
def test_reshard_between_layouts(src, dst, route, device_puts):
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    want = _host(params)

    src_pc = ParallelConfig.from_str(src)
    dst_pc = ParallelConfig.from_str(dst)
    src_mesh = make_mesh(src_pc, jax.devices()[: src_pc.world_size])
    dst_mesh = make_mesh(dst_pc, jax.devices()[: dst_pc.world_size])

    on_src = sharding.shard_params(params, src_mesh)
    del device_puts[:]
    dst_shardings = sharding.tree_named(
        dst_mesh, sharding.param_pspecs(params)
    )
    on_dst, counts = realloc.reshard_counted(on_src, dst_shardings)

    # Destination layout is the canonical one for dst_mesh.
    flat_got = jax.tree.leaves(on_dst)
    for leaf, want_sh in zip(flat_got, jax.tree.leaves(dst_shardings)):
        assert leaf.sharding == want_sh
    _assert_tree_equal(on_dst, want)

    # The route, by the counters and by what reached jax.device_put.
    n = len(flat_got)
    assert counts["bytes"] == realloc.tree_bytes(on_dst)
    assert (
        counts["leaves_aliased"] + counts["leaves_resharded"]
        + counts["leaves_put"]
    ) == n
    if route == "on_device":
        # Same devices in the same order: no leaf may see the host.
        assert counts["leaves_put"] == 0 and counts["bytes_put"] == 0
        assert counts["leaves_resharded"] > 0
        assert 0 < counts["bytes_resharded"] <= counts["bytes"]
        assert not [
            1 for leaf, to in device_puts
            if _assignment(leaf.sharding) == _assignment(to)
        ]
    else:
        assert counts["leaves_put"] == n
        assert counts["bytes_put"] == counts["bytes"]
        assert len(device_puts) == n

    # Round-trip back.
    back = realloc.reshard_params(on_dst, src_mesh)
    _assert_tree_equal(back, want)


def test_identical_layout_and_dtype_is_the_same_object(device_puts):
    """In place: nothing to move, nothing to copy — every leaf comes back
    as the object that went in (the one-chip colocated alias: a copy of a
    1.5B model's weights does not fit beside its optimizer state)."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(5))
    mesh = make_mesh(ParallelConfig.from_str("d1f2m2"), jax.devices()[:4])
    on = sharding.shard_params(params, mesh)
    del device_puts[:]
    same_mesh = make_mesh(ParallelConfig.from_str("d1f2m2"), jax.devices()[:4])
    out, counts = realloc.reshard_counted(
        on,
        sharding.tree_named(same_mesh, sharding.param_pspecs(params)),
        dtype=jax.tree.leaves(on)[0].dtype,
    )
    n = len(jax.tree.leaves(on))
    assert counts["leaves_aliased"] == n
    assert counts["leaves_resharded"] == counts["leaves_put"] == 0
    assert all(
        a is b for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(on))
    )
    assert not device_puts


def test_equivalent_layout_keeps_the_buffers_under_the_new_sharding():
    """f4 and m4 both replicate the norm scales: the bytes stay where they
    are, and the leaf carries the destination's sharding (what the
    destination's programs were compiled for)."""
    from areal_tpu.engines.offload import buffers_alias

    f4 = make_mesh(ParallelConfig.from_str("d1f4"), jax.devices()[:4])
    m4 = make_mesh(ParallelConfig.from_str("d1m4"), jax.devices()[:4])
    P = jax.sharding.PartitionSpec
    x = jax.device_put(
        jnp.arange(12.0).reshape(3, 4), jax.sharding.NamedSharding(f4, P())
    )
    dst = jax.sharding.NamedSharding(m4, P("pipe", None))
    out, counts = realloc.reshard_counted({"ln": x}, {"ln": dst})
    assert counts["leaves_aliased"] == 1 and counts["bytes_resharded"] == 0
    assert out["ln"].sharding == dst
    assert buffers_alias(out["ln"], x)
    np.testing.assert_array_equal(np.asarray(out["ln"]), np.asarray(x))


@pytest.mark.parametrize("source", ["host", "disjoint", "uncommitted"])
def test_everything_else_takes_device_put(source, device_puts):
    """Host numpy (checkpoint load, pushed weights), another device set
    (decoupled gen/train meshes) and uncommitted arrays: jax.device_put."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(6))
    want = _host(params)
    dst_mesh = make_mesh(ParallelConfig.from_str("d2m2"), jax.devices()[4:8])
    if source == "host":
        tree = want
    elif source == "disjoint":
        tree = sharding.shard_params(
            params,
            make_mesh(ParallelConfig.from_str("d1f2m2"), jax.devices()[:4]),
        )
    else:
        tree = params
    del device_puts[:]
    out, counts = realloc.reshard_counted(
        tree, sharding.tree_named(dst_mesh, sharding.param_pspecs(params))
    )
    n = len(jax.tree.leaves(params))
    assert counts["leaves_put"] == n == len(device_puts)
    assert counts["leaves_aliased"] == counts["leaves_resharded"] == 0
    assert counts["bytes_put"] == counts["bytes"]
    _assert_tree_equal(out, want)


def test_reshard_disjoint_device_sets():
    """Decoupled gen/train meshes: params move between non-overlapping
    device subsets (reference: sglang.d64p1m1+d32p2m1 split allocation)."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    want = _host(params)

    pc4 = ParallelConfig.from_str("d1f2m2")
    train_mesh = make_mesh(pc4, jax.devices()[:4])
    gen_mesh = make_mesh(ParallelConfig.from_str("d2m2"), jax.devices()[4:8])

    on_train = sharding.shard_params(params, train_mesh)
    on_gen = realloc.reshard_params(on_train, gen_mesh)
    assert set(d for l in jax.tree.leaves(on_gen) for d in l.sharding.device_set) == set(
        jax.devices()[4:8]
    )
    _assert_tree_equal(on_gen, want)


def test_reshard_with_dtype_cast():
    """fp32 master -> bf16 serving copy in one reallocation."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(2))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)

    mesh_a = make_mesh(ParallelConfig.from_str("d1f4"), jax.devices()[:4])
    mesh_b = make_mesh(ParallelConfig.from_str("d1m4"), jax.devices()[4:8])
    on_a = sharding.shard_params(params, mesh_a)
    on_b = realloc.reshard_params(on_a, mesh_b, dtype=jnp.bfloat16)
    for leaf in jax.tree.leaves(on_b):
        assert leaf.dtype == jnp.bfloat16
    _assert_tree_equal(
        on_b, jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    )


def test_cast_happens_on_device_and_equals_astype_then_place(device_puts):
    """fp32 master -> bf16 serving copy on the SAME devices: one compiled
    program casts and re-lays-out; bit-equal to astype + device_put."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    mesh_a = make_mesh(ParallelConfig.from_str("d1f4"), jax.devices()[:4])
    mesh_b = make_mesh(ParallelConfig.from_str("d1m4"), jax.devices()[:4])
    on_a = sharding.shard_params(params, mesh_a)
    dst = sharding.tree_named(mesh_b, sharding.param_pspecs(params))
    want = jax.device_put(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), on_a), dst
    )
    del device_puts[:]
    got, counts = realloc.reshard_counted(on_a, dst, dtype=jnp.bfloat16)
    n = len(jax.tree.leaves(params))
    # A cast is never in place, even where the layout already fits.
    assert counts["leaves_resharded"] == n and counts["leaves_put"] == 0
    assert counts["bytes_resharded"] == counts["bytes"] == sum(
        x.size * 2 for x in jax.tree.leaves(params)
    )
    assert not device_puts
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == jnp.bfloat16 and g.sharding == w.sharding
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # The source survives (nothing was donated).
    _assert_tree_equal(on_a, _host(params))


def test_relayout_program_is_held_not_rebuilt():
    """A second hand-back between the same layouts traces and compiles
    nothing: the jitted function is held per destination and dtype."""
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(7))
    mesh_a = make_mesh(ParallelConfig.from_str("d1f2"), jax.devices()[2:4])
    mesh_b = make_mesh(ParallelConfig.from_str("d1m2"), jax.devices()[2:4])
    dst = sharding.tree_named(mesh_b, sharding.param_pspecs(params))
    on_a = sharding.shard_params(params, mesh_a)
    realloc.reshard(on_a, dst)
    before = realloc._relayout_program.cache_info()
    moved = [
        d for x, d in zip(jax.tree.leaves(on_a), jax.tree.leaves(dst))
        if not x.sharding.is_equivalent_to(d, x.ndim)
    ]
    program = realloc._relayout_program(tuple(moved), None)
    assert realloc._relayout_program.cache_info().misses == before.misses
    assert program._cache_size() == 1
    # New values, same layouts: the optimizer step's output.
    on_a2 = jax.tree.map(lambda x: x + 1, on_a)
    out = realloc.reshard(on_a2, dst)
    assert realloc._relayout_program.cache_info().misses == before.misses
    assert program._cache_size() == 1
    _assert_tree_equal(out, _host(on_a2))


def test_replicate_to():
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(4))
    mesh_a = make_mesh(ParallelConfig.from_str("d1f4m2"), jax.devices())
    mesh_b = make_mesh(ParallelConfig.from_str("d4"), jax.devices()[:4])
    on_a = sharding.shard_params(params, mesh_a)
    rep = realloc.replicate_to(on_a, mesh_b)
    for leaf in jax.tree.leaves(rep):
        assert leaf.sharding.is_fully_replicated
    _assert_tree_equal(rep, _host(params))

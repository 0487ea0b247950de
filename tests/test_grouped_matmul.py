"""The decode step's grouped expert matmul (`ops/pallas/grouped_matmul.py`):
the kernel, interpreted, against a per-group dense product in fp32 at the
cells' shapes and at every shape of group sizes a step can make; the rule
that picks a step's tile (`tiles`: by bytes, PR 47) over a sweep of
shapes, and its grids at the four shapes the benchmark runs; the tile in
the trace's scope names, and that the benchmark's readers read `layer/mlp`
of such names as before; Mosaic and XLA:TPU for real on the mellum cell's
decode loop."""
import functools
import hashlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops.pallas import grouped_matmul as gm
from benchmark.metrics import _moe, _program

# name -> (rows R, tokens T, experts E, K, N): a step's call in the cell
SHAPES = {
    "mellum_up": (256, 32, 16, 2304, 896),
    "mellum_down": (256, 32, 16, 896, 2304),
    "nemotron_up": (384, 64, 16, 2688, 1856),
    "nemotron_down": (384, 64, 16, 1856, 2688),
    "toy": (48, 12, 4, 256, 192),
}


def _sizes(kind, e, t, r):
    """A step's rows per expert, by what the step looks like."""
    some = np.minimum(np.arange(e) % 5 + 1, t)  # 1..5 rows, every expert
    if kind == "every_expert":
        return some
    if kind == "none_at_the_front":
        return np.where(np.arange(e) < 2, 0, some)
    if kind == "none_in_the_middle":
        return np.where((np.arange(e) > 0) & (np.arange(e) < e - 1), 0, some)
    if kind == "none_at_the_end":
        return np.where(np.arange(e) >= e - 2, 0, some)
    if kind == "one_expert_takes_every_token":  # a group of `max_rows`
        return np.where(np.arange(e) == e // 2, t, some)
    assert kind == "every_row_held"  # no row past the groups
    sizes = np.full(e, r // e)
    assert r % e == 0 and r // e <= t
    return sizes


def _dense(xs, w, sizes, layer):
    """Row by row in fp32: group g's rows times expert `layer * E + g`."""
    e = len(sizes)
    want = np.zeros((xs.shape[0], w.shape[2]), np.float32)
    lo = 0
    for g, size in enumerate(sizes):
        want[lo: lo + size] = (
            np.asarray(xs[lo: lo + size], np.float32)
            @ np.asarray(w[layer * e + g], np.float32))
        lo += size
    return want


_KINDS = [
    "every_expert", "none_at_the_front", "none_in_the_middle",
    "none_at_the_end", "one_expert_takes_every_token", "every_row_held"]


@pytest.mark.parametrize("shape,kind", [
    *((shape, kind) for shape in ("toy", "mellum_up", "mellum_down")
      for kind in _KINDS),
    # interpreted, a Nemotron call takes 20-40 s: one kind each
    ("nemotron_up", "one_expert_takes_every_token"),
    ("nemotron_down", "none_at_the_front"),
])
def test_the_kernel_is_the_per_group_dense_product(shape, kind):
    """bf16 operands as the cells', the tile `tiles` picks, the SECOND
    layer of a stacked leaf: every held row is its expert's product to
    bf16's rounding of an fp32 sum, every row past the groups zero."""
    r, t, e, k, n = SHAPES[shape]
    sizes = _sizes(kind, e, t, r)
    rng = np.random.default_rng(k + len(kind))
    xs = jnp.asarray(rng.standard_normal((r, k)), jnp.bfloat16)
    w = jnp.asarray(
        rng.standard_normal((2 * e, k, n)) * k**-0.5, jnp.bfloat16)
    got = np.asarray(gm.grouped_decode_matmul(
        xs, w, jnp.asarray(sizes, jnp.int32), jnp.int32(1), max_rows=t
    ).astype(jnp.float32))
    want = _dense(xs, w, sizes, 1)
    np.testing.assert_allclose(got, want, rtol=2**-7, atol=2**-7)
    held = int(sizes.sum())
    assert np.abs(want[:held]).max() > 1 and not got[held:].any()


@pytest.mark.parametrize("r,t,sizes,tile", [
    # rows no multiple of 16; K and N both cut
    (30, 5, (5, 0, 4, 1), (128, 256)),
    (30, 5, (0, 5, 5, 0), (512, 128)),
    (44, 11, (11, 0, 11, 7), (256, 512)),  # whole N, two K pieces
    (16, 16, (0, 0, 16, 0), (512, 512)),  # one window is every row
])
def test_any_tile_gives_the_same_rows(r, t, sizes, tile):
    """`_call` at a forced tile, fp32, R no multiple of 16, layer 0 and
    the last: the tile moves the order of a sum and nothing else."""
    e, k, n = 4, 512, 512
    rng = np.random.default_rng(r)
    sizes = np.asarray(sizes)
    xs = jnp.asarray(rng.standard_normal((r, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3 * e, k, n)) * k**-0.5, jnp.float32)
    for layer in (0, 2):
        got = gm._call(xs, w, jnp.asarray(sizes, jnp.int32), layer, t, *tile)
        np.testing.assert_allclose(
            got, _dense(xs, w, sizes, layer), rtol=1e-4, atol=1e-4)
        assert not np.asarray(got[int(sizes.sum()):]).any()


# ------------------------------------- the packed rows' kernel: fwd, dx, dw

# Toy cuts of the three share cells' expert widths: mellum's [2304, 896]
# (whole lanes, K in three pieces), nemotron's [2688, 1856] (1,856 is 14.5
# lanes: 232 is not whole lanes either) and lfm2's [2048, 1792].
WIDTHS = {"mellum": (384, 128), "nemotron": (384, 232), "lfm2": (256, 256)}
# Rows per group of a slab of 128 rows walked in row tiles of 32.
GROUPS = {
    "an_empty_group_first": (0, 40, 30, 20),
    "an_empty_group_last": (40, 30, 20, 0),
    "empty_groups_in_the_middle": (40, 0, 0, 30),
    "a_group_larger_than_a_row_tile": (100, 5, 5, 5),
    "boundaries_inside_a_row_tile": (10, 10, 10, 10),
    "rows_past_every_group": (8, 8, 8, 8),  # three row tiles hold no row
    "every_row_in_one_group": (0, 0, 128, 0),
    "boundaries_on_the_row_tiles": (32, 64, 0, 32),
}
SLAB, ROW_BLOCK = 128, 32


def _operands(k, n, sizes, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed + k + n)
    e = len(sizes)
    return (
        jnp.asarray(rng.standard_normal((SLAB, k)), dtype),
        jnp.asarray(rng.standard_normal((e, k, n)) * k**-0.5, dtype),
        jnp.asarray(sizes, jnp.int32),
        jnp.asarray(rng.standard_normal((SLAB, n)), dtype),
    )


def _dense_three(xs, w, sizes, dy):
    """(out, dx, dw) group by group in fp64; rows past the groups zero."""
    xs, w, dy = (np.asarray(a, np.float64) for a in (xs, w, dy))
    out, dx, dw = np.zeros(dy.shape), np.zeros(xs.shape), np.zeros(w.shape)
    lo = 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = slice(lo, lo + size)
        out[rows] = xs[rows] @ w[g]
        dx[rows] = dy[rows] @ w[g].T
        dw[g] = xs[rows].T @ dy[rows]
        lo += size
    return out, dx, dw


def _ragged_three(xs, w, sizes, dy):
    """The same three from `jax.lax.ragged_dot` and its autodiff, the rows
    past the groups masked as `_grouped_rows` masks them."""
    held = (jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None]

    def f(xs, w):
        return jnp.where(held, jax.lax.ragged_dot(
            jnp.where(held, xs, 0), w, sizes), 0)

    out, vjp = jax.vjp(f, xs, w)
    return (out, *vjp(dy))


@pytest.mark.parametrize("groups", list(GROUPS))
@pytest.mark.parametrize("width", list(WIDTHS))
def test_forward_dx_and_dw_are_the_per_group_products(
        width, groups, monkeypatch):
    """`grouped_matmul` and its gradient rule, interpreted, in row tiles
    of 32: each of the three against the per-group dense product and
    against `ragged_dot` and its autodiff; rows past every group zero,
    forward and in dx; a group without rows gets a zero dw."""
    monkeypatch.setattr(gm, "ROW_BLOCK", ROW_BLOCK)
    k, n = WIDTHS[width]
    xs, w, sizes, dy = _operands(k, n, GROUPS[groups])
    out, vjp = jax.vjp(lambda xs, w: gm.grouped_matmul(xs, w, sizes), xs, w)
    got = (out, *vjp(dy))
    for name, mine, dense, ragged in zip(
            ("out", "dx", "dw"), got, _dense_three(xs, w, sizes, dy),
            _ragged_three(xs, w, sizes, dy)):
        np.testing.assert_allclose(
            mine, dense, rtol=1e-4, atol=1e-4, err_msg=name)
        np.testing.assert_allclose(
            mine, ragged, rtol=1e-4, atol=1e-4, err_msg=name)
    held = int(sizes.sum())
    assert not np.asarray(got[0][held:]).any()
    assert not np.asarray(got[1][held:]).any()
    empty = np.flatnonzero(np.asarray(sizes) == 0)
    assert not np.asarray(got[2])[empty].any()
    assert float(jnp.abs(got[2]).max()) > 1


@pytest.mark.parametrize("tile", [
    (16, 128, 128), (32, 384, 128), (64, 128, 256), (128, 384, 256),
    (48, 384, 256),  # rows padded to a multiple of the row tile
])
@pytest.mark.parametrize("groups", [
    "an_empty_group_first", "a_group_larger_than_a_row_tile",
    "rows_past_every_group"])
def test_any_train_tile_gives_the_same_rows(groups, tile):
    """Forced (tm, tk, tn): K cut (sums in VMEM across steps), N cut, a
    row tile of every size — the tile moves the order of a sum and
    nothing else, in each of the three kernels."""
    k, n = 384, 256
    xs, w, sizes, dy = _operands(k, n, GROUPS[groups])
    want = _dense_three(xs, w, sizes, dy)
    got = (
        gm._matmul_call(xs, w, sizes, False, tile),
        gm._matmul_call(dy, w, sizes, True, tile),
        gm._dw_call(xs, dy, sizes, w.dtype, tile),
    )
    for name, mine, dense in zip(("out", "dx", "dw"), got, want):
        assert mine.shape == dense.shape
        np.testing.assert_allclose(
            mine, dense, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_bf16_operands_give_ragged_dots_numbers(width, monkeypatch):
    """The cells' precision: bf16 into the product, fp32 sums, the result
    in bf16 — `ragged_dot`'s numbers to bf16's rounding of one sum."""
    monkeypatch.setattr(gm, "ROW_BLOCK", ROW_BLOCK)
    k, n = WIDTHS[width]
    xs, w, sizes, dy = _operands(
        k, n, GROUPS["boundaries_inside_a_row_tile"], jnp.bfloat16)
    out, vjp = jax.vjp(lambda xs, w: gm.grouped_matmul(xs, w, sizes), xs, w)
    for name, mine, ragged in zip(
            ("out", "dx", "dw"), (out, *vjp(dy)),
            _ragged_three(xs, w, sizes, dy)):
        assert mine.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(mine, np.float32), np.asarray(ragged, np.float32),
            rtol=2**-6, atol=2**-5, err_msg=name)


@pytest.mark.parametrize("policy", ["nothing_saveable", "dots_saveable"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_under_checkpoint_the_gradients_are_ragged_dots(
        gated, policy, monkeypatch):
    """An expert MLP of two or three grouped matmuls under `jax.checkpoint`
    (the forward kernel runs again on the way back): the gradients of the
    rows and of every matrix equal those of the same function on
    `ragged_dot`."""
    monkeypatch.setattr(gm, "ROW_BLOCK", ROW_BLOCK)
    k, n = WIDTHS["nemotron"]
    xs, wu, sizes, _ = _operands(k, n, GROUPS["an_empty_group_first"])
    _, wg, _, _ = _operands(k, n, GROUPS["an_empty_group_first"], seed=1)
    wd = jnp.swapaxes(wg, 1, 2) * 0.5
    held = (jnp.arange(SLAB) < jnp.sum(sizes))[:, None]

    def ragged(lhs, w):
        return jnp.where(held, jax.lax.ragged_dot(
            jnp.where(held, lhs, 0), w, sizes), 0)

    def loss(product):
        @functools.partial(
            jax.checkpoint, policy=getattr(jax.checkpoint_policies, policy))
        def mlp(xs, wg, wu, wd):
            hid = product(xs, wu)
            hid = jax.nn.silu(product(xs, wg)) * hid if gated else hid * hid
            return product(hid, wd)

        return lambda *a: jnp.sum(jnp.sin(mlp(*a)))

    got = jax.grad(loss(lambda lhs, w: gm.grouped_matmul(lhs, w, sizes)),
                   argnums=(0, 1, 2, 3))(xs, wg, wu, wd)
    want = jax.grad(loss(ragged), argnums=(0, 1, 2, 3))(xs, wg, wu, wd)
    for name, mine, theirs in zip(("dxs", "dwg", "dwu", "dwd"), got, want):
        if name == "dwg" and not gated:
            assert not np.asarray(mine).any()
            continue
        assert float(jnp.abs(theirs).max()) > 1e-2, name
        np.testing.assert_allclose(
            mine, theirs, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("every_group", [False, True], ids=["rows", "dw"])
@pytest.mark.parametrize("seed", range(4))
def test_the_visit_tables_name_every_live_pair_once(seed, every_group):
    """`_visits` over random group sizes (some empty, rows left past the
    groups): every (row tile, group) pair that shares a row is visited
    once with exactly the shared rows, in order of group; for the forward
    every row tile past the held rows once more, to be zeroed, reading
    what the visit before it read; for dw every empty group once; what is
    left of the table repeats the last visit and does nothing."""
    rng = np.random.default_rng(seed)
    e, tm, tiles_m = 6, 16, 8
    sizes = rng.integers(0, 30, e) * (rng.random(e) < 0.7)
    sizes = np.minimum(sizes, (tm * tiles_m - 20) // e)
    group, src, dst, lo, hi = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes, jnp.int32), tm * tiles_m, tm, every_group))
    assert len(group) == tiles_m + e - 1
    ends = np.cumsum(sizes)
    starts = ends - sizes
    want = [
        (g, t, max(starts[g], t * tm), min(ends[g], (t + 1) * tm))
        for g in range(e) for t in range(tiles_m)
        if max(starts[g], t * tm) < min(ends[g], (t + 1) * tm)]
    live = [(g, t, a, b) for g, t, a, b in zip(group, src, lo, hi) if b > a]
    assert live == want
    assert all(d == s for d, s, a, b in zip(dst, src, lo, hi) if b > a)
    idle = [i for i in range(len(group)) if hi[i] <= lo[i]]
    if every_group:
        firsts = [i for i in idle if i == 0 or group[i] != group[i - 1]]
        assert sorted(group[firsts]) == list(np.flatnonzero(sizes == 0))
    else:
        firsts = [i for i in idle if i == 0 or dst[i] != dst[i - 1]]
        assert list(dst[firsts]) == list(range(-(-ends[-1] // tm), tiles_m))
    for i in idle:  # nothing new is fetched for a visit without rows
        if i and not (every_group and i in firsts):
            assert (group[i], src[i]) == (group[i - 1], src[i - 1])


def test_the_train_tiles_at_the_three_cells_widths():
    """(tm, tk, tn) from the shapes alone: 256 rows against the largest
    piece of the matrix whose step fits half the VMEM the kernel asks for
    — the whole of it in forward and dx at the three cells' widths, in dw
    at two of them; a larger matrix is cut in whole lanes, and a dimension
    that is not whole lanes never."""
    picked = {
        (k, n): (gm.matmul_tiles(16384, k, n, 2), gm.dw_tiles(16384, k, n, 2))
        for k, n in [(2304, 896), (896, 2304), (2688, 1856), (1856, 2688),
                     (2048, 1792), (1792, 2048)]}
    assert picked == TRAIN_TILES
    assert gm.matmul_tiles(40, 256, 232, 4) == (48, 256, 232)
    for (k, n), (mm, dw) in picked.items():
        for tm, tk, tn in (mm, dw):
            assert k % tk == 0 and n % tn == 0
            assert (tk % 128 == 0 or tk == k) and (tn % 128 == 0 or tn == n)
        assert gm.step_bytes(*mm[1:], mm[0], 2) <= gm.VMEM_LIMIT // 2
    assert gm.matmul_tiles(16384, 8192, 2048, 2) == (256, 2048, 2048)
    assert gm.dw_tiles(16384, 4096, 1856, 2) == (256, 1024, 1856)
    assert gm.dw_tiles(16384, 3712, 4096, 2) == (256, 3712, 512)


TRAIN_TILES = {
    (2304, 896): ((256, 2304, 896), (256, 2304, 896)),
    (896, 2304): ((256, 896, 2304), (256, 896, 2304)),
    (2688, 1856): ((256, 2688, 1856), (256, 896, 1856)),
    (1856, 2688): ((256, 1856, 2688), (256, 1856, 896)),
    (2048, 1792): ((256, 2048, 1792), (256, 2048, 1792)),
    (1792, 2048): ((256, 1792, 2048), (256, 1792, 2048)),
}


# --------------------------------------------------------------- the chooser


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("rows", [8, 64, 256, 384, 640])
def test_a_tile_divides_the_matrix_in_whole_lanes_and_fits_the_limit(
        rows, itemsize):
    """Over every [k, n] of multiples of 64 up to 4,096: a tile divides
    its dimension and is whole lanes of it or all of it; what a step holds
    in VMEM (2 x weight tile + 2 x the rows' piece + 2 x the result block
    + the sums) stays under half of what the kernel asks Mosaic for
    wherever any tile does; it is the smallest contiguous piece of
    MIN_TILE to MAX_TILE bytes wherever the matrix has one, and N is cut
    only where it has none."""
    dims = range(64, 4097, 64)
    padded = rows + -rows % gm.ROW_TILE
    for k in dims:
        for n in dims:
            tk, tn = gm.tiles(k, n, rows, itemsize)
            assert k % tk == 0 and n % tn == 0
            assert (tk % 128 == 0 or tk == k) and (tn % 128 == 0 or tn == n)
            assert tk == k or tn == n  # one dimension is cut, never both
            size = tk * tn * itemsize
            legal = [(p, n) for p in gm._pieces(k)] + [
                (k, p) for p in gm._pieces(n)]
            legal = [t for t in legal if gm.step_bytes(
                *t, padded, itemsize) <= gm.VMEM_LIMIT // 2]
            assert (tk, tn) in legal or not legal
            contiguous = [
                t for t in legal if t[1] == n
                and gm.MIN_TILE <= t[0] * n * itemsize <= gm.MAX_TILE]
            if contiguous:
                assert (tk, tn) == contiguous[0]
            elif size < gm.MIN_TILE:  # nothing larger would do
                assert all(t[0] * t[1] <= tk * tn for t in legal)
            else:
                assert tk == k


def test_the_four_grids_the_benchmark_runs():
    """Grid steps a call = K pieces x N pieces x 16 experts.  Mellum's
    were 672 a call by lane divisors (PR 40's `tile_for`: 896 = 7 x 128
    gave 128); Nemotron's are as PR 40 committed them."""
    grids = {}
    for name, (r, _t, e, k, n) in SHAPES.items():
        tk, tn = gm.tiles(k, n, r, 2)
        grids[name] = ((tk, tn), (k // tk) * (n // tn) * e)
    assert grids == {
        "mellum_up": ((384, 896), 96),
        "mellum_down": ((896, 2304), 16),
        "nemotron_up": ((384, 1856), 112),
        "nemotron_down": ((1856, 384), 112),
        "toy": ((256, 192), 4),
    }


# The traced call at Nemotron's two shapes — prologue, grid, block shapes,
# index maps, the kernel's body, its compiler parameters — as the parent
# of PR 47 (a264bd5) traced it: sha256 of `str(jax.make_jaxpr(...))`, which
# holds no source location (the lowered module's kernel bytecode does).
_PARENT_CALLS = {
    "nemotron_up":
        "4628336a5d2ee8e5ffe662e3a625182efbc498b88c427fd7d18d36fab8d0c654",
    "nemotron_down":
        "3c0d6abd386390eef25d68568b15f6084005528ad647a56e6ea17e6d7e771854",
}


@pytest.mark.parametrize("shape", sorted(_PARENT_CALLS))
def test_nemotrons_call_traces_to_the_parents_program(shape, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r, t, e, k, n = SHAPES[shape]
    spec = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(
        lambda *a: gm.grouped_decode_matmul.__wrapped__(*a, max_rows=t))(
        spec((r, k), jnp.bfloat16), spec((4 * e, k, n), jnp.bfloat16),
        spec((e,), jnp.int32), spec((), jnp.int32)))
    assert "vmem_limit_bytes=67108864" in text and "block_size=384" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_CALLS[shape]


# ------------------------------------------------------ the tile in the trace


def test_the_readers_take_layer_mlp_of_a_tiled_name_as_before():
    """`_program.scope_seconds` and `_moe.mlp_seconds` match scopes by
    runs of elements: one more element between `experts` and the kernel
    changes no sum (2 traced steps; the kernel, the activation between
    the calls, the router, and an attention scope that is not the MLP's)."""
    phases = lambda s: {"fwd": s, "recompute": 0.0, "bwd": 0.0}  # noqa: E731
    decode = "gen/generate/gen/decode_step/layer/mlp"
    scopes = {
        f"{decode}/experts/w384x896/grouped_decode_matmul": phases(0.8),
        f"{decode}/experts/w896x2304/grouped_decode_matmul": phases(0.4),
        f"{decode}/experts": phases(0.2),
        f"{decode}/router": phases(0.1),
        "gen/generate/gen/decode_step/layer/attn/window": phases(9.0),
        "train/grad/layer/mlp/experts": phases(3.0),
    }
    run = types.SimpleNamespace(trace={
        "scope_seconds": scopes, "busy_s": 20.0, "traced_steps": 2,
        "op_seconds_scoped": {
            "grouped_decode_matmul.3 = bf16[256,896] @w384x896:fwd": 0.8}})
    assert _program.scope_seconds(
        run, "gen/decode_step", "layer/mlp") == pytest.approx(0.75)
    assert _moe.mlp_seconds(run, _moe.DECODE) == pytest.approx(0.75)
    assert _program.scope_seconds(
        run, "gen/decode_step", "layer/mlp/experts") == pytest.approx(0.7)
    assert _moe.mlp_seconds(run, _moe.TRAIN) == pytest.approx(1.5)
    assert _moe.ragged_seconds(run, _moe.DECODE) is None  # no ragged kernel

"""The static program's softmax attention on the Pallas kernel `kv_decode`
(`ops/pallas/kv_decode.py`), interpreted on the CPU: the kernel against
`ops/attention.decode_attention` over a layer of the stacked cache (GQA, MHA,
the block step's fold, windows that start late, end inside a tile, end row by
row, or are empty); the tiles its index map names; one trace a program; the
chooser (`transformer.kv_kernel_form`) over the benchmark's thirteen
configurations, on one device and on a mesh; the decode step and the block
step on either form; and the counters a roll-out reports
(`gen/kv_kernel`, `gen/kv_live_tile_share`) against a count by hand."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models import transformer as tfm
from areal_tpu.ops.attention import decode_attention
from areal_tpu.ops.pallas import flash_attention, kv_decode as kd
from benchmark import files
from benchmark import run as bench_run
from tests import lowered_programs

LAYERS, LAYER = 3, 1


def _operands(b, s, n_kv, rep, d, tokens=1, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, tokens, n_kv * rep, d), dtype)
    k = jax.random.normal(ks[1], (LAYERS, b, s, n_kv, d), dtype)
    v = jax.random.normal(ks[2], (LAYERS, b, s, n_kv, d), dtype)
    return q, k, v


def _xla(q, k, v, lo, hi):
    """`decode_attention` over layer LAYER, a row's tokens folded into the
    query heads of their key head as `_attention_block_step` folds them."""
    b, t, n_q, d = q.shape
    g = k.shape[3]
    qx = q.reshape(b, t, g, n_q // g, d).transpose(0, 2, 1, 3, 4)
    out = decode_attention(
        qx.reshape(b, 1, -1, d), k[LAYER], v[LAYER], jnp.asarray(lo),
        jnp.asarray(hi))
    return out.reshape(b, g, t, n_q // g, d).transpose(
        0, 2, 1, 3, 4).reshape(q.shape)


CASES = {
    # name: (rows, slots, key heads, query heads a key head, tokens a row,
    #        valid_from, valid_to, (rows, slots) a grid step or None)
    "gqa_r6_g2": (4, 384, 2, 6, 1, [0, 0, 0, 0], 384, None),
    "mha_r1_g16": (2, 256, 16, 1, 1, [0, 0], 256, None),
    "block_fold_q4_r8": (2, 256, 4, 8, 4, [0, 0], 200, None),
    "valid_from_late": (4, 384, 2, 6, 1, [0, 5, 130, 300], 384, None),
    "valid_to_inside_a_tile": (4, 384, 2, 6, 1, [0, 5, 130, 140], 201, None),
    "valid_to_a_row": (4, 384, 2, 6, 1, [0, 5, 130, 300],
                       [384, 200, 131, 301], None),
    "rows_two_a_step": (4, 384, 2, 6, 1, [0, 5, 130, 300], 333, (2, 128)),
    "one_row_a_step": (4, 384, 2, 6, 1, [0, 5, 130, 300], 333, (1, 128)),
    "two_tiles_a_step": (2, 512, 4, 2, 1, [7, 300], 400, (2, 256)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_decode_attention_over_the_live_keys(name):
    b, s, g, rep, t, lo, hi, block = CASES[name]
    q, k, v = _operands(b, s, g, rep, 128, tokens=t)
    lo = jnp.asarray(lo, jnp.int32)
    got = kd.kv_decode(q, k, v, LAYER, lo, hi, block=block)
    want = _xla(q, k, v, lo, hi)
    assert got.shape == q.shape and got.dtype == q.dtype
    # bf16 outputs of order 1: one unit in the last place is 2^-8.
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2**-7, rtol=0)


def test_in_fp32_the_kernel_is_decode_attention_to_rounding():
    """A toy window (48 slots: one tile, the whole window) and a toy head
    (16 wide), fp32 operands: the same sums in another order."""
    q, k, v = _operands(2, 48, 2, 3, 16, dtype=jnp.float32)
    lo = jnp.asarray([3, 10], jnp.int32)
    got = kd.kv_decode(q, k, v, LAYER, lo, 40)
    np.testing.assert_allclose(got, _xla(q, k, v, lo, 40), atol=2e-6, rtol=0)


def test_an_empty_window_gives_exact_zeros():
    """As `_decode_attention`: a row whose window is empty (a pad row, a
    row whose `valid_to` is its `valid_from`) reads nothing and gives 0 —
    beside a live row in its tile, and in a tile of empty rows alone."""
    q, k, v = _operands(4, 256, 2, 6, 128)
    lo = jnp.asarray([0, 100, 256, 7], jnp.int32)
    hi = jnp.asarray([200, 100, 256, 7], jnp.int32)
    for block in ((4, 128), (1, 128)):
        got = np.asarray(
            kd.kv_decode(q, k, v, LAYER, lo, hi, block=block), np.float32)
        assert np.all(got[1:] == 0.0) and np.any(got[0] != 0.0)
    want = np.asarray(_xla(q, k, v, lo, hi), np.float32)
    assert np.all(want[1:] == 0.0)


@pytest.mark.parametrize("rows", [1, 2, 4])
def test_the_index_map_names_no_dead_tile(rows):
    """Step by step over a row tile's slot tiles: inside [first, last] the
    step's own tile, outside it the nearest live one — so what Pallas
    fetches (a block index that differs from the step before's) is each
    live tile once and no other; a row tile of empty windows stays at 0."""
    block_s, ns = 128, 10
    lo = np.asarray([0, 130, 700, 1279, 5, 5, 640, 900], np.int32)
    hi = np.asarray([1, 131, 1280, 1280, 5, 0, 1000, 1024], np.int32)
    first, last = (np.asarray(x) for x in kd.live_tiles(
        jnp.asarray(lo), jnp.asarray(hi), rows, block_s, ns))
    for ti in range(len(lo) // rows):
        at = [
            int(kd.tile_index(si, first[ti], last[ti], ns))
            for si in range(ns)]
        rows_lo = lo[ti * rows:(ti + 1) * rows]
        rows_hi = hi[ti * rows:(ti + 1) * rows]
        live = sorted({
            tile for a, z in zip(rows_lo, rows_hi) if z > a
            for tile in range(a // block_s, (z - 1) // block_s + 1)})
        if not live:
            assert first[ti] > last[ti] and set(at) == {0}
            continue
        assert (first[ti], last[ti]) == (live[0], live[-1])
        fetched = [at[0]] + [b for a, b in zip(at, at[1:]) if b != a]
        assert fetched == list(range(live[0], live[-1] + 1))
        computed = [si for si in range(ns) if first[ti] <= si <= last[ti]]
        assert computed == fetched


def test_three_layers_trace_the_kernel_once():
    """One `jit` entry point: a program whose three layers call the
    kernel — the layer a Python int, a traced scalar, a slot beside it —
    traces its body once (ROADMAP A2 (2))."""
    q, k, v = _operands(2, 256, 2, 2, 128, seed=3)
    lo = jnp.zeros((2,), jnp.int32)

    def program(q, k, v, slot):
        out = kd.kv_decode(q, k, v, 0, lo, slot + 1)
        out = out + kd.kv_decode(q + out, k, v, jnp.int32(1), lo, slot + 1)
        return out + kd.kv_decode(
            q + out, k, v, jnp.asarray(2) * 1, lo, slot + 1)

    before = kd.traced()
    jax.jit(program).lower(q, k, v, jnp.int32(100))
    assert kd.traced() - before == 1


# ---------------------------------------------------------------- the chooser

KV_ALONE = {
    "qwen2.5-math-1.5b", "olmoe-1b-7b-0125-l3", "sdar-30b-a3b-chat-l8-e16",
    "r1-distill-qwen-7b-l8",
}
CONFIGS = sorted(c["name"] for c in files.benchmark_json()["configs"])


def _published(name):
    return bench_run.model_config(files.load_json("configs", name + ".json"))


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the chooser sees on a TPU backend (nothing is run)."""
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)


@pytest.mark.parametrize("name", CONFIGS)
def test_the_chooser_takes_the_plans_whose_cache_is_kv_alone(name, on_a_tpu):
    """On one TPU device, at the published widths: the kernel for the dense
    models, the OLMoE layer and the block-diffusion model; the XLA form for
    every plan that keeps a state, a ring, a latent row, compressed keys or
    index keys — from the kinds' records, nothing traced."""
    cfg = _published(name)
    keeps = set()
    for branch in tfm.branches_of(cfg).values():
        keeps.update(branch.cache)
    assert tfm.kv_cache_alone(cfg) == (keeps == {"k", "v"})
    assert tfm.kv_kernel_form(cfg, None, 1280) == (name in KV_ALONE)
    # A bool forces the form of a k/v plan and of no other.
    assert tfm.kv_kernel_form(cfg, True, 1280) == (name in KV_ALONE)
    assert not tfm.kv_kernel_form(cfg, False, 1280)


@pytest.mark.parametrize("kind,name", sorted({
    **lowered_programs.CACHE_KINDS, "wk": "mellum2-12b-a2.5b-l4-e16",
}.items()))
def test_a_toy_plan_that_keeps_more_than_kv_is_refused(kind, name, on_a_tpu):
    cfg = lowered_programs.toy_config(name)
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, 2, 256))
    assert getattr(cache, kind) is not None
    assert not tfm.kv_cache_alone(cfg)
    assert not tfm.kv_kernel_form(cfg, None, 256)
    assert not tfm.kv_kernel_form(cfg, True, 256)


def test_off_a_tpu_the_kernel_is_nobodys_choice():
    cfg = _published("qwen2.5-math-1.5b")
    assert not tfm.kv_kernel_form(cfg, None, 1280)
    assert tfm.kv_kernel_form(cfg, True, 1280)  # the tests' force


def test_a_mesh_keeps_the_xla_form(on_a_tpu):
    """The four-chip cell's plan is k/v alone and its engine hands the
    MESH (`GeneratorEngine._row_kernel`): the kernel is one device's
    program, so the cell keeps `decode_attention`."""
    cfg = _published("r1-distill-qwen-7b-l8")
    mesh = make_mesh(ParallelConfig.from_str("m4"), jax.devices()[:4])
    assert tfm.kv_kernel_form(cfg, None, 512)
    assert not tfm.kv_kernel_form(cfg, mesh, 512)


@pytest.mark.parametrize("s_max,head_dim,takes", [
    (1280, 128, True), (896, 128, True), (96, 128, False), (1300, 128, False),
    (1280, 64, False), (1280, 256, True),
])
def test_the_chooser_refuses_what_the_kernel_cannot_cut(
        s_max, head_dim, takes, on_a_tpu):
    """A window that is no whole 128-slot tiles (a toy's 96 slots) and a
    head that is no whole 128-lane tiles keep the XLA form."""
    cfg = dataclasses.replace(
        lowered_programs.toy_config("qwen2.5-math-1.5b"), head_dim=head_dim)
    assert kd.fits(s_max, head_dim) == takes
    assert tfm.kv_kernel_form(cfg, None, s_max) == takes


# ------------------------------------------------- the steps, on either form


@pytest.mark.parametrize(
    "name", ["qwen2.5-math-1.5b", "olmoe-1b-7b-0125-l3",
             "sdar-30b-a3b-chat-l8-e16"])
def test_the_step_on_the_kernel_is_the_step_on_xla(name):
    """`decode_step` of a dense and an OLMoE toy and `block_step` of the
    block-diffusion toy (fp32, two layers, toy heads and window): logits
    and the cache the step leaves, kernel forced against XLA forced."""
    cfg = lowered_programs.toy_config(name)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    cache = tfm.init_kv_cache(cfg, 2, 96)
    cache = dataclasses.replace(cache, **{
        f: jax.random.normal(jax.random.PRNGKey(i), x.shape, x.dtype)
        for i, f in enumerate("kv") for x in [getattr(cache, f)]})
    tok, pos = jnp.asarray([3, 5]), jnp.asarray([40, 60])
    valid_from, slot = jnp.asarray([20, 0]), 60
    if cfg.block_length:
        tok = jnp.tile(tok[:, None], (1, cfg.block_length))
        pos = pos[:, None] + jnp.arange(cfg.block_length)

        def step(form):
            logits, cache_, _ = tfm.block_step(
                params, cfg, tok, pos, cache, slot, valid_from,
                row_kernel=form)
            # the mask token's logit is -inf on either form
            return jnp.where(jnp.isfinite(logits), logits, 0.0), cache_
    else:
        def step(form):
            return tfm.decode_step(
                params, cfg, tok, pos, cache, slot, valid_from,
                row_kernel=form)[:2]

    before = kd.traced()
    (got, got_cache), (want, want_cache) = step(True), step(False)
    assert kd.traced() > before  # the forced kernel was the one that ran
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # The second layer's token comes through the first layer's attention.
    np.testing.assert_allclose(got_cache.k, want_cache.k, atol=2e-5)
    np.testing.assert_allclose(got_cache.v, want_cache.v, atol=2e-5)


# ----------------------------------------------------- a roll-out's counters


def _engine(cfg, params, monkeypatch, form):
    from areal_tpu.engines.generator import GeneratorEngine

    monkeypatch.setattr(
        GeneratorEngine, "_row_kernel", property(lambda self: form))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    return GeneratorEngine(
        cfg, params, mesh, max_decode_batch=8, eos_token_id=cfg.vocab_size,
        donation_safe_swap=False)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 400, size=n).astype(np.int32) for n in lens]


def _tiles_by_hand(valid_from, ends, s_total, tile):
    live = sum(
        (end - 1) // tile - lo // tile + 1
        for lo in valid_from for end in ends if end > lo)
    return live / (len(valid_from) * len(ends) * -(-s_total // tile))


def test_a_rollouts_counters_against_a_count_by_hand(monkeypatch):
    """Token loop, the kernel forced: 300 new tokens past a bucket of 160
    slots, no EOS, so the loop makes 300 steps and step t attends
    [valid_from, sp + t + 1) of 512 allocated slots.  Then the same
    roll-out on the XLA form: the same tokens, `gen/kv_kernel` 0.0 and no
    share."""
    from areal_tpu.engines.packing import bucket_len

    cfg = lowered_programs.toy_config("qwen2.5-math-1.5b")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lens = [140, 20, 77]
    prompts = _prompts(lens)
    g = GenerationHyperparameters(n=1, max_new_tokens=300, greedy=True)
    sp = bucket_len(max(lens))
    s_total = bucket_len(sp + 300)
    out = {}
    for form in (True, False):
        eng = _engine(cfg, params, monkeypatch, form)
        out[form] = eng.static_rollout(prompts, g, jax.random.PRNGKey(1))
        stats = dict(eng.last_pool_stats)
        if not form:
            assert stats["gen/kv_kernel"] == 0.0
            assert "gen/kv_live_tile_share" not in stats
            continue
        assert stats["gen/kv_kernel"] == 1.0
        assert s_total // kd.BLOCK_S >= 4  # more tiles than one
        want = _tiles_by_hand(
            [sp - n for n in lens], [sp + t + 1 for t in range(300)],
            s_total, kd.BLOCK_S)
        assert 0.3 < want < 0.9
        assert stats["gen/kv_live_tile_share"] == pytest.approx(want)
        # A second call of the same generate call adds to both sums.
        eng.static_rollout(prompts, g, jax.random.PRNGKey(2))
        assert eng.last_pool_stats["gen/kv_live_tile_share"] == (
            pytest.approx(want))
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_allclose(out[True][1], out[False][1], atol=1e-4)


def test_a_block_rollouts_counters_against_a_count_by_hand(monkeypatch):
    """The block loop: the first block's log-prob forward, then a block's
    denoising forwards and its commit, each over [valid_from, the block's
    end)."""
    from areal_tpu.engines import block_diffusion as bd
    from areal_tpu.engines.packing import bucket_len

    cfg = lowered_programs.toy_config("sdar-30b-a3b-chat-l8-e16")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    lens = [30, 142, 77]
    prompts = _prompts(lens, seed=1)
    g = GenerationHyperparameters(n=1, max_new_tokens=200)
    eng = _engine(cfg, params, monkeypatch, True)
    eng.static_rollout(prompts, g, jax.random.PRNGKey(1))
    stats = dict(eng.last_pool_stats)
    blk, sp = cfg.block_length, bucket_len(max(lens))
    nb = bd.n_blocks(cfg, 200, [n % blk for n in lens])
    assert stats["bd/blocks"] == nb  # no EOS: every block ran
    s_total = -(-(sp + nb * blk) // 128) * 128
    ends = [sp + blk] + [
        sp + (k + 1) * blk for k in range(nb)
        for _ in range(cfg.denoising_forwards + 1)]
    want = _tiles_by_hand(
        [sp - n // blk * blk for n in lens], ends, s_total, kd.BLOCK_S)
    assert stats["gen/kv_kernel"] == 1.0
    assert stats["gen/kv_live_tile_share"] == pytest.approx(want)

"""The sampler's two-level inverse-CDF draw (`ops/sampling._inverse_cdf_draw`):
the map u -> token against the float64 CDF, support under warpers and
residual masks, a group that underflows, the marginal distribution, the
returned log-probability, and the draw under a vocabulary-sharded mesh."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.ops.sampling import (
    NEG_INF,
    _inverse_cdf_draw,
    apply_top_k,
    apply_top_p,
    sample_token,
)

BELOW_ONE = float(np.nextafter(np.float32(1.0), np.float32(0.0)))


def sweep(warped, grid):
    """tokens [N, B]: every row of `warped` drawn at every u of `grid`."""
    b = warped.shape[0]

    @jax.jit
    def run(w, us):
        return jax.lax.map(
            lambda u: _inverse_cdf_draw(w, jnp.full((b,), u, jnp.float32))[0],
            us,
        )

    return np.asarray(run(jnp.asarray(warped), jnp.asarray(grid, jnp.float32)))


def grid_of(n):
    return np.concatenate(
        [[0.0], np.linspace(0.0, 1.0, n, endpoint=False)[1:], [BELOW_ONE]]
    ).astype(np.float32)


def probs64(warped):
    w = np.asarray(warped, np.float64)
    p = np.exp(w - w.max(axis=-1, keepdims=True))
    p[np.asarray(warped) < NEG_INF / 2] = 0.0
    return p / p.sum(axis=-1, keepdims=True)


def masked_logits(rng, b, v):
    """Random logits with a quarter of the tokens masked as a warper would,
    the first and last token of a row and (where there is more than one) a
    whole group of 128 among them."""
    x = (2.0 * rng.standard_normal((b, v))).astype(np.float32)
    x[rng.random((b, v)) < 0.25] = NEG_INF
    x[:, -1] = NEG_INF
    x[0, 0] = NEG_INF
    if v > 256:
        x[:, 128:256] = NEG_INF
    x[:, 1] = 0.5  # a row always keeps some mass
    return x


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("v", [8, 32, 300, 1187 * 128])
def test_u_to_token_follows_the_cdf(v, b):
    rng = np.random.default_rng(v + b)
    x = masked_logits(rng, b, v)
    n = 512 if v > 1000 else 20000
    grid = grid_of(n)
    toks = sweep(x, grid)  # [N, B]
    p = probs64(x)
    cdf = np.cumsum(p, axis=-1)
    rows = np.arange(b)[None, :]
    # In the vocabulary, of positive mass: no masked token, no padding.
    assert toks.min() >= 0 and toks.max() < v
    assert (p[rows, toks] > 0).all()
    # Monotone in the enumeration (the vocabulary's own order).
    assert (np.diff(toks, axis=0) >= 0).all()
    # u lies in its token's interval of the float64 CDF, to fp32 rounding.
    u = np.minimum(grid.astype(np.float64), 1.0 - 1e-6)[:, None]
    lo = np.where(toks > 0, cdf[rows, np.maximum(toks - 1, 0)], 0.0)
    hi = cdf[rows, toks]
    assert (u >= lo - 2e-5).all() and (u <= hi + 2e-5).all()
    if v <= 1000:
        # and every token's interval is as long as its probability
        for r in range(b):
            share = np.bincount(toks[:, r], minlength=v) / len(grid)
            np.testing.assert_allclose(share, p[r], atol=2.0 / n + 2e-5)


@pytest.mark.parametrize("v", [300, 1000])
@pytest.mark.parametrize("kind", ["top_k_1", "top_p", "residual"])
def test_only_tokens_in_support_come_back(kind, v):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, v)) * 3.0, jnp.float32)
    if kind == "top_k_1":
        warped = apply_top_k(x, 1)
    elif kind == "top_p":
        warped = apply_top_p(x, 0.8)
    else:  # the rejected draft's mass removed, as `spec_accept` does
        onehot = jnp.arange(v)[None, :] == jnp.argmax(x, axis=-1)[:, None]
        warped = jnp.where(onehot, NEG_INF, x)
    toks = sweep(warped, grid_of(4000))
    support = np.asarray(warped) > NEG_INF / 2
    assert support.sum() < support.size
    assert support[np.arange(4)[None, :], toks].all()
    if kind == "top_k_1":
        assert (toks == np.asarray(jnp.argmax(x, axis=-1))[None, :]).all()
    else:  # every token in support is reached on a grid this fine
        p = probs64(warped)
        hit = np.zeros_like(support)
        hit[np.arange(4)[None, :], toks] = True
        assert hit[p > 1e-3].all()


@pytest.mark.parametrize("light", [-80.0, -200.0])
@pytest.mark.parametrize("heavy_group", [0, 1, 2])
def test_a_group_whose_mass_underflows(heavy_group, light):
    """Three groups, one holds everything: the others' masses are absorbed
    by the scan (exp(-80)) or are exactly zero (exp(-200))."""
    x = np.full((2, 384), light, np.float32)
    x[:, 128 * heavy_group:128 * (heavy_group + 1)] = 0.0
    x[1, 128 * heavy_group + 5] = NEG_INF
    grid = grid_of(1280)
    toks = sweep(x, grid)
    p = probs64(x)
    assert (p[np.arange(2)[None, :], toks] > 0).all()
    # u = 0 may land on the first token of any positive mass; every other
    # u is inside the heavy group, a 128th of the line a token
    body = toks[2:]
    assert (body // 128 == heavy_group).all()
    want = np.floor(grid[2:, None] * 128).astype(np.int64)
    assert (np.abs(body[:, 0] % 128 - want[:, 0]) <= 1).all()
    assert (body[:, 1] % 128 != 5).all()


def test_marginal_distribution_at_300():
    v, n = 300, 40000
    rng = np.random.default_rng(11)
    row = (1.5 * rng.standard_normal(v)).astype(np.float32)
    logits = jnp.asarray(np.broadcast_to(row, (n, v)))
    tok, _ = jax.jit(sample_token)(logits, jax.random.PRNGKey(12))
    counts = np.bincount(np.asarray(tok), minlength=v) / n
    p = probs64(row[None])[0]
    sigma = np.sqrt(p * (1 - p) / n)
    assert (np.abs(counts - p) <= 4.5 * sigma + 1e-4).all()


@pytest.mark.parametrize("v", [300, 5000])
@pytest.mark.parametrize(
    "temperature,top_k,top_p",
    [(1.0, 0, 1.0), (0.7, 0, 1.0), (1.0, 5, 1.0), (1.0, 0, 0.9),
     (1.3, 20, 0.8)],
    ids=["plain", "temperature", "top_k", "top_p", "all_three"],
)
def test_the_logprob_is_the_unwarped_scaled_log_softmax(
    temperature, top_k, top_p, v
):
    rng = np.random.default_rng(v)
    logits = jnp.asarray(4.0 * rng.standard_normal((16, v)), jnp.float32)
    tok, logp = jax.jit(
        lambda l, k: sample_token(
            l, k, temperature=temperature, top_k=top_k, top_p=top_p
        )
    )(logits, jax.random.PRNGKey(v))
    want = jax.nn.log_softmax(logits / temperature, axis=-1)
    want = np.asarray(jnp.take_along_axis(want, tok[:, None], axis=-1))[:, 0]
    np.testing.assert_allclose(np.asarray(logp), want, atol=1e-5)
    warped = np.asarray(
        apply_top_p(apply_top_k(logits / temperature, top_k), top_p)
    )
    assert (warped[np.arange(16), np.asarray(tok)] > NEG_INF / 2).all()


COLLECTIVE = re.compile(
    r"= (.+?) (all-gather|all-reduce|all-to-all|collective-permute)"
    r"(-start)?\((.*)$"
)


@pytest.mark.parametrize("v", [4 * 3 * 128, 4 * 297 * 128])
def test_a_vocabulary_sharded_draw_stays_on_its_shard(v):
    """Logits `P(None, "model")` over 4 devices: the same tokens as the
    unsharded call for the same key, and no collective over a block with
    V or V / 4 columns a row — only group masses and one group cross."""
    b = 8
    devices = np.asarray(jax.devices()[:4]).reshape(1, 4)
    mesh = Mesh(devices, ("data", "model"))
    rng = np.random.default_rng(3)
    logits = jnp.asarray(3.0 * rng.standard_normal((b, v)), jnp.float32)
    key = jax.random.PRNGKey(5)
    want_tok, want_logp = jax.jit(sample_token)(logits, key)
    sharded = jax.device_put(logits, NamedSharding(mesh, P(None, "model")))
    fn = jax.jit(sample_token)
    tok, logp = fn(sharded, key)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(want_tok))
    np.testing.assert_allclose(
        np.asarray(logp), np.asarray(want_logp), atol=1e-5
    )
    hlo = fn.lower(sharded, key).compile().as_text()
    wide = re.compile(r"\[%d,(%d|%d)\]" % (b, v, v // 4))
    found = [m for m in map(COLLECTIVE.search, hlo.splitlines()) if m]
    assert found, "the sharded program has collectives at all"
    for m in found:
        assert not wide.search(m.group(1) + m.group(4)), m.group(0)[:200]

"""Token sampling: temperature / top-k / top-p warpers + categorical draw.

Capability parity: realhf/impl/model/nn/real_llm_generate.py `genstep`
(top-k/top-p logits warpers, unfinished-sequence masking) — implemented as
static-shape jnp ops (sort/cumsum) so the whole decode loop jits.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e10


def apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits per row; mask the rest.  k<=0 disables."""
    if k <= 0:
        return logits
    k = min(k, logits.shape[-1])
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def apply_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest prefix of sorted probs with
    cumulative mass >= p.  p>=1 disables."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative mass (exclusive) is < p.
    keep_sorted = (cum - probs) < p
    # Threshold logit = smallest kept logit.
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < thresh, NEG_INF, logits)


@jax.named_scope("head_logprob")
def sample_token(
    logits: jax.Array,  # [B, V] fp32
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    greedy: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (token [B] int32, logprob [B] fp32 of the chosen token under
    the WARPED distribution's log_softmax of unwarped logits).

    Note: the returned logprob is under the *unwarped* temperature-scaled
    distribution — the convention PPO needs for importance ratios (the
    behavior policy's density), matching the reference which recomputes
    logprobs from raw logits.
    """
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if greedy:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        warped = apply_top_p(apply_top_k(scaled, top_k), top_p)
        # Inverse-CDF draw: ONE uniform per row.  The gumbel-max trick
        # (jax.random.categorical) generates B*V threefry values —
        # ~3.4 ms/step at a 152k vocab on v5e, the single largest
        # decode-step cost outside the weight streaming.
        u = jax.random.uniform(key, (logits.shape[0],), jnp.float32)
        tok, logp = _inverse_cdf_draw(warped, u)
        if top_k <= 0 and top_p >= 1.0:
            # No warper: `warped` IS `scaled`, and the draw's own max and
            # total are the chosen token's logsumexp.
            return tok, logp
    # Chosen-token logprob via logsumexp (no full-vocab log_softmax write).
    lse = jax.nn.logsumexp(scaled, axis=-1)
    chosen = jnp.take_along_axis(scaled, tok[:, None], axis=-1)[:, 0]
    return tok, chosen - lse


# Tokens to a group of the two-level draw: a vector register's lanes.
_GROUP = 128


def _first_above(cum: jax.Array, mass: jax.Array, r: jax.Array) -> jax.Array:
    """Per row the first entry of positive mass whose running sum `cum`
    exceeds `r`; the last entry of positive mass where none does (0 for a
    row of no mass at all).  Never an entry of zero mass, whatever order
    the scan behind `cum` rounded in."""
    n = cum.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    live = mass > 0
    first = jnp.min(jnp.where(live & (cum > r[:, None]), idx, n), axis=-1)
    last = jnp.max(jnp.where(live, idx, 0), axis=-1)
    return jnp.minimum(first, last)


@jax.named_scope("sample_draw")
def _inverse_cdf_draw(
    warped: jax.Array, u: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """One inverse-CDF draw per row from warped logits [B, V], u in [0,1),
    in two levels; returns (token [B] int32, its log-probability [B] under
    `warped`'s own softmax).

    The vocabulary is enumerated in its own order and cut into contiguous
    groups of `_GROUP` tokens (V padded up with columns of no mass).  With
    p = exp(warped - max) in fp32: (1) every group's mass, one reducing
    pass; (2) a scan over the V / 128 group masses picks group j with
    C[j-1] <= r < C[j], r = min(u * total, total * (1 - 1e-6)), total =
    C[-1]; (3) a scan over group j's own 128 masses picks the token with
    r - C[j-1].  Only ONE position of a cumulative sum over V was ever
    used; nothing here scans V.

    The guarantee, at both levels: a token of zero mass (warper-masked,
    an `NEG_INF` residual, a padding column) is never returned.  `r` is
    kept strictly below each scan's OWN last value — u * total can round
    up to total in fp32, and a group's reduced mass and the last value of
    its scan round apart — and the pick (`_first_above`) only looks at
    entries of positive mass, so neither that mismatch nor a scan that
    rounds non-monotonically can select past the last token in support.

    Group j's logits are taken as a sum over the groups masked to j (one
    non-zero term: exact), not gathered: under a vocabulary-sharded
    `warped` every pass is local to a shard and only [B, V / 128] masses
    and one [B, 128] group cross chips."""
    b, v = warped.shape
    m = jnp.max(warped, axis=-1)
    pad = -v % _GROUP
    if pad:
        warped = jnp.pad(
            warped, ((0, 0), (0, pad)), constant_values=-jnp.inf
        )
    g = (v + pad) // _GROUP
    x = warped.reshape(b, g, _GROUP)
    mass = jnp.sum(jnp.exp(x - m[:, None, None]), axis=-1)  # [B, G]
    cum = jnp.cumsum(mass, axis=-1)
    total = cum[:, -1]
    r = jnp.minimum(u * total, total * (1.0 - 1e-6))
    j = _first_above(cum, mass, r)
    groups = jnp.arange(g, dtype=jnp.int32)
    below = jnp.sum(
        jnp.where(groups[None, :] == j[:, None] - 1, cum, 0.0), axis=-1
    )
    x_j = jnp.sum(
        jnp.where(groups[None, :, None] == j[:, None, None], x, 0.0), axis=1
    )  # [B, 128]
    p_j = jnp.exp(x_j - m[:, None])
    cum_j = jnp.cumsum(p_j, axis=-1)
    r_j = jnp.clip(r - below, 0.0, cum_j[:, -1] * (1.0 - 1e-6))
    k = _first_above(cum_j, p_j, r_j)
    chosen = jnp.take_along_axis(x_j, k[:, None], axis=-1)[:, 0]
    return j * _GROUP + k, chosen - (m + jnp.log(total))


def spec_accept(
    logits: jax.Array,  # [B, K+1, V] fp32 — model dists after each draft
    drafts: jax.Array,  # [B, K] int32 — proposed tokens
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    greedy: bool = False,
    n_valid: Optional[jax.Array] = None,  # [B] int32 — live logit positions
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact speculative verification of K deterministic drafts.

    logits[:, j] is the model's next-token distribution AFTER consuming
    drafts[:, :j] (logits[:, K] is the bonus position).  Returns
    (emitted [B, K+1], logps [B, K+1], n_emitted [B]) where per row the
    first n_emitted entries are valid: accepted drafts followed by one
    closing token (the rejection resample, or the bonus draw when all K
    drafts were accepted).  The emitted sequence is distributed EXACTLY as
    K+1 sequential draws from the warped distribution (standard
    speculative rejection sampling with a point-mass proposal: accept
    draft d w.p. p(d); on reject, resample from p with d's mass removed).
    Logps follow `sample_token`'s convention: the unwarped
    temperature-scaled distribution's log-density of the emitted token.

    `n_valid` makes the verification RAGGED: row b only forwarded its
    first n_valid[b] positions (pending + n_valid-1 drafts), so logits
    past that are garbage — drafts at j >= n_valid-1 are treated as
    rejected, which keeps the closing draw at a position < n_valid.
    Truncating speculation early is always distribution-exact (it is
    the K' = n_valid-1 instance of the same scheme); the serving chunk
    uses this when its lane budget grants a row fewer than K+1 query
    lanes.  Rows with n_valid == 0 return garbage the caller masks.
    """
    b, k1, v = logits.shape
    k = k1 - 1
    scaled = logits / jnp.maximum(temperature, 1e-6)
    live_draft = None
    if n_valid is not None and k > 0:
        live_draft = (
            jnp.arange(k)[None, :] < (n_valid - 1)[:, None]
        )  # [B, K]
    if greedy:
        argm = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
        acc = drafts == argm[:, :k]  # [B, K]
        if live_draft is not None:
            acc = acc & live_draft
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        # Closing token = argmax at the first rejected position (or bonus).
        close = jnp.take_along_axis(argm, n_acc[:, None], axis=1)[:, 0]
        emitted = jnp.concatenate([drafts, close[:, None]], axis=1)
        emitted = emitted.at[jnp.arange(b), n_acc].set(close)
    else:
        warped = apply_top_p(apply_top_k(scaled, top_k), top_p)
        logZ = jax.nn.logsumexp(warped, axis=-1)  # [B, K+1]
        d_logit = jnp.take_along_axis(
            warped[:, :k], drafts[:, :, None], axis=-1
        )[..., 0]
        p_draft = jnp.exp(d_logit - logZ[:, :k])  # [B, K] accept probs
        key, k_acc, k_res = jax.random.split(key, 3)
        u_acc = jax.random.uniform(k_acc, (b, k))
        acc = u_acc < p_draft
        if live_draft is not None:
            acc = acc & live_draft
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)
        # Closing draw at position n_acc: from the residual (draft masked
        # out) on rejection, from the untouched dist on the bonus position.
        close_logits = jnp.take_along_axis(
            warped, n_acc[:, None, None], axis=1
        )[:, 0]  # [B, V]
        rejected_draft = jnp.take_along_axis(
            drafts, jnp.minimum(n_acc, k - 1)[:, None], axis=1
        )[:, 0] if k > 0 else jnp.zeros((b,), jnp.int32)
        mask_draft = (n_acc < k)  # rejection (not bonus)
        onehot = (
            jnp.arange(v)[None, :] == rejected_draft[:, None]
        ) & mask_draft[:, None]
        close_logits = jnp.where(onehot, NEG_INF, close_logits)
        u_res = jax.random.uniform(k_res, (b,))
        close, _ = _inverse_cdf_draw(close_logits, u_res)
        emitted = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1
        )
        emitted = emitted.at[jnp.arange(b), n_acc].set(close)
    # Unwarped temp-scaled logprob of every emitted token at its position.
    lse = jax.nn.logsumexp(scaled, axis=-1)  # [B, K+1]
    chosen = jnp.take_along_axis(scaled, emitted[:, :, None], axis=-1)[..., 0]
    logps = chosen - lse
    return emitted, logps, n_acc + 1


# --------------------------------------------------------------------------
# Generation by diffusion over blocks: a draw with its confidence, and the
# places a denoising step reveals
# --------------------------------------------------------------------------


@jax.named_scope("head_logprob")
def draw_with_confidence(
    logits: jax.Array,  # [N, V] fp32
    u: jax.Array,  # [N] uniforms in [0, 1)
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    greedy: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """`sample_token`'s draw from its warped distribution, with that
    distribution's probability of the token drawn (the CONFIDENCE an
    unmasking rule ranks places by) -> (token [N] int32, confidence [N])."""
    scaled = logits / jnp.maximum(temperature, 1e-6)
    warped = apply_top_p(apply_top_k(scaled, top_k), top_p)
    if greedy:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        chosen = jnp.take_along_axis(warped, tok[:, None], axis=-1)[:, 0]
        return tok, jnp.exp(chosen - jax.nn.logsumexp(warped, axis=-1))
    tok, logp = _inverse_cdf_draw(warped, u)
    return tok, jnp.exp(logp)


def reveal_by_confidence(
    conf: jax.Array,  # [R, B] fp32 — a draw's confidence a place
    masked: jax.Array,  # [R, B] bool — the places still masked
    n: jax.Array,  # scalar int — places to reveal this step
) -> jax.Array:
    """The places of a block a denoising step reveals, [R, B] bool: the `n`
    masked places of largest confidence (all of them where fewer are
    masked), ties to the lower position."""
    c_i, c_j = conf[:, :, None], conf[:, None, :]
    idx = jnp.arange(conf.shape[1])
    ahead = (c_j > c_i) | ((c_j == c_i) & (idx[None, :] < idx[:, None])[None])
    rank = jnp.sum(ahead & masked[:, None, :], axis=-1)  # among the masked
    return masked & (rank < n)

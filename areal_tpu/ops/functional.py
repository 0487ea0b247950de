"""Loss/log-prob numerics over dense packed rows.

Capability parity: realhf/impl/model/utils/functional.py
(`gather_packed_shifted_log_probs`, `masked_normalization`) adapted to the
[B, S] packed-row layout (segment_ids delimit sequences, 0 = pad).
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from areal_tpu.parallel import sharding


def shifted_label_mask(segment_ids: jax.Array) -> jax.Array:
    """True at position t when (t, t+1) belong to the same segment — i.e.
    position t predicts a real next token.  [B, S] bool."""
    nxt = jnp.pad(
        segment_ids[:, 1:], ((0, 0), (0, 1)), constant_values=0
    )
    return (segment_ids > 0) & (segment_ids == nxt)


def next_token_logprobs(
    logits: jax.Array, tokens: jax.Array, segment_ids: jax.Array
) -> jax.Array:
    """log p(tokens[t+1] | prefix) at each position t (0 where invalid).

    [B, S] fp32.  The last position of every segment (and padding) is 0.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=0)
    gathered = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.where(shifted_label_mask(segment_ids), gathered, 0.0)


@jax.named_scope("head_logprob")
def fused_next_token_logprobs(
    x: jax.Array,  # [B, S, D] final hidden states (compute dtype)
    head: jax.Array,  # [D, V] LM head (embed.T when tied)
    tokens: jax.Array,  # [B, S] int32
    segment_ids: jax.Array,  # [B, S] int32, 0 = pad
    chunk_size: int = 512,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """log p(tokens[t+1] | prefix) at each position t, WITHOUT materializing
    [B, S, V] logits: the head matmul + logsumexp run per position-chunk
    inside a checkpointed scan, so peak memory is one [chunk, V] block and
    the backward recomputes it.  At a 152k vocab this is the difference
    between ~150 MB and ~10 GB of fp32 logits per micro-batch — the
    TPU-native counterpart of the reference's fused vocab-parallel
    cross-entropy (realhf model_parallel/modules.py:1060-1180).

    Traced under a `mesh` whose parameter-sharding axes (model x fsdp)
    divide V, the head is vocabulary-parallel: the [D, V] weight is re-laid
    once to V split over those axes with D whole, and a chunk's logits stay
    [chunk, V / (m f)] a chip, so the partitioner reduces only [chunk]
    statistics (max, sum-exp, target logit) forward and one [chunk, D] block
    of dx backward.  Left to the stored layout (D over fsdp) it all-reduces
    the fp32 [chunk, V] logits themselves, every chunk, forward and
    recomputed.  With no mesh, a product of 1 or an indivisible V nothing
    is constrained.

    [B, S] fp32; 0 at the last position of every segment and padding.
    """
    head, vocab_parallel = _head_layout(head, mesh)
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=0)
    lp = _chunked_label_logprobs(x, head, labels, chunk_size, vocab_parallel)
    return jnp.where(shifted_label_mask(segment_ids), lp, 0.0)


def _head_layout(head: jax.Array, mesh: Optional[Mesh]):
    """(the head as a fused log-prob head reads it, its vocabulary-parallel
    sharding or None): `fused_next_token_logprobs`' rule."""
    if sharding.head_vocab_shards(mesh, head.shape[1]) <= 1:
        return head, None
    # The stored layout first: nothing moves forward, and its transpose
    # brings dhead back inside the gradient program.  Without it the
    # gradient leaves V-sharded and the optimizer step re-lays the weight
    # and both moments there and back (6 all-to-alls, not 1).
    head = jax.lax.with_sharding_constraint(
        head, sharding.named(mesh, sharding.HEAD_STORED)
    )
    vocab_parallel = sharding.named(mesh, sharding.HEAD_VOCAB_PARALLEL)
    return jax.lax.with_sharding_constraint(head, vocab_parallel), vocab_parallel


@jax.named_scope("head_logprob")
def fused_label_logprobs(
    x: jax.Array,  # [B, K, D] final hidden states of the rows to score
    head: jax.Array,  # [D, V]
    labels: jax.Array,  # [B, K] int32 — the token AT each row's place
    label_mask: jax.Array,  # [B, K] — where a label is wanted
    chunk_size: int = 512,
    mesh: Optional[Mesh] = None,
    exclude: Optional[int] = None,
) -> jax.Array:
    """log softmax(x head)[label] a row, IN PLACE — no shift: the labels
    are explicit, as a model that predicts the token at a position (and
    not the next one) has them — by `fused_next_token_logprobs`' chunked,
    checkpointed scan.  `exclude`: a vocabulary id left out of the softmax
    (its logit -inf: a mask token no position may hold).  [B, K] fp32, 0
    where `label_mask` is not set."""
    head, vocab_parallel = _head_layout(head, mesh)
    lp = _chunked_label_logprobs(
        x, head, labels, chunk_size, vocab_parallel, exclude)
    return jnp.where(label_mask > 0, lp, 0.0)


def _chunked_label_logprobs(
    x, head, labels, chunk_size, vocab_parallel, exclude=None
):
    """[B, S] fp32 log softmax(x head)[labels], a chunk of positions at a
    time inside a checkpointed scan."""
    b, s, d = x.shape
    t = b * s
    c = min(chunk_size, t)
    pad = (-t) % c
    xf = x.reshape(t, d)
    lf = labels.reshape(t)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
    n_chunks = (t + pad) // c
    xc = xf.reshape(n_chunks, c, d)
    lc = lf.reshape(n_chunks, c)

    def body(carry, inp):
        xi, li = inp
        logits = jnp.einsum(
            "cd,dv->cv", xi, head, preferred_element_type=jnp.float32
        )
        if vocab_parallel is not None:
            logits = jax.lax.with_sharding_constraint(logits, vocab_parallel)
        if exclude is not None:
            logits = jnp.where(
                jnp.arange(logits.shape[-1]) == exclude, -jnp.inf, logits)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return carry, tgt - lse

    body = jax.checkpoint(body)
    _, lp = jax.lax.scan(body, None, (xc, lc))
    return lp.reshape(-1)[:t].reshape(b, s)


def masked_normalization(
    x: jax.Array,
    mask: jax.Array,
    eps: float = 1e-5,
    high_precision: bool = True,
) -> jax.Array:
    """Whiten x over masked entries (global mean/std), zeros elsewhere.
    Reference: functional.py masked_normalization (used for advantages)."""
    dtype = jnp.float64 if high_precision and jax.config.jax_enable_x64 else jnp.float32
    xf = x.astype(dtype)
    m = mask.astype(dtype)
    n = jnp.maximum(m.sum(), 1.0)
    mean = (xf * m).sum() / n
    var = (jnp.square(xf - mean) * m).sum() / n
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return jnp.where(mask, out, 0.0).astype(jnp.float32)


def sft_loss(logp: jax.Array, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
    """Sum of next-token NLL over answer tokens (prompt/pad excluded).

    `logp` is the engine's per-token next-token logprobs [B, S] (engines
    compute it fused — see fused_next_token_logprobs).  batch needs:
    segment_ids, prompt_mask (True on prompt tokens).  Positions whose LABEL
    (t+1) is a prompt token are excluded too.  Returns (nll_sum, stats) —
    pair with loss_weight_fn = n_label_tokens.
    """
    seg = batch["segment_ids"]
    label_is_prompt = jnp.pad(
        batch["prompt_mask"][:, 1:], ((0, 0), (0, 1)), constant_values=True
    )
    mask = shifted_label_mask(seg) & (~label_is_prompt)
    nll = -(logp * mask).sum()
    n = jnp.maximum(mask.sum(), 1)
    return nll, {
        "nll_sum": nll,
        "n_tokens": n.astype(jnp.float32),
    }


def sft_label_count(arrays: Dict) -> float:
    """Host-side loss_weight_fn matching sft_loss's mask."""
    import numpy as np

    seg = arrays["segment_ids"]
    nxt = np.pad(seg[:, 1:], ((0, 0), (0, 1)), constant_values=0)
    shift_ok = (seg > 0) & (seg == nxt)
    label_is_prompt = np.pad(
        arrays["prompt_mask"][:, 1:], ((0, 0), (0, 1)), constant_values=True
    )
    # Host-side by construction: inputs are numpy (loss_weight_fn runs on
    # the data path before device placement), so this float() is one cheap
    # host reduction, not a device sync.
    return float((shift_ok & ~label_is_prompt).sum())

"""Generalized Advantage Estimation over packed sequences.

Capability parity: csrc/cugae/gae.cu `gae_1d_nolp_misalign` (per-sequence
backward scan over packed 1D rewards/values with cu_seqlens) and the Python
fallback `pygae1d_nolp_misalign` (realhf/impl/model/utils/
ppo_functional.py:271).  TPU-native formulation: the backward linear
recurrence  adv[t] = delta[t] + γλ·adv[t+1]  is a `jax.lax.associative_scan`
over the packed buffer with the carry coefficient zeroed at sequence
boundaries — O(log T) depth, fully on-device, no kernel needed (the scan
lowers to an efficient XLA program; a Pallas variant would only matter if
this ever showed up in profiles, which it doesn't next to the matmuls).
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=())
@jax.named_scope("ppo/gae")
def gae_packed(
    rewards: jax.Array,  # [T] fp32 per-token rewards (terminal included)
    values: jax.Array,  # [T] fp32 V(s_t), 0 on padding
    segment_ids: jax.Array,  # [T] int32, 0 = pad; sequences contiguous
    bootstrap: jax.Array,  # [T] fp32, V(s_{T}) placed at each seq's LAST pos
    gamma: float | jax.Array,
    lam: float | jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (advantages [T], returns [T]); zeros on padding.

    delta[t] = r[t] + γ·V[t+1] − V[t], where V beyond a sequence's last
    position is `bootstrap` at that position (0 for terminated episodes,
    V_last for truncated ones — caller decides, matching the reference's
    seq_no_eos_mask convention).
    """
    seg = segment_ids
    same_next = jnp.pad(
        seg[1:] == seg[:-1], (0, 1), constant_values=False
    ) & (seg > 0)
    v_next = jnp.where(
        same_next, jnp.pad(values[1:], (0, 1)), bootstrap
    )
    delta = rewards + gamma * v_next - values
    coef = jnp.where(same_next, gamma * lam, 0.0)

    # adv[t] = delta[t] + coef[t] * adv[t+1]  — reversed linear recurrence.
    a = coef[::-1]
    b = delta[::-1]

    def op(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    _, y = jax.lax.associative_scan(op, (a, b))
    adv = y[::-1]
    valid = seg > 0
    adv = jnp.where(valid, adv, 0.0)
    returns = jnp.where(valid, adv + values, 0.0)
    return adv, returns


def pygae_packed(
    rewards: np.ndarray,
    values: np.ndarray,
    seqlens,
    bootstrap_per_seq: np.ndarray,
    gamma: float,
    lam: float,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-numpy oracle (mirrors pygae1d_nolp_misalign) for parity tests."""
    adv = np.zeros_like(rewards, dtype=np.float64)
    ret = np.zeros_like(rewards, dtype=np.float64)
    off = 0
    for si, L in enumerate(seqlens):
        run = 0.0
        for t in reversed(range(L)):
            v_next = (
                bootstrap_per_seq[si] if t == L - 1 else values[off + t + 1]
            )
            delta = rewards[off + t] + gamma * v_next - values[off + t]
            run = delta + gamma * lam * run
            adv[off + t] = run
            ret[off + t] = run + values[off + t]
        off += L
    return adv.astype(np.float32), ret.astype(np.float32)

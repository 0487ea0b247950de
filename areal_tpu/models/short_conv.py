"""The gated short convolution: the mixer of most layers of an lfm2_moe
model (eighteen of these to six softmax-attention layers).

With h the normed layer input and (B, C, u) the thirds of ONE input
projection, in that order:

    g_t = B_t * u_t                                  (the gated input)
    c_t = sum_j taps[j] * g_{t - (K-1) + j}          (depthwise, causal, K = 3)
    y_t = out_proj(C_t * c_t)

No activation, no bias, no state: what a decode step needs of the past is
the row's last K - 1 gated inputs, `KVCache.conv` [layers, B, K-1, D] in
the compute type (g is a product of two slices of in_proj's output) with
`KVCache.state` None.  The conv's sum and the C gate are taken in fp32.

Packed rows: the conv restarts at every segment start
(`linear_attention.causal_conv` counts an input only inside t's own
segment), and prefill leaves each row's last K - 1 VALID gated inputs
whichever side its pads lie on (`linear_attention.conv_tail_at`).

Parameters (leaves of `params["blocks"]`, stacked [n_sconv_layers, ...]):
    sc_in    [D, 3 D]   B | C | u
    sc_conv  [K, D]     depthwise taps, oldest first
    sc_out   [D, D]
"""

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.branches import (
    Branch,
    HybridLayoutError,
    Refusal,
    nbytes,
    segment_restarts,
)
from areal_tpu.models.config import ModelConfig
from areal_tpu.models.linear_attention import (
    conv_act,
    conv_kernel_form,
    conv_tail_at,
)

Params = Dict[str, jax.Array]

SCONV_LEAVES = ("sc_in", "sc_conv", "sc_out")


def init_sconv(cfg: ModelConfig, key: jax.Array, n: int, dense) -> Params:
    """`n` layers' leaves; `dense(key, shape, fan_in)` is the caller's
    matrix init."""
    D, K = cfg.hidden_dim, cfg.sconv_kernel
    ks = jax.random.split(key, 3)
    return {
        "sc_in": dense(ks[0], (n, D, 3 * D), D),
        "sc_conv": dense(ks[1], (n, K, D), K),
        "sc_out": dense(ks[2], (n, D, D), D),
    }


def _gated_input(x: jax.Array, blk: Params) -> Tuple[jax.Array, jax.Array]:
    """in_proj of x [..., D] -> (g = B * u, C), each [..., D]."""
    with jax.named_scope("in_proj"):
        b, c, u = jnp.split(x @ blk["sc_in"], 3, axis=-1)
        return b * u, c


def _out(c_gate: jax.Array, conv: jax.Array, blk: Params) -> jax.Array:
    with jax.named_scope("out_proj"):
        y = c_gate.astype(jnp.float32) * conv
        return y.astype(c_gate.dtype) @ blk["sc_out"]


@jax.named_scope("layer/sconv")
def sconv_forward(
    h: jax.Array,  # [B, S, D] normed layer input
    blk: Params,
    cfg: ModelConfig,
    segment_ids: jax.Array,
    with_state: bool = False,
    kernel=None,  # None | bool | Mesh: `flash_attention.row_kernel_form`
):
    """-> y [B, S, D]; `with_state` (prefill) adds the gated inputs at each
    row's last K - 1 valid tokens [B, K-1, D].  The conv has one form per
    backend and caller (`linear_attention.conv_kernel_form`)."""
    g, c_gate = _gated_input(h, blk)
    with jax.named_scope("conv"):
        conv = conv_act(
            g, blk["sc_conv"], None, segment_ids, conv_kernel_form(
                cfg.hidden_dim, cfg.sconv_kernel, kernel, with_state),
            act="identity")
    y = _out(c_gate, conv, blk)
    if with_state:
        idx = jnp.arange(segment_ids.shape[-1])
        last = jnp.max(jnp.where(segment_ids > 0, idx, 0), axis=-1)
        return y, conv_tail_at(g, segment_ids, last, cfg.sconv_kernel)
    return y


@jax.named_scope("layer/sconv")
def sconv_step(
    h: jax.Array,  # [B, 1, D]
    blk: Params,
    cfg: ModelConfig,
    tails: jax.Array,  # [n_sconv, B, K-1, D] every conv layer's last inputs
    li,  # this layer's index into them
) -> Tuple[jax.Array, jax.Array]:
    """One decode token per row -> (y [B, 1, D], tails), layer `li` of the
    tails shifted in place: the whole population comes in and goes out so
    that its read and its write lie under this scope."""
    tail = jax.lax.dynamic_index_in_dim(tails, li, axis=0, keepdims=False)
    g, c_gate = _gated_input(h[:, 0], blk)
    with jax.named_scope("conv"):
        window = jnp.concatenate([tail, g[:, None].astype(tail.dtype)], 1)
        conv = jnp.einsum(
            "bkc,kc->bc", window.astype(jnp.float32),
            blk["sc_conv"].astype(jnp.float32),
        )
        tails = jax.lax.dynamic_update_index_in_dim(
            tails, window[:, 1:], li, axis=0
        )
    return _out(c_gate, conv, blk)[:, None], tails


# The kind's record (`models/branches.py`).


def _packed(ctx, h, blk):
    if not ctx.with_state:
        return sconv_forward(
            h, blk, ctx.cfg, ctx.segment_ids, kernel=ctx.row_kernel), {}
    out, tail = sconv_forward(
        h, blk, ctx.cfg, ctx.segment_ids, with_state=True,
        kernel=ctx.row_kernel)
    return out, {"conv": tail}


def _step(ctx, h, blk, cache, li):
    """Layer li of the tails shifted in place."""
    out, tails = sconv_step(h, blk, ctx.cfg, cache.conv, li)
    return out, dataclasses.replace(cache, conv=tails), {}


_CACHE = {  # a tail and no state
    "conv": lambda cfg, batch, s_max, dtype: (
        (batch, cfg.sconv_kernel - 1, cfg.hidden_dim), dtype),
}


def _matmul_params(cfg: ModelConfig) -> int:
    """ONE gated short-convolution layer's matmul parameters: in_proj [D,
    3 D] and out_proj [D, D], and the depthwise conv's K multiply-adds a
    channel."""
    h = cfg.hidden_dim
    return 4 * h * h + cfg.sconv_kernel * h


def _cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """The tails beside the attention layers' k/v, and k/v at every layer
    of the plan, as an all-attention model would keep."""
    return {
        "conv_cache_bytes": nbytes(cache.conv),
        "kv_cache_bytes": nbytes(cache.k, cache.v),
        "kv_cache_bytes_all_attention": 2 * cache.k.dtype.itemsize * (
            cfg.n_layers * batch * s_max * cfg.kv_dim
        ),
    }


def _train_stats(cfg: ModelConfig, n_layers: int, seg: jax.Array, row_kernel):
    """The short convolutions' restarts, summed over them, and the conv's
    form in this gradient program (1: the Pallas operator
    `causal_conv_act`), a trace-time constant."""
    return {
        "sconv/segment_restarts": n_layers * segment_restarts(seg),
        "sconv/conv_on_kernel": jnp.float32(conv_kernel_form(
            cfg.hidden_dim, cfg.sconv_kernel, row_kernel)),
    }


BRANCH = Branch(
    leaves=SCONV_LEAVES,
    init=init_sconv,
    cache=_CACHE,
    packed=_packed,
    step=_step,
    refusal=Refusal(
        HybridLayoutError,
        "gated short-convolution layers beside attention layers run under "
        "data and fsdp sharding only: the conv's channels and its cached "
        "tail are not split over `model`, the conv has no halo over a split "
        "sequence, and the pipeline's stage scans one kind of layer "
        "(PERF.md section 7)",
        "a short convolution's tail has no slot beside the page pool on the "
        "serving plane yet, and its chunk has one kind of layer and none "
        "before the scan: gated short-convolution layers beside attention "
        "layers generate on the static decode program only (at most "
        "max_decode_batch requests, no stop sequences, no speculative "
        "decoding, max_new_tokens within static_path_max_new)",
    ),
    matmul_params=_matmul_params,
    cache_stats=_cache_stats,
    train_stats=_train_stats,
)

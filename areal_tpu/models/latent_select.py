"""Latent attention in two geometries (dots3_note): full layers whose
queries read the latent rows a learned token indexer SELECTS, and
sliding-window layers in a latent geometry of their own over a RING of
latent rows; a sigmoid gate a head on both; one tensor-parallel rank's
share of the heads.

One geometry (`Geom`), H heads held:

    c_q = a_q norm(x W_qa)              [q_nope_h | q_pe_h] = c_q W_qb,h
    [c_kv | k_pe] = x W_kva             c = a_kv norm(c_kv)
    [k_nope_h | v_h] = c (W_kb,h | W_vb,h)
    s_h[t, s] = (q_nope_h . k_nope_h + rope(q_pe_h) . rope(k_pe)) / sqrt(qk)
    o_h = softmax over the keys t may read (s_h) v_h
    out = concat_h(sigmoid(x W_g)_h o_h) W_o

a_q = sqrt(hidden / q_rank), a_kv = sqrt(hidden / kv_rank) under
`cfg.latent_rescale`, else one; the q/k heads are `nope + rope` wide, the v
heads `v` wide, and the two may differ.  The cache keeps ONE row a token,
[c | rope(k_pe)].

- A FULL layer (`LATENT_SELECT`): a query reads the `index_topk` visible
  keys of largest index score, ties to the lower position (every visible
  key while there are no more than that):

      I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])
      qI = c_q W_Iq (HI heads of DI), kI = LayerNorm(x W_Ik), both roped on
      their first `rope` columns; w = x W_Iw / sqrt(HI DI)

  The selection carries no gradient and the indexer's leaves take none
  (`config.FROZEN_LEAVES`; their inputs are detached).  Over packed rows
  (training, `forward`, prefill) selection and attention are ONE pass over
  blocks of queries (`select_attention`): a block's scores against every
  key of the row, the K-th largest by bisection on the scores' bits, then
  the block's attention in `jnp` under that choice — no [S, S] array is
  ever whole; the flash kernels' choice operand is a block of 64 keys, not
  a key (PERF.md section 7).  One token a row (`decode_step`): the row's index keys are scored, `lax.top_k`
  takes the K slots, their latent rows are GATHERED and the absorbed
  kernel `latent_decode` reads those alone — not `s_max` rows under a mask.
- A WINDOW layer (`LATENT_WINDOW`): the last `attn_window` keys, the
  query's own included, rope at `window_rope_theta`.  Packed rows go
  through `packed_attention` with its band (the flash kernels on a TPU),
  v carried on zero columns up to the q/k width: exact, at qk / v times
  the value products.  The cache is a ring of min(attn_window, s_max)
  latent rows (`transformer.ring_valid`); the decode step reads the ring
  where it lies, absorbed, in XLA (513 rows a row).

Parameters (leaves of `params["blocks"]`, stacked over the layers of the
kind; a window layer's under `sw_`):
    wq_a [D, rq]  q_a_norm [rq]  wq_b [rq, H * qk]
    wkv_a [D, c + rope]  kv_a_norm [c]  wk_b [c, H * nope]  wv_b [c, H * v]
    wo [H * v, D]  hgate [D, H]
    idx_q [rq * HI * DI]  idx_k [D * DI]  idx_w [D * HI]   (full layers)
    idx_k_norm, idx_k_norm_b [DI]
The indexer's three matrices are stored FLAT, one vector a layer, and
reshaped where they are used (PERF.md section 7 has why, and what lets
them be matrices again).
"""

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from areal_tpu.models.branches import (
    Branch,
    Counter,
    LatentLayoutError,
    Refusal,
    nbytes,
)
from areal_tpu.models.config import LATENT_SELECT, LATENT_WINDOW, ModelConfig
from areal_tpu.ops.attention import (
    NEG_INF,
    latent_decode_attention,
    packed_attention,
)
from areal_tpu.ops.norms import apply_rotary, rms_norm

Params = Dict[str, jax.Array]

QUERY_BLOCK = 128  # queries a block of the selection and of its attention
INDEX_NORM_EPS = 1e-6  # the index keys' LayerNorm
INDEX_HEAD_GROUP = 32  # index heads scored at a time: [bq, 32, S] fp32


@dataclasses.dataclass(frozen=True)
class Geom:
    """One latent geometry: the leaf prefix, the heads HELD, the ranks and
    the head widths."""

    prefix: str
    n_heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    hidden: int
    eps: float
    rescale: bool

    @property
    def qk(self) -> int:
        return self.nope + self.rope

    @property
    def row(self) -> int:
        """Values a token's row of the cache holds."""
        return self.kv_rank + self.rope

    @property
    def scale(self) -> float:
        return self.qk**-0.5

    @property
    def q_alpha(self) -> float:
        return (self.hidden / self.q_rank) ** 0.5 if self.rescale else 1.0

    @property
    def kv_alpha(self) -> float:
        return (self.hidden / self.kv_rank) ** 0.5 if self.rescale else 1.0

    def leaf(self, name: str) -> str:
        return self.prefix + name


_GEOM_LEAVES = (
    "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wk_b", "wv_b", "wo",
    "hgate",
)
INDEX_LEAVES = ("idx_q", "idx_k", "idx_w", "idx_k_norm", "idx_k_norm_b")
WINDOW_PREFIX = "sw_"


def full_geom(cfg: ModelConfig) -> Geom:
    return Geom(
        "", cfg.n_q_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        cfg.hidden_dim, cfg.rms_norm_eps, cfg.latent_rescale,
    )


def window_geom(cfg: ModelConfig) -> Geom:
    return Geom(
        WINDOW_PREFIX, cfg.swa_n_heads, cfg.swa_q_lora_rank,
        cfg.swa_kv_lora_rank, cfg.swa_qk_nope_head_dim,
        cfg.swa_qk_rope_head_dim, cfg.swa_v_head_dim, cfg.hidden_dim,
        cfg.rms_norm_eps, cfg.latent_rescale,
    )


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def _init_geom(g: Geom, cfg: ModelConfig, key, n: int, dense) -> Params:
    D, H = g.hidden, g.n_heads
    ks = jax.random.split(key, 8)
    ones = jnp.ones
    out = {
        "wq_a": dense(ks[0], (n, D, g.q_rank), D),
        "q_a_norm": ones((n, g.q_rank), cfg.dtype),
        "wq_b": dense(ks[1], (n, g.q_rank, H * g.qk), g.q_rank),
        "wkv_a": dense(ks[2], (n, D, g.row), D),
        "kv_a_norm": ones((n, g.kv_rank), cfg.dtype),
        "wk_b": dense(ks[3], (n, g.kv_rank, H * g.nope), g.kv_rank),
        "wv_b": dense(ks[4], (n, g.kv_rank, H * g.v), g.kv_rank),
        # fan-in of the WHOLE layer's heads: a rank's partial sum is its
        # share of an output of unit scale
        "wo": dense(ks[5], (n, H * g.v, D), H * g.v * cfg.head_share),
    }
    if cfg.attn_gate_headwise:
        out["hgate"] = dense(ks[6], (n, D, H), D)
    return {g.leaf(name): w for name, w in out.items()}


def init_select(cfg: ModelConfig, key, n: int, dense) -> Params:
    """`n` full layers' leaves: the geometry's and the indexer's."""
    key = jax.random.fold_in(key, 11)
    out = _init_geom(full_geom(cfg), cfg, key, n, dense)
    if cfg.index_topk:
        D, rq = cfg.hidden_dim, cfg.q_lora_rank
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        ks = jax.random.split(jax.random.fold_in(key, 1), 3)
        out.update(
            idx_q=dense(ks[0], (n, rq, hi * di), rq).reshape(n, -1),
            idx_k=dense(ks[1], (n, D, di), D).reshape(n, -1),
            idx_w=dense(ks[2], (n, D, hi), D).reshape(n, -1),
            idx_k_norm=jnp.ones((n, di), cfg.dtype),
            idx_k_norm_b=jnp.zeros((n, di), cfg.dtype),
        )
    return out


def init_window(cfg: ModelConfig, key, n: int, dense) -> Params:
    return _init_geom(
        window_geom(cfg), cfg, jax.random.fold_in(key, 12), n, dense)


# --------------------------------------------------------------------------
# The projections of one geometry
# --------------------------------------------------------------------------


def _project(g: Geom, h, blk: Params, cos, sin):
    """h [B, S, D] -> (q_nope [B, S, H, nope], roped q_pe [B, S, H, rope],
    the scaled query latent c_q [B, S, rq], the cache's row [B, S, c +
    rope]: the scaled normed latent beside the roped shared key part)."""
    b, s, _ = h.shape
    f32 = jnp.float32
    with jax.named_scope("q"):
        c_q = rms_norm(
            h @ blk[g.leaf("wq_a")],
            blk[g.leaf("q_a_norm")].astype(f32) * g.q_alpha, g.eps)
        q = (c_q @ blk[g.leaf("wq_b")]).reshape(b, s, g.n_heads, g.qk)
    with jax.named_scope("kv"):
        kv = h @ blk[g.leaf("wkv_a")]
        c_kv = rms_norm(
            kv[..., :g.kv_rank],
            blk[g.leaf("kv_a_norm")].astype(f32) * g.kv_alpha, g.eps)
        q_pe, k_pe = apply_rotary(
            q[..., g.nope:], kv[..., None, g.kv_rank:], cos, sin)
        row = jnp.concatenate([c_kv, k_pe[..., 0, :]], axis=-1)
    return q[..., :g.nope], q_pe, c_q, row


def _materialise(g: Geom, q_nope, q_pe, row, blk: Params):
    """Every head's keys and values up-projected from the rows -> (q, k
    [B, S, H, qk], v [B, S, H, v])."""
    b, s = row.shape[:2]
    with jax.named_scope("kv"):
        c_kv, k_pe = row[..., :g.kv_rank], row[..., None, g.kv_rank:]
        k_nope = (c_kv @ blk[g.leaf("wk_b")]).reshape(b, s, g.n_heads, g.nope)
        v = (c_kv @ blk[g.leaf("wv_b")]).reshape(b, s, g.n_heads, g.v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, s, g.n_heads, g.rope))],
            axis=-1)
    return jnp.concatenate([q_nope, q_pe], axis=-1), k, v


def _absorb(g: Geom, q_nope, q_pe, blk: Params):
    """The query carried into the latent space -> [B, S, H, c + rope]."""
    with jax.named_scope("q"):
        q_lat = jnp.einsum(
            "bshn,chn->bshc", q_nope,
            blk[g.leaf("wk_b")].reshape(g.kv_rank, g.n_heads, g.nope))
        return jnp.concatenate([q_lat, q_pe], axis=-1)


def _out(g: Geom, a, h, blk: Params, cfg: ModelConfig, absorbed: bool):
    """a: the heads' outputs [B, S, H, v] — or, `absorbed`, their weighted
    sums of latent rows [B, S, H, c], the value up-projection still to
    come — gated a head and projected: this rank's partial `o_proj` sum."""
    b, s = a.shape[:2]
    if absorbed:
        with jax.named_scope("attend"):
            a = jnp.einsum(
                "bshc,chv->bshv", a,
                blk[g.leaf("wv_b")].reshape(g.kv_rank, g.n_heads, g.v))
    if cfg.attn_gate_headwise:
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(
                (h @ blk[g.leaf("hgate")]).astype(jnp.float32))
            a = (a.astype(jnp.float32) * gate[..., None]).astype(a.dtype)
    with jax.named_scope("o_proj"):
        return a.reshape(b, s, g.n_heads * g.v) @ blk[g.leaf("wo")]


# --------------------------------------------------------------------------
# The indexer
# --------------------------------------------------------------------------


def _layer_norm(x, w, b, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtype)


def index_projections(cfg: ModelConfig, h, c_q, blk: Params, cos, sin):
    """-> (qI [B, S, HI, DI], kI [B, S, DI] in the weights' type, w [B, S,
    HI] fp32).  Nothing here is differentiated: the inputs are detached."""
    h, c_q = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
    b, s, d = h.shape
    hi, di, r = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    qi = (c_q @ blk["idx_q"].reshape(cfg.q_lora_rank, hi * di)).reshape(
        b, s, hi, di)
    ki = _layer_norm(
        h @ blk["idx_k"].reshape(d, di), blk["idx_k_norm"],
        blk["idx_k_norm_b"], INDEX_NORM_EPS)
    q_r, k_r = apply_rotary(qi[..., :r], ki[..., None, :r], cos, sin)
    qi = jnp.concatenate([q_r, qi[..., r:]], axis=-1)
    ki = jnp.concatenate([k_r[..., 0, :], ki[..., r:]], axis=-1)
    w = (h @ blk["idx_w"].reshape(d, hi)).astype(jnp.float32) * (
        hi**-0.5 * di**-0.5)
    return qi, ki, w


def index_scores(qi, w, ki) -> jax.Array:
    """qI [B, Q, HI, DI], w [B, Q, HI], kI [B, S, DI] -> I [B, Q, S] fp32,
    `INDEX_HEAD_GROUP` heads at a time."""
    hi = qi.shape[2]
    group = min(INDEX_HEAD_GROUP, hi)
    out = None
    for j in range(0, hi, group):
        s = jnp.einsum(
            "bqjd,bsd->bqjs", qi[:, :, j:j + group], ki,
            preferred_element_type=jnp.float32)
        s = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(s), w[:, :, j:j + group])
        out = s if out is None else out + s
    return out


def _sortable(x: jax.Array) -> jax.Array:
    """fp32 -> uint32 in the same order (-inf lowest)."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def topk_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """scores [..., S] fp32, visible [..., S] bool -> bool: the `k` visible
    entries of largest score, ties to the lower position; every visible
    entry where there are no more than `k`.  The k-th largest is found by
    bisection on the scores' bits: 32 counts, no sort."""
    s = scores.shape[-1]
    if s <= k:
        return visible
    u = _sortable(jnp.where(visible, scores, -jnp.inf))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, tie = u > thr, u == thr
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return visible & (above | (tie & (jnp.cumsum(tie, axis=-1) <= room)))


def _query_block(s: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, s) + 1) if s % n == 0)


def _visible(seg_q, pos_q, segment_ids):
    """[B, Q, S] bool: key s is of query t's sequence and not after it."""
    pos_k = jnp.arange(segment_ids.shape[1])
    return (
        (seg_q[:, :, None] == segment_ids[:, None, :])
        & (seg_q[:, :, None] > 0)
        & (pos_k[None, None, :] <= pos_q[None, :, None])
    )


def _blocks_of(x, bq):
    """[B, S, ...] -> [S / bq, B, bq, ...]: what `lax.map` walks."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // bq, bq, *x.shape[2:]), 1, 0)


def _unblock(x):
    """`_blocks_of`, undone."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _pack_width(bq: int) -> int:
    return max(p for p in (32, 16, 8, 4, 2, 1) if bq % p == 0)


def _pack(mask, p: int):
    """[B, Q, S] bool -> [B, Q / p, S] uint32: `p` queries to a word."""
    b, q, s = mask.shape
    at = jnp.arange(p, dtype=jnp.uint32)[None, None, :, None]
    return jnp.sum(
        mask.reshape(b, q // p, p, s).astype(jnp.uint32) << at, axis=2,
        dtype=jnp.uint32)


def _unpack(bits, p: int):
    """`_pack`, undone."""
    b, g, s = bits.shape
    at = jnp.arange(p, dtype=jnp.uint32)[None, None, :, None]
    return ((bits[:, :, None, :] >> at) & 1).astype(bool).reshape(b, g * p, s)


def select_block(cfg: ModelConfig, qb, wb, ki, visible) -> jax.Array:
    """One block of queries' selection, [B, Q, S] bool: its index scores
    against every key of the row, the `index_topk` largest of the visible."""
    with jax.named_scope("indexer/score"):
        scores = index_scores(qb, wb, ki)
    with jax.named_scope("indexer/topk"):
        return topk_mask(scores, visible, cfg.index_topk)


_SELECTION = "latent_selection"  # a block's packed selection, kept


def select_attention(cfg: ModelConfig, q, k, v, qi, w, ki, segment_ids,
                     scale: float) -> jax.Array:
    """q, k [B, S, H, qk], v [B, S, H, v]; the indexer's qI [B, S, HI, DI],
    w [B, S, HI], kI [B, S, DI] (None without an indexer) -> [B, S, H, v]:
    softmax attention over the keys each query SELECTS, in ONE pass over
    blocks of queries — a block's selection, then its attention under it —
    so no [S, S] array, of scores or of choices, is ever whole.  Where no
    query has more than `index_topk` keys every visible key is read.

    A block is rematerialised in the backward pass; what it keeps for that
    is its slice of q and its selection, 32 queries to a word ([S / 32, S]
    uint32 over the blocks of a row: 22 MB at 13 k), so the indexer is not
    run a third time.  The selection carries no gradient.

    A pass that takes no gradient packs and unpacks all the same (a tenth
    of this function's forward, PERF.md section 7).  A `custom_vjp` that
    packs only under a gradient is no way out: beneath the layer's remat
    JAX then keeps every block's visibility from the forward pass — the
    [S, S] choice again, stacked (`tests/test_dots3_note_v5e.py`)."""
    s = segment_ids.shape[1]
    selects = bool(cfg.index_topk) and s > cfg.index_topk
    bq = _query_block(s)
    p = _pack_width(bq)
    index = ()
    if selects:
        ki = jax.lax.stop_gradient(ki)
        index = tuple(
            _blocks_of(jax.lax.stop_gradient(x), bq) for x in (qi, w))

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(_SELECTION))
    def block(i, seg_q, qb, *index):
        mb = _visible(seg_q, i * bq + jnp.arange(bq), segment_ids)
        if selects:
            bits = checkpoint_name(
                _pack(select_block(cfg, *index, ki, mb), p), _SELECTION)
            mb = _unpack(bits, p)
        with jax.named_scope("attend"):
            a = jnp.einsum(
                "bqhd,bkhd->bhqk", qb, k, preferred_element_type=jnp.float32
            ) * scale
            a = jnp.where(mb[:, None], a, NEG_INF)
            pr = jax.nn.softmax(a, axis=-1)
            pr = jnp.where(mb.any(axis=-1)[:, None, :, None], pr, 0.0)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", pr.astype(v.dtype), v,
                preferred_element_type=jnp.float32).astype(q.dtype)

    return _unblock(jax.lax.map(lambda xs: block(*xs), (
        jnp.arange(s // bq), _blocks_of(segment_ids, bq), _blocks_of(q, bq),
        *index)))


# --------------------------------------------------------------------------
# Over packed rows
# --------------------------------------------------------------------------


@jax.named_scope("layer/latent_attn")
def select_forward(ctx, h, blk: Params):
    """A full layer over packed rows -> (its output, the rows it leaves in
    the cache: latent rows and, with an indexer, index keys)."""
    cfg, g = ctx.cfg, full_geom(ctx.cfg)
    q_nope, q_pe, c_q, row = _project(g, h, blk, ctx.cos, ctx.sin)
    left = {"latent": row}
    qi = w = ki = None
    if cfg.index_topk:
        with jax.named_scope("indexer/proj"):
            qi, ki, w = index_projections(cfg, h, c_q, blk, ctx.cos, ctx.sin)
        left["ikeys"] = ki
    q, k, v = _materialise(g, q_nope, q_pe, row, blk)
    a = select_attention(cfg, q, k, v, qi, w, ki, ctx.segment_ids, g.scale)
    return _out(g, a, h, blk, cfg, absorbed=False), left


@jax.named_scope("layer/latent_window")
def window_forward(ctx, h, blk: Params):
    """A window layer over packed rows: the band through
    `packed_attention`, v on zero columns up to the q/k width."""
    cfg, g = ctx.cfg, window_geom(ctx.cfg)
    q_nope, q_pe, _, row = _project(g, h, blk, *ctx.window_rope)
    q, k, v = _materialise(g, q_nope, q_pe, row, blk)
    with jax.named_scope("attend"):
        vp = jnp.pad(v, ((0, 0),) * 3 + ((0, g.qk - g.v),))
        a = packed_attention(
            q, k, vp, ctx.segment_ids, causal=True, use_flash=ctx.use_flash,
            window=cfg.attn_window, scope="latent_window")[..., :g.v]
    return _out(g, a, h, blk, cfg, absorbed=False), row


def _window_packed(ctx, h, blk):
    from areal_tpu.models.transformer import _ring_tail

    out, row = window_forward(ctx, h, blk)
    if ctx.ring is None:
        return out, {}
    return out, {"wlatent": _ring_tail(row, ctx.ring)}


# --------------------------------------------------------------------------
# One token a row
# --------------------------------------------------------------------------


def _put_row(buf, new, li, slot):
    """[B, 1, C] written at (layer, :, slot)."""
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype)[None], (li, 0, slot, 0))


def selects_on_decode(cfg: ModelConfig, s_max: int) -> bool:
    """Whether a decode step over a window of `s_max` slots gathers the
    selected rows (else every live row is read: no more than `index_topk`
    can be live)."""
    return bool(cfg.index_topk) and s_max > cfg.index_topk


@jax.named_scope("layer/latent_attn")
def _select_step(ctx, h, blk, cache, li):
    """A full layer, one token a row: the token's latent row and index key
    go into layer `li`; the row's index keys are scored, the top
    `index_topk` slots' latent rows gathered and read by the absorbed
    kernel — those alone.  What it read rides out for the counter ([3]
    fp32: latent rows read, rows visible, index keys scored)."""
    cfg, slot, g = ctx.cfg, ctx.slot, full_geom(ctx.cfg)
    b = h.shape[0]
    q_nope, q_pe, c_q, row = _project(g, h, blk, ctx.cos, ctx.sin)
    rows = _put_row(cache.latent, row, li, slot)
    q = _absorb(g, q_nope, q_pe, blk)[:, 0]
    lo = jnp.maximum(ctx.valid_from, 0).astype(jnp.int32)
    visible = (slot + 1 - lo).astype(jnp.float32)
    ikeys = cache.ikeys
    if not selects_on_decode(cfg, rows.shape[2]):
        if cfg.index_topk:  # kept for a later, longer window
            with jax.named_scope("indexer/proj"):
                _, ki, _ = index_projections(
                    cfg, h, c_q, blk, ctx.cos, ctx.sin)
            ikeys = _put_row(ikeys, ki, li, slot)
        with jax.named_scope("attend"):
            a = latent_decode_attention(
                q, rows, li, ctx.valid_from, slot + 1, g.kv_rank, g.scale,
                use_kernel=ctx.row_kernel)
        read = jnp.stack([jnp.sum(visible), jnp.sum(visible), jnp.float32(0)])
    else:
        k = cfg.index_topk
        with jax.named_scope("indexer/proj"):
            qi, ki, w = index_projections(cfg, h, c_q, blk, ctx.cos, ctx.sin)
        ikeys = _put_row(ikeys, ki, li, slot)
        with jax.named_scope("indexer/score"):
            keys = jax.lax.dynamic_index_in_dim(ikeys, li, 0, keepdims=False)
            scores = index_scores(qi, w, keys)[:, 0]  # [B, s_max]
            at = jnp.arange(keys.shape[1])
            live = (at[None, :] >= lo[:, None]) & (at[None, :] <= slot)
            scores = jnp.where(live, scores, -jnp.inf)
        with jax.named_scope("indexer/topk"):
            # ties to the lower slot; the dead slots' -inf come last
            _, chosen = jax.lax.top_k(scores, k)
        with jax.named_scope("attend"):
            # The chosen rows straight off the STACKED cache, by flat row
            # number: a slice of the layer first is a copy of all its
            # s_max rows a step (122 MB at 8 rows of 13 k: chip run, PR 64).
            n_layers, _, s_max, width = rows.shape
            at = (li * b + jnp.arange(b)) * s_max
            picked = jnp.take(
                rows.reshape(n_layers * b * s_max, width),
                at[:, None] + chosen, axis=0)  # [B, k, c + rope]
            n_live = jnp.minimum(slot + 1 - lo, k)
            a = latent_decode_attention(
                q, picked[None], 0, jnp.zeros((b,), jnp.int32), n_live,
                g.kv_rank, g.scale, use_kernel=ctx.row_kernel)
        read = jnp.stack([
            jnp.sum(n_live).astype(jnp.float32), jnp.sum(visible),
            jnp.sum(visible)])
    out = _out(g, a[:, None], h, blk, cfg, absorbed=True)
    return out, dataclasses.replace(
        cache, latent=rows, ikeys=ikeys), {LATENT_SELECT: read}


@jax.named_scope("layer/latent_window")
def _window_step(ctx, h, blk, cache, li):
    """A window layer, one token a row: the token's row goes to entry
    `slot` mod ring of ring `li`, over the row that left the window, and
    the live entries (`ctx.live`) are read where they lie, absorbed."""
    cfg, g = ctx.cfg, window_geom(ctx.cfg)
    q_nope, q_pe, _, row = _project(g, h, blk, *ctx.window_rope)
    ring = _put_row(cache.wlatent, row, li, ctx.slot % cache.wlatent.shape[2])
    q = _absorb(g, q_nope, q_pe, blk)[:, 0]  # [B, H, c + rope]
    with jax.named_scope("attend"):
        rows = jax.lax.dynamic_index_in_dim(ring, li, 0, keepdims=False)
        s = jnp.einsum(
            "bhc,bsc->bhs", q, rows.astype(q.dtype),
            preferred_element_type=jnp.float32) * g.scale
        s = jnp.where(ctx.live[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(ctx.live.any(axis=-1)[:, None, None], p, 0.0)
        a = jnp.einsum(
            "bhs,bsc->bhc", p.astype(rows.dtype), rows[..., :g.kv_rank],
            preferred_element_type=jnp.float32).astype(q.dtype)
    out = _out(g, a[:, None], h, blk, cfg, absorbed=True)
    return out, dataclasses.replace(cache, wlatent=ring), {}


# --------------------------------------------------------------------------
# The kinds' records (`models/branches.py`)
# --------------------------------------------------------------------------


def _geom_matmul_params(g: Geom) -> int:
    return (
        g.hidden * g.q_rank + g.q_rank * g.n_heads * g.qk + g.hidden * g.row
        + g.kv_rank * g.n_heads * (g.nope + g.v) + g.n_heads * g.v * g.hidden
        + g.hidden * g.n_heads
    )


def _select_matmul_params(cfg: ModelConfig) -> int:
    """A full layer's projections, the gate's and the indexer's three."""
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    index = (
        cfg.q_lora_rank * hi * di + cfg.hidden_dim * (di + hi)
    ) if cfg.index_topk else 0
    return _geom_matmul_params(full_geom(cfg)) + index


def _select_flops(cfg: ModelConfig, n_tokens, sum_sq_seqlens) -> float:
    """A token's SELECTED keys (never more than its sequence has) at the
    two head widths, and its index scores against every visible key — the
    causal factor folded in as `transformer._softmax_flops` folds it."""
    g = full_geom(cfg)
    pairs = sum_sq_seqlens
    index = 0.0
    if cfg.index_topk:
        pairs = min(pairs, 2.0 * n_tokens * cfg.index_topk)
        index = 2.0 * cfg.index_n_heads * cfg.index_head_dim * sum_sq_seqlens
    return 2.0 * g.n_heads * (g.qk + g.v) * pairs + index


def _window_flops(cfg: ModelConfig, n_tokens, sum_sq_seqlens) -> float:
    g = window_geom(cfg)
    pairs = min(sum_sq_seqlens, 2.0 * n_tokens * cfg.attn_window)
    return 2.0 * g.n_heads * (g.qk + g.v) * pairs


def _select_report(sums, cfg: ModelConfig, params) -> Dict[str, float]:
    read, visible, scored, steps = (float(x) for x in sums)
    if not steps:
        return {}
    return dict(
        latent_rows_read=read, latent_rows_visible=visible,
        index_keys_scored=scored, select_decode_steps=int(steps))


def _select_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    """The latent rows, the indexer's key rows beside them, and whether the
    decode step of this window reads a selection (set from shapes)."""
    return {
        "latent_cache_bytes": nbytes(cache.latent),
        "index_cache_bytes": nbytes(cache.ikeys),
        "select_on_kernel": int(selects_on_decode(cfg, s_max)),
    }


def _window_cache_stats(cfg: ModelConfig, cache, batch: int, s_max: int):
    return {
        "latent_ring_bytes": nbytes(cache.wlatent),
        "latent_ring_rows": int(cache.wlatent.shape[2]),
    }


def _select_train_stats(cfg, n_layers: int, seg: jax.Array, row_kernel):
    """(query, key) pairs the indexer scores in this micro-batch's full
    layers (a block of queries scores every key of its row), and the form
    of the selection over packed rows (0: the `jnp` form), a trace-time
    constant."""
    b, s = seg.shape
    scored = float(n_layers * b * s * s) if (
        cfg.index_topk and s > cfg.index_topk) else 0.0
    return {
        "latent_select/index_pairs_scored": jnp.float32(scored),
        "latent_select/select_on_kernel": jnp.float32(0.0),
    }


_REFUSAL = Refusal(
    LatentLayoutError,
    "latent attention by `window_pattern` (selected full layers, window "
    "layers on a ring of latent rows) runs under data and fsdp sharding "
    "only: the heads of the low-rank projections are not split over "
    "`model` (a rank's share of them is a configuration's, `head_share`), "
    "the selection has no ring over a split sequence and the pipeline's "
    "stage scans one kind of layer (PERF.md section 7)",
    "latent rows, index keys and a ring of latent rows have no pages on "
    "the serving plane yet, and its chunk has no layer before the scan: "
    "they generate on the static decode program only (at most "
    "max_decode_batch requests, no stop sequences, no speculative "
    "decoding, max_new_tokens within static_path_max_new)",
)

SELECT_BRANCH = Branch(
    leaves=_GEOM_LEAVES + INDEX_LEAVES,
    init=init_select,
    cache={
        "latent": lambda cfg, batch, s_max, dtype: (
            (batch, s_max, cfg.latent_dim), dtype),
        "ikeys": lambda cfg, batch, s_max, dtype: (
            (batch, s_max, cfg.index_head_dim), dtype),
    },
    packed=select_forward,
    step=_select_step,
    refusal=_REFUSAL,
    # a block of queries' scores are the residuals that do not fit beside
    # an MLP's at rows of 13 k tokens: a branch at a time
    remat_alone=True,
    matmul_params=_select_matmul_params,
    attn_flops=_select_flops,
    counter=Counter(
        LATENT_SELECT, lambda cfg, rows: 4,
        lambda given, cfg, at: jnp.concatenate([
            jnp.sum(given.reshape(-1, 3), axis=0), jnp.ones((1,), jnp.float32)
        ]),
        _select_report,
    ),
    cache_stats=_select_cache_stats,
    train_stats=_select_train_stats,
)

WINDOW_BRANCH = Branch(
    leaves=tuple(WINDOW_PREFIX + n for n in _GEOM_LEAVES),
    init=init_window,
    cache={
        "wlatent": lambda cfg, batch, s_max, dtype: (
            (batch, min(cfg.attn_window, s_max),
             cfg.swa_kv_lora_rank + cfg.swa_qk_rope_head_dim), dtype),
    },
    packed=_window_packed,
    step=_window_step,
    refusal=_REFUSAL,
    remat_alone=True,
    matmul_params=lambda cfg: _geom_matmul_params(window_geom(cfg)),
    attn_flops=_window_flops,
    flash_window=lambda cfg: cfg.attn_window,
    cache_stats=_window_cache_stats,
)

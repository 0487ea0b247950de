"""GSPMD sharding rules for the transformer pytree.

Capability parity: realhf/impl/model/parallelism/ — but instead of
Megatron-style explicit Column/RowParallelLinear modules with hand-written
collectives, we annotate the SAME pure-functional model with
`jax.sharding.PartitionSpec`s and let the XLA SPMD partitioner insert
all-gathers / reduce-scatters / psums (sequence parallelism falls out
automatically).  One rule table replaces ~2.5k LoC of TP modules.

Conventions (mesh axes from areal_tpu/base/topology.py):
- `model`  — tensor parallel: attention heads + MLP hidden + vocab.
- `fsdp`   — ZeRO-style: remaining param dim sharded; batch also sharded.
  The STORED head keeps its hidden dim here; the log-prob head re-lays its
  compute copy so that the vocabulary is split over `model` AND `fsdp`
  (`HEAD_VOCAB_PARALLEL`): each chip holds a slice of the vocabulary and
  no [chunk, V] block of logits crosses the chips.
- `data`   — pure DP: params replicated, batch sharded.
- `seq`    — context parallel: sequence dim of activations (ring attention).
- `pipe`   — pipeline stages (layer-stacked leading axis).
"""

import functools
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.base import logging
from areal_tpu.models.config import DENSE_PREFIX
from areal_tpu.base.topology import (
    DATA_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
)

logger = logging.getLogger("sharding")

BATCH = (DATA_AXIS, FSDP_AXIS)

# Param rules: leaf name -> PartitionSpec.  The leading layer-stack axis of
# block params shards over `pipe`: stage s holds layers [s*L/P, (s+1)*L/P)
# (see areal_tpu/parallel/pipeline.py); on pipe=1 meshes it is a no-op.
_BLOCK_RULES: Dict[str, P] = {
    "ln1": P(PIPE_AXIS, None),
    "ln2": P(PIPE_AXIS, None),
    "ln1_b": P(PIPE_AXIS, None),
    "ln2_b": P(PIPE_AXIS, None),
    # olmoe QK-norm weights over the whole projection: layer-stacked vectors
    # like ln1 (replicated — the norm's mean runs over every head).
    "q_norm": P(PIPE_AXIS, None),
    "k_norm": P(PIPE_AXIS, None),
    "bo": P(PIPE_AXIS, None),
    "bproj": P(PIPE_AXIS, None),
    "bfc": P(PIPE_AXIS, MODEL_AXIS),  # matches wg's model-sharded output
    "wq": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "wk": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "wv": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "bq": P(PIPE_AXIS, MODEL_AXIS),
    "bk": P(PIPE_AXIS, MODEL_AXIS),
    "bv": P(PIPE_AXIS, MODEL_AXIS),
    "wo": P(PIPE_AXIS, MODEL_AXIS, FSDP_AXIS),
    # Dense MLP
    "wg": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "wu": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "wd": P(PIPE_AXIS, MODEL_AXIS, FSDP_AXIS),
    # MoE: expert axis = expert parallelism over fsdp; hidden over model.
    "router": P(PIPE_AXIS, FSDP_AXIS, None),
    "moe_wg": P(PIPE_AXIS, FSDP_AXIS, None, MODEL_AXIS),
    "moe_wu": P(PIPE_AXIS, FSDP_AXIS, None, MODEL_AXIS),
    "moe_wd": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS, None),
    # qwen3_next.  Per-head QK-norm weights ([head_dim]) take the q_norm /
    # k_norm rule above; the attention gate is a second query projection;
    # the shared expert is a dense MLP of its own.
    "wqg": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "ws_g": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "ws_u": P(PIPE_AXIS, FSDP_AXIS, MODEL_AXIS),
    "ws_d": P(PIPE_AXIS, MODEL_AXIS, FSDP_AXIS),
    "ws_gate": P(PIPE_AXIS, FSDP_AXIS, None),
    # Gated DeltaNet leaves: ZeRO-sharded over fsdp, NOT split over `model`
    # — the conv, the per-head gates and the state's heads would all have
    # to split with q | k | v's packed output axis, and `attn_dispatch`
    # refuses a hybrid model on a mesh with model > 1 by name instead.
    "la_wqkv": P(PIPE_AXIS, FSDP_AXIS, None),
    "la_wz": P(PIPE_AXIS, FSDP_AXIS, None),
    "la_wba": P(PIPE_AXIS, FSDP_AXIS, None),
    "la_conv": P(PIPE_AXIS, None, None),
    "la_A_log": P(PIPE_AXIS, None),
    "la_dt_bias": P(PIPE_AXIS, None),
    "la_norm": P(PIPE_AXIS, None),
    "la_wo": P(PIPE_AXIS, None, FSDP_AXIS),
    # Latent attention (MLA): ZeRO-sharded over fsdp on the input dim, NOT
    # split over `model` — the heads sit in the packed output axes of
    # `wq_b` / `wk_b` / `wv_b` and the latent row is shared by all of them;
    # `attn_dispatch` refuses a latent model on a mesh with model > 1 by
    # name.  `wo` takes the rule above.  The sigmoid router's choice bias
    # is a vector per layer.
    "wq_a": P(PIPE_AXIS, FSDP_AXIS, None),
    "q_a_norm": P(PIPE_AXIS, None),
    "wq_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "wkv_a": P(PIPE_AXIS, FSDP_AXIS, None),
    "kv_a_norm": P(PIPE_AXIS, None),
    "wk_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "wv_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "router_bias": P(PIPE_AXIS, None),
    # Latent attention by `window_pattern` (`models/latent_select.py`): the
    # headwise gate, the token indexer's leaves (its three matrices are
    # stored as one vector a layer) and a window layer's own geometry under
    # `sw_`, by the rules above: ZeRO over fsdp, nothing over `model`.
    "hgate": P(PIPE_AXIS, FSDP_AXIS, None),
    "idx_q": P(PIPE_AXIS, FSDP_AXIS),
    "idx_k": P(PIPE_AXIS, FSDP_AXIS),
    "idx_w": P(PIPE_AXIS, FSDP_AXIS),
    "idx_k_norm": P(PIPE_AXIS, None),
    "idx_k_norm_b": P(PIPE_AXIS, None),
    "sw_wq_a": P(PIPE_AXIS, FSDP_AXIS, None),
    "sw_q_a_norm": P(PIPE_AXIS, None),
    "sw_wq_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "sw_wkv_a": P(PIPE_AXIS, FSDP_AXIS, None),
    "sw_kv_a_norm": P(PIPE_AXIS, None),
    "sw_wk_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "sw_wv_b": P(PIPE_AXIS, FSDP_AXIS, None),
    "sw_wo": P(PIPE_AXIS, None, FSDP_AXIS),
    "sw_hgate": P(PIPE_AXIS, FSDP_AXIS, None),
    # Mamba-2 leaves: ZeRO-sharded over fsdp, NOT split over `model` — the
    # heads sit in the packed output axis of `ssm_in` beside the groups'
    # B | C and the per-head dt, and the conv, the gated norm's groups and
    # the state would all have to split with them; `attn_dispatch` refuses
    # a pattern of one-branch layers on a mesh with model > 1 by name.
    "ssm_in": P(PIPE_AXIS, FSDP_AXIS, None),
    "ssm_conv": P(PIPE_AXIS, None, None),
    "ssm_conv_b": P(PIPE_AXIS, None),
    "ssm_A_log": P(PIPE_AXIS, None),
    "ssm_D": P(PIPE_AXIS, None),
    "ssm_dt_bias": P(PIPE_AXIS, None),
    "ssm_norm": P(PIPE_AXIS, None),
    "ssm_out": P(PIPE_AXIS, None, FSDP_AXIS),
    # Gated short convolution: ZeRO-sharded over fsdp on the projections'
    # hidden dimension, NOT split over `model` — B | C | u sit in `sc_in`'s
    # packed output axis and the conv's channels and cached tail would have
    # to split with them; `attn_dispatch` refuses the plan on a mesh with
    # model > 1 by name.
    "sc_in": P(PIPE_AXIS, FSDP_AXIS, None),
    "sc_conv": P(PIPE_AXIS, None, None),
    "sc_out": P(PIPE_AXIS, None, FSDP_AXIS),
    # Lightning attention: ZeRO-sharded over fsdp, NOT split over `model`
    # — the constant decay is a head's and the fp32 state's heads would
    # have to split with the projections; `attn_dispatch` refuses
    # minicpm_sala's plan on a mesh with model > 1 by name (its block-
    # sparse layers take the attention rules above).
    "lt_wq": P(PIPE_AXIS, FSDP_AXIS, None),
    "lt_wk": P(PIPE_AXIS, FSDP_AXIS, None),
    "lt_wv": P(PIPE_AXIS, FSDP_AXIS, None),
    "lt_wg": P(PIPE_AXIS, FSDP_AXIS, None),
    "lt_q_norm": P(PIPE_AXIS, None),
    "lt_k_norm": P(PIPE_AXIS, None),
    "lt_norm": P(PIPE_AXIS, None),
    "lt_wo": P(PIPE_AXIS, None, FSDP_AXIS),
}

_TOP_RULES: Dict[str, P] = {
    "embed": P(MODEL_AXIS, FSDP_AXIS),
    "pos_embed": P(None, FSDP_AXIS),
    "final_ln": P(None),
    "final_ln_b": P(None),
    "lm_head": P(FSDP_AXIS, MODEL_AXIS),
    "value_head": P(FSDP_AXIS, None),
}


# The [D, V] LM head (`lm_head`, and equally the tied `embed` transposed):
# its stored layout, and the log-prob head's vocabulary-parallel one — V
# over every axis that shards parameters, rows whole; a chunk's [chunk, V]
# logits take the same.
HEAD_STORED = _TOP_RULES["lm_head"]
HEAD_VOCAB_PARALLEL = P(None, (MODEL_AXIS, FSDP_AXIS))


def param_pspecs(params: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec pytree matching the transformer params structure."""
    out: Dict[str, Any] = {}
    for k, v in params.items():
        if k == "blocks":
            blocks = {}
            for bk, bv in v.items():
                # A leading dense layer's leaf takes its layer's own rule.
                name = bk.removeprefix(DENSE_PREFIX)
                if name in ("wg", "wu", "wd") and np.ndim(bv) == 4:
                    blocks[bk] = _BLOCK_RULES["moe_" + name]
                else:
                    blocks[bk] = _BLOCK_RULES[name]
            out[k] = blocks
        else:
            out[k] = _TOP_RULES[k]
    return out


def batch_pspec(with_seq: bool = True) -> P:
    """Sharding for [B, S] token/segment arrays."""
    return P(BATCH, SEQ_AXIS if with_seq else None)


def act_pspec() -> P:
    """Sharding for [B, S, D] activations."""
    return P(BATCH, SEQ_AXIS, None)


def logits_pspec() -> P:
    return P(BATCH, SEQ_AXIS, MODEL_AXIS)


@functools.lru_cache(maxsize=None)
def _vocab_shards(model: int, fsdp: int, vocab: int) -> int:
    n = model * fsdp
    if n > 1 and vocab % n:
        # Cached per (mesh sizes, V): said once, not at every trace.
        logger.warning(
            f"vocabulary {vocab} does not divide by model x fsdp = {n}: "
            "the log-prob head keeps its stored layout (not "
            "vocabulary-parallel)"
        )
        return 1
    return n


def head_vocab_shards(mesh: Optional[Mesh], vocab: int) -> int:
    """Ways the log-prob head splits a vocabulary of `vocab` under `mesh`:
    model x fsdp, or 1 (no mesh, a product of 1, or V not divisible) — the
    caller then adds no constraint and traces the program it traced
    without a mesh."""
    if mesh is None:
        return 1
    return _vocab_shards(mesh.shape[MODEL_AXIS], mesh.shape[FSDP_AXIS], vocab)


def kv_cache_pspec() -> P:
    """[L, B, S, n_kv, d] — batch over (data,fsdp), heads over model."""
    return P(None, BATCH, None, MODEL_AXIS, None)


def attn_dispatch(mesh: Mesh, cfg=None):
    """Shared engine policy -> (use_flash, cp_mesh, pp_mesh, pp_microbatches,
    rows_multiple).

    use_flash: None (auto: flash on TPU) on single-device meshes; the MESH
    itself on multi-device tp/fsdp layouts — packed_attention shard_maps
    the Pallas kernel over it (batch on data/fsdp, heads on model) when the
    backend is TPU and head counts divide the model axis (pass `cfg` to
    check; without cfg multi-device flash stays off).  Ring context
    parallelism owns any mesh with a nontrivial `seq` axis; the block stack
    is microbatch-pipelined whenever `pipe` > 1 with 4 microbatches per
    stage (GPipe bubble (P-1)/(M+P-1) < ~20%).

    `rows_multiple` is what packed-batch row counts must divide by: the
    batch-sharding degree, times the microbatch count under PP (each
    microbatch must itself split over the batch axes — product, not lcm).
    """
    import numpy as np

    from areal_tpu.base.topology import BATCH_AXES

    if cfg is not None and any(
        mesh.shape[a] > 1 for a in (MODEL_AXIS, SEQ_AXIS, PIPE_AXIS)
    ):
        from areal_tpu.models.transformer import plan_refusal

        refusal = plan_refusal(cfg, serving=False)
        if refusal:
            raise type(refusal)(f"mesh {dict(mesh.shape)}: {refusal}")
    if mesh.devices.size == 1:
        use_flash = None
    else:
        from areal_tpu.base.distributed import is_tpu_backend

        m = mesh.shape[MODEL_AXIS]
        eligible = (
            is_tpu_backend()
            and mesh.shape[SEQ_AXIS] == 1
            and mesh.shape[PIPE_AXIS] == 1
            and cfg is not None
            and cfg.n_kv_heads % m == 0
            and cfg.n_q_heads % m == 0
        )
        use_flash = mesh if eligible else False
    cp_mesh = mesh if mesh.shape[SEQ_AXIS] > 1 else None
    pp_mesh = mesh if mesh.shape[PIPE_AXIS] > 1 else None
    # REQUESTED in-flight microbatches: 4P amortizes the GPipe bubble to
    # (P-1)/(5P-1).  The schedule steps down to the largest multiple of P
    # that divides the actual row count (pipeline.py), so rows only need
    # padding to batch_axes x P — small PPO minibatches no longer pad to
    # 8P rows (the old rows_multiple = batch x 4P).
    pp_microbatches = 4 * mesh.shape[PIPE_AXIS]
    rows_multiple = int(np.prod([mesh.shape[a] for a in BATCH_AXES]))
    if pp_mesh is not None:
        rows_multiple *= mesh.shape[PIPE_AXIS]
    return use_flash, cp_mesh, pp_mesh, pp_microbatches, rows_multiple


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def place_rows(mesh: Mesh, value, spec: P):
    """Put a row-major [B, ...] host batch array onto the mesh.

    Single-process meshes (and meshes whose process boundaries cut only
    non-batch axes) take the plain device_put path.  When the batch axis
    SPANS processes, each process contributes only its own contiguous row
    block via jax.make_array_from_process_local_data — the sharded data
    plane ships a member only those rows (zero placeholders elsewhere), and
    device_put's cross-process value check would (rightly) reject the
    now-divergent full host arrays.  With unsharded full data the local
    slice is identical, so this path is always safe when n > 1.
    """
    import numpy as np

    from areal_tpu.base.topology import local_batch_shard

    sh = NamedSharding(mesh, spec)
    rank, n = local_batch_shard(mesh)
    if n <= 1:
        return jax.device_put(value, sh)
    b = value.shape[0]
    if b % n:
        raise ValueError(
            f"batch rows ({b}) must divide the process shard count ({n}); "
            "the packer pads rows to the mesh batch degree"
        )
    lo = rank * (b // n)
    local = np.ascontiguousarray(value[lo : lo + b // n])
    return jax.make_array_from_process_local_data(sh, local, value.shape)


def tree_named(mesh: Mesh, specs) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Place a (host or device) param pytree onto the mesh per the rules."""
    shardings = tree_named(mesh, param_pspecs(params))
    return jax.device_put(params, shardings)


def replicate(tree: Any, mesh: Mesh) -> Any:
    return jax.device_put(tree, NamedSharding(mesh, P()))


def check_divisibility(params: Dict[str, Any], mesh: Mesh) -> Optional[str]:
    """Return an error string if any param dim doesn't divide by its mesh
    axes (callers can fall back to replication or a smaller mesh)."""
    specs = param_pspecs(params)

    def _chk(path, leaf, spec):
        for dim, axes in zip(np.shape(leaf), spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % total:
                return f"{'/'.join(map(str, path))}: dim {dim} % {axes}={total}"
        return None

    flat_p, _ = jax.tree_util.tree_flatten_with_path(params)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    for (path, leaf), spec in zip(flat_p, flat_s):
        err = _chk([getattr(k, "key", k) for k in path], leaf, spec)
        if err:
            return err
    return None

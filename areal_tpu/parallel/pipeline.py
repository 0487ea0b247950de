"""Pipeline parallelism: GPipe-style microbatch pipelining over the `pipe`
mesh axis.

Capability parity: realhf/impl/model/parallelism/pipeline_parallel/
(static_schedule.py InferenceSchedule/TrainSchedule + backend/pipe_runner.py)
— re-designed for XLA instead of an interpreted instruction stream:

- Layer-stacked block params are sharded over `pipe` on their leading axis
  (areal_tpu/parallel/sharding.py), so stage s holds layers
  [s*L/P, (s+1)*L/P).
- The schedule is ONE `lax.scan` over M + P - 1 ticks inside a `shard_map`
  that manualizes only the pipe axis (`axis_names={"pipe"}`); tensor/fsdp/
  seq axes stay under GSPMD inside each stage.  Each tick every stage runs
  its local layers on its current microbatch and hands the activation to the
  next stage with `ppermute` — XLA overlaps the transfer with the next
  tick's compute.
- Backward is plain autodiff through the scan: the transposed ppermutes
  run the reverse pipeline, giving the 1F1B-equivalent dataflow without an
  instruction VM.  `jax.checkpoint` around the per-tick stage body keeps
  activation memory at one microbatch per stage.
- Bubble fraction is (P-1)/(M+P-1), the GPipe bound; callers pick
  n_microbatches >= 4*P to amortize.

Generation under PP (the reference's GenerateSchedule token feedback loop)
is not routed through this module: decode is latency-bound and runs on
pipe=1 meshes; see areal_tpu/engines/generator.py.
"""

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.base.topology import PIPE_AXIS, SEQ_AXIS


def _stage_scan(blocks_local, cfg, use_flash, cp_manual, x, seg, cos, sin):
    """Run this stage's local layer stack on one microbatch."""
    from areal_tpu.models.transformer import _block_forward

    def body(carry, blk):
        y, aux, _ = _block_forward(
            carry, blk, cfg, seg, cos, sin, use_flash, cp_manual=cp_manual
        )
        return y, aux

    y, auxes = jax.lax.scan(body, x, blocks_local)
    return y, jnp.sum(auxes)


def pipelined_blocks(
    blocks: Dict[str, jax.Array],
    cfg,
    x: jax.Array,  # [B, S, D] embedded activations
    segment_ids: jax.Array,  # [B, S]
    cos: jax.Array,
    sin: jax.Array,
    mesh: Mesh,
    n_microbatches: int,
    use_flash: "bool | None" = False,
    cp: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Transformer block stack under pipeline parallelism -> (y, aux_loss).

    `cp=True` composes ring context parallelism INSIDE each stage: the
    shard_map manualizes BOTH pipe and seq, every stage computes on its
    local sequence chunk (all stage ops are per-token except attention),
    and attention runs the ring body (`ops/ring_attention._ring_shard`)
    directly on the chunk.  Nesting a fresh seq shard_map per stage is
    NOT used — jax rejects that composition once operands vary over the
    manual pipe axis (and silently mistrains under check_vma=False).

    `n_microbatches` is a REQUEST: the schedule uses the largest multiple
    of `pipe` that divides B and is <= the request (padding rows only up
    to B % pipe == 0 beats forcing B % 4P == 0 — the reference's
    TrainSchedule likewise takes whatever microbatch count the batch
    admits).  Requires B % pipe == 0 and n_layers % pipe == 0.
    """
    n_stages = mesh.shape[PIPE_AXIS]
    b = x.shape[0]
    if b % n_stages:
        raise ValueError(
            f"batch rows {b} not divisible by {n_stages} pipe stages"
        )
    m = max(n_stages, min(n_microbatches, b))
    m -= m % n_stages
    while b % m:
        m -= n_stages
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"{cfg.n_layers} layers not divisible by {n_stages} pipe stages"
        )
    cp_manual = None
    if cp:
        n_seq = mesh.shape[SEQ_AXIS]
        if x.shape[1] % n_seq:
            raise ValueError(
                f"row length {x.shape[1]} not divisible by seq={n_seq}"
            )
        if cfg.is_moe and cfg.moe_dispatch == "topk":
            # Capacity dispatch computes expert capacity from the tokens
            # it SEES: per-(CP-chunk, microbatch) capacity would silently
            # differ from the global dispatch the non-pipelined CP path
            # computes (different drops => different numerics).  The
            # dropless dispatches ("grouped", "dense") are per-token
            # chunk-invariant and pass through; only the load-balancing
            # aux becomes a mean of per-chunk terms instead of the global
            # batch term (gradient pressure per chunk, same fixed point).
            raise NotImplementedError(
                "capacity (topk) MoE under combined CP + PP; use "
                "moe_dispatch='grouped' (dropless, chunk-invariant)"
            )
        cp_manual = (SEQ_AXIS, n_seq)
        use_flash = False  # dense ring blocks inside the manual region

    def to_mbs(t):
        return t.reshape(m, b // m, *t.shape[1:])

    x_mbs, seg_mbs = to_mbs(x), to_mbs(segment_ids)
    cos_mbs, sin_mbs = to_mbs(cos), to_mbs(sin)

    def pipe_body(sids, qids, blocks_local, x_mbs, seg_mbs, cos_mbs, sin_mbs):
        # The stage / seq-chunk indices arrive as inputs sharded over
        # their own axes, so each member reads its index from element 0.
        stage = sids[0]
        cp_info = cp_manual and (*cp_manual, qids[0])
        fwd = functools.partial(
            _stage_scan, blocks_local, cfg, use_flash, cp_info
        )
        fwd = jax.checkpoint(
            fwd, policy=jax.checkpoint_policies.nothing_saveable
        )
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        outputs = jnp.zeros_like(x_mbs)
        aux0 = jnp.zeros((), jnp.float32)
        recv = jnp.zeros_like(x_mbs[0])

        def tick(carry, t):
            recv, outputs, aux_sum = carry
            # Stage s works on microbatch (t - s) this tick.
            mb = jnp.clip(t - stage, 0, m - 1)
            feed = jnp.where(t - stage < m, x_mbs[jnp.clip(t, 0, m - 1)], 0.0)
            inp = jnp.where(stage == 0, feed, recv)
            seg1 = seg_mbs[mb]
            out, aux = fwd(inp, seg1, cos_mbs[mb], sin_mbs[mb])
            valid = (t - stage >= 0) & (t - stage < m)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
            # The last stage finishes microbatch (t - (P-1)) at tick t.
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            write = valid & (stage == n_stages - 1)
            slot = jax.lax.dynamic_index_in_dim(
                outputs, out_idx, 0, keepdims=False
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, slot), out_idx, 0
            )
            if n_stages > 1:
                recv = jax.lax.ppermute(out, PIPE_AXIS, perm)
            return (recv, outputs, aux_sum), None

        (recv, outputs, aux_sum), _ = jax.lax.scan(
            tick,
            (recv, outputs, aux0),
            jnp.arange(m + n_stages - 1, dtype=jnp.int32),
        )
        # Only the last stage holds real outputs; replicate over the pipe
        # axis (stages' own garbage is zeroed by masking before the psum).
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        outputs = jax.lax.psum(outputs, PIPE_AXIS)
        # Aux (MoE balancing) is an intensive per-layer statistic; average
        # over microbatches so it matches the non-pipelined scan's scale.
        # (Under CP aux stays pipe-summed only: MoE is fenced there.)
        aux_sum = jax.lax.psum(aux_sum, PIPE_AXIS) / m
        return outputs, aux_sum

    # Under CP the seq axis is manual too: activations/segments/rotary
    # tables enter as per-chunk shards ([m, rows, S/n_seq, ...]).
    seq = SEQ_AXIS if cp_manual else None
    act = P(None, None, seq)
    fn = shard_map(
        pipe_body,
        mesh=mesh,
        in_specs=(
            P(PIPE_AXIS),
            P(SEQ_AXIS) if cp_manual else P(),
            P(PIPE_AXIS),
            act, act, act, act,
        ),
        out_specs=(act, P()),
        axis_names={PIPE_AXIS, SEQ_AXIS} if cp_manual else {PIPE_AXIS},
        check_vma=False,
    )
    sids = jnp.arange(n_stages, dtype=jnp.int32)
    qids = jnp.arange(
        mesh.shape[SEQ_AXIS] if cp_manual else 1, dtype=jnp.int32
    )
    y_mbs, aux = fn(sids, qids, blocks, x_mbs, seg_mbs, cos_mbs, sin_mbs)
    return y_mbs.reshape(b, *x.shape[1:]), aux

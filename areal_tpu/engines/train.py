"""Training engine: optax + GSPMD-FSDP, micro-batched grad accumulation.

Capability parity: realhf/impl/model/backend/megatron.py (`ReaLMegatronEngine`
— DDP + DistributedOptimizer/ZeRO-1 + grad-accum train_batch) and
backend/mock_train.py — redesigned for TPU:

- ZeRO/FSDP is not an optimizer wrapper but a sharding: master params (fp32)
  and optimizer state carry the same NamedShardings as the model pytree
  (fsdp/model axes), so optimizer math is automatically distributed.
- Mixed precision Megatron-style: fp32 master params, bf16 compute — the
  jitted step casts to the model's compute dtype inside the graph (XLA fuses
  the casts into the matmuls).
- Grad accumulation across micro-batches keeps one jitted grad_fn and one
  jitted apply_fn regardless of the number of micro-batches, with
  token-weighted loss normalization matching the reference
  (pipe_runner.py loss normalization across mbs).
"""

import functools
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import Engine, FinetuneSpec, OptimizerConfig
from areal_tpu.base import faults, integrity, logging, tracer
from areal_tpu.base.distributed import is_primary, to_host
from areal_tpu.engines import packing
from areal_tpu.engines.offload import HostOffloadMixin
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import DENSE_PREFIX, FROZEN_LEAVES, ModelConfig
from areal_tpu.parallel import realloc, sharding

logger = logging.getLogger("train_engine")


def make_lr_schedule(cfg: OptimizerConfig, total_steps: int):
    warmup = max(int(total_steps * cfg.warmup_steps_proportion), 0)
    floor = cfg.lr * cfg.min_lr_ratio
    decay = max(total_steps - warmup, 1)
    if cfg.lr_scheduler_type == "constant":
        main = optax.constant_schedule(cfg.lr)
    elif cfg.lr_scheduler_type == "linear":
        main = optax.linear_schedule(cfg.lr, floor, decay)
    elif cfg.lr_scheduler_type == "cosine":
        main = optax.cosine_decay_schedule(cfg.lr, decay, alpha=cfg.min_lr_ratio)
    else:
        raise ValueError(f"unknown lr_scheduler_type {cfg.lr_scheduler_type!r}")
    if warmup == 0:
        return main
    return optax.join_schedules(
        [optax.linear_schedule(0.0, cfg.lr, warmup), main], [warmup]
    )


def make_optimizer(
    cfg: OptimizerConfig, total_steps: int, trainable=None
) -> optax.GradientTransformation:
    """`trainable`: a pytree of bools over the params, False for the leaves
    no optimizer may move (`_trainable_mask`); Adam then keeps no moment
    for them, no weight decay reaches them, and their update is their
    gradient, which is zero.  None: every leaf is trained."""
    sched = make_lr_schedule(cfg, total_steps)
    chain = []
    if cfg.gradient_clipping and cfg.gradient_clipping > 0:
        chain.append(optax.clip_by_global_norm(cfg.gradient_clipping))
    adamw = optax.adamw(
        learning_rate=sched,
        b1=cfg.beta1,
        b2=cfg.beta2,
        eps=cfg.eps,
        weight_decay=cfg.weight_decay,
    )
    chain.append(adamw if trainable is None else optax.masked(adamw, trainable))
    return optax.chain(*chain)


def _trainable_mask(params):
    """`make_optimizer`'s mask: False for the block leaves of
    `FROZEN_LEAVES`; None where the model has none (every family but the
    sigmoid-routed one), so their optimizer is what it was."""
    blocks = params.get("blocks", {})
    frozen = [  # a leading layer's leaf is its layer's own under `dense_`
        n for n in blocks if n.removeprefix(DENSE_PREFIX) in FROZEN_LEAVES]
    if not frozen:
        return None
    mask = jax.tree.map(lambda _: True, params)
    for n in frozen:
        mask["blocks"][n] = False
    return mask


@functools.lru_cache(maxsize=None)
def _moments_fn(value_keys: Tuple[str, ...], mask_key: str):
    @jax.jit
    def f(batch):
        mask = batch[mask_key] > 0
        out = {"count": mask.sum().astype(jnp.float32)}
        for k in value_keys:
            v = jnp.where(mask, batch[k].astype(jnp.float32), 0.0)
            out[k] = jnp.stack([v.sum(), (v * v).sum(), jnp.abs(v).sum()])
        return out

    return f


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def _moe_stats(aux, counts, cfg: ModelConfig, pairs: int) -> Dict[str, jax.Array]:
    """A micro-batch's MoE counters (plain keys: averaged over micro-batches
    by `train_batch`): the router's load-balancing loss as added to the
    objective, and the real tokens on the fullest expert over the mean per
    expert, mean over layers (1.0 = perfectly balanced; `counts` is None
    under PP, which reports the loss alone).  Where the grouped dispatch
    works on a slab of the layer's `pairs` (row, choice) pairs — a rank's
    share of under half its router, `transformer._experts_grouped` — two
    more: the real tokens' pairs held here over the slab's rows, max over
    layers (past 1.0 the overflow ran), and the rows gathered for them over
    `pairs` in %, mean over layers (the dispatch's own loop bound,
    `expert_slabs_run`: one slab, and one more for every slab's worth held
    beyond it).  A slab's dispatch leaves pads out (`_mlp_moe`), so `counts`
    there is its group sizes and these count what it did."""
    out = {"moe/aux_loss": jax.lax.stop_gradient(aux)}
    if counts is not None:
        c = counts.astype(jnp.float32)  # [L, E]
        out["moe/load_max_over_mean"] = jnp.mean(
            c.max(axis=-1) / jnp.maximum(c.mean(axis=-1), 1e-9)
        )
        slab = tfm.expert_slab_rows(cfg, pairs)
        if slab < pairs and cfg.moe_dispatch == "grouped":
            held = counts.sum(axis=-1)
            slabs = tfm.expert_slabs_run(slab, pairs, held)
            out["moe/slab_fill_max"] = jnp.max(held / slab)
            out["moe/rows_gathered_share"] = jnp.mean(
                jnp.minimum(slabs * slab, pairs) * (100.0 / pairs)
            )
    return out


def _model_out(params, cfg: ModelConfig, x, batch, mesh: Mesh):
    """Per-token model output [B, S] from final hidden states (see
    transformer.per_token_output)."""
    if cfg.block_length:  # two streams a sequence: labels in place
        return tfm.block_token_output(
            params, cfg, x, batch["labels"], batch["label_mask"],
            batch["head_index"], mesh=mesh)
    return tfm.per_token_output(
        params, cfg, x, batch["tokens"], batch["segment_ids"], mesh=mesh
    )


_GRID_COUNTS = (
    "real_tokens", "grid_tokens", "n_rows", "empty_rows",
    "flash_live_tiles", "flash_grid_tiles",
)
_WINDOW_TILES = "flash_live_tiles_window"  # a plan with window layers only


def _grid_counts(
    chunks: Sequence[Dict[str, np.ndarray]], window: Optional[int] = None
) -> Dict[str, int]:
    """What a call's packed grids hold: real tokens against grid cells,
    rows against rows with no real token (under batch sharding an empty
    row is a chip that trains zeros), and the attention tiles the flash
    kernels visit against the rows' full squares — `window`: also the
    tiles a sliding-window layer's schedule visits
    (`flash_live_tiles_window`; `flash_live_tiles` stays the full layers').
    Rows and tiles also go to the tracer's `pack` counter track."""
    real = [c["segment_ids"] > 0 for c in chunks]
    tiles = [packing.flash_tile_counts(c["segment_ids"]) for c in chunks]
    counted = {
        "n_rows": sum(r.shape[0] for r in real),
        "empty_rows": sum(int((~r.any(axis=1)).sum()) for r in real),
        "flash_live_tiles": sum(live for live, _ in tiles),
        "flash_grid_tiles": sum(grid for _, grid in tiles),
    }
    if window is not None:
        counted[_WINDOW_TILES] = sum(
            packing.flash_tile_counts(c["segment_ids"], window=window)[0]
            for c in chunks
        )
    tracer.counter("pack", **counted)
    return {
        "real_tokens": sum(int(r.sum()) for r in real),
        "grid_tokens": sum(r.size for r in real),
        **counted,
    }


class TrainEngine(HostOffloadMixin, Engine):
    """Engine holding fp32 master params + optimizer state on a mesh."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        mesh: Mesh,
        optimizer_config: Optional[OptimizerConfig] = None,
        ftspec: Optional[FinetuneSpec] = None,
        compute_dtype=jnp.bfloat16,
        # Master-weight / Adam-moment dtype.  fp32 is the Megatron-style
        # default; bf16 halves optimizer memory (params+mu+nu: 12 vs 6
        # bytes/param) for memory-bound single-chip configs — the tradeoff
        # large-model recipes make when HBM, not accuracy, binds.
        master_dtype=jnp.float32,
        # Activation rematerialization per layer: "full" (save nothing),
        # "dots" (save ALL matmul outputs; ~zero recompute when they
        # fit), "dots_small" (save only the two per-layer residual-
        # branch outputs — ~1/8 of "dots" memory, recomputes most of
        # the layer), "none".  See models/transformer.py _backbone.
        remat_policy: str = "full",
        # Pipeline schedule (pipe>1 meshes only):
        #   "gpipe"    — up to 4P in-flight microbatches; bubble
        #                (P-1)/(5P-1), backward residuals for all of them;
        #   "1f1b-mem" — P in-flight microbatches per jitted step: peak
        #                activation memory drops to 1F1B's O(P) bound
        #                (reference: static_schedule.py:323 TrainSchedule),
        #                amortization comes from the engine's grad-
        #                accumulation loop across micro-batches instead of
        #                intra-schedule interleaving (more bubble ticks —
        #                the memory/throughput trade is the caller's).
        pipe_schedule: str = "gpipe",
        # Anomaly sentinels (the numerical-integrity guard plane).
        # Non-finite loss/grad detection is ALWAYS on — a NaN update is
        # never worth applying.  The tunable sentinels default off:
        #   anomaly_grad_norm_mult M > 1: quarantine when the grad norm
        #     exceeds M x a running EWMA of clean-step grad norms (the
        #     EWMA only starts judging after `anomaly_ewma_warmup` clean
        #     steps, so early-training norm drift doesn't trip it);
        #   anomaly_update_norm_max > 0: absolute ceiling on the post-
        #     optimizer update norm.
        # All verdicts are computed inside the jitted apply and returned
        # as ONE packed scalar vector, so the guard costs a single extra
        # host sync per train step and zero retraces.
        anomaly_grad_norm_mult: float = 0.0,
        anomaly_update_norm_max: float = 0.0,
        anomaly_ewma_warmup: int = 5,
    ):
        self.cfg = cfg
        # The band the window layers' flash schedule keeps, and with it one
        # more of `_grid_counts`' keys.
        self._flash_window = next(
            (b.flash_window(cfg) for b in tfm.branches_of(cfg).values()
             if b.flash_window), None)
        self._grid_keys = _GRID_COUNTS + (
            (_WINDOW_TILES,) if self._flash_window else ())
        self.mesh = mesh
        self.optimizer_config = optimizer_config or OptimizerConfig()
        self.ftspec = ftspec or FinetuneSpec()
        # On CPU tests bf16 matmuls are slow and loose; use fp32 there.
        if jax.default_backend() == "cpu":
            compute_dtype = jnp.float32
        self.compute_dtype = compute_dtype
        self.master_dtype = master_dtype
        self.remat_policy = remat_policy

        self.param_specs = sharding.param_pspecs(params)
        self.param_shardings = sharding.tree_named(mesh, self.param_specs)
        # Master copy, sharded.
        params = _cast_tree(params, master_dtype)
        self.params = jax.device_put(params, self.param_shardings)
        self.optimizer = make_optimizer(
            self.optimizer_config, max(self.ftspec.total_train_steps, 1),
            _trainable_mask(params),
        )

        # Optimizer state mirrors the params (ZeRO-1): every param-shaped
        # leaf (Adam's mu/nu) is born with its param's sharding, the rest
        # (step counts) replicated over the mesh.  Left to the SPMD
        # partitioner, `zeros_like` outputs come back REPLICATED — full-size
        # moments on every chip.  The apply jits pin their out_shardings to
        # these, so the params/opt/guard carry run through train steps with
        # byte-identical cache keys — one compiled executable per apply fn
        # for the whole trial, checkpoint restores included.
        self.opt_shardings = self._opt_state_shardings()
        self.opt_state = jax.jit(
            self.optimizer.init, out_shardings=self.opt_shardings
        )(self.params)

        if 0.0 < anomaly_grad_norm_mult <= 1.0:
            raise ValueError(
                "anomaly_grad_norm_mult must be > 1 when set (got "
                f"{anomaly_grad_norm_mult}); 0 disables the spike sentinel"
            )
        self.anomaly_grad_norm_mult = float(anomaly_grad_norm_mult)
        self.anomaly_update_norm_max = float(anomaly_update_norm_max)
        self.anomaly_ewma_warmup = int(anomaly_ewma_warmup)
        # (EWMA of clean-step grad norms, clean-step count) — traced args
        # of the guarded apply, so their evolution never retraces.
        self._guard_state = None
        self._faults = faults.FaultInjector.from_env()
        # Counts batched device->host stat transfers; chaos legs assert
        # exactly one per train_batch / stream chunk / stream end call.
        self.host_transfers = 0

        self._grad_fns: Dict[Any, Callable] = {}
        self._fwd_fns: Dict[Any, Callable] = {}
        self._apply_fn = None
        self._scaled_apply_fn = None
        self._batch_sharding = sharding.named(mesh, sharding.batch_pspec())
        (
            self._use_flash,
            self._cp_mesh,
            self._pp_mesh,
            self._pp_microbatches,
            self.batch_shard,
        ) = sharding.attn_dispatch(mesh, cfg)
        # What the gradient program's expert matmuls take (a grouped MoE
        # model's): None, the backend's choice, on one device; XLA's ragged
        # kernel on a mesh (the Pallas grouped matmul is one device's
        # program).
        self._expert_kernel = None if mesh.devices.size == 1 else False
        # What a Gated DeltaNet layer's chunked rule takes over packed rows
        # (`linear_attention.linear_attn_forward`): None, the backend's
        # form, on one device; the MESH where there are more.
        self._row_kernel = None if mesh.devices.size == 1 else mesh
        # (expert matmuls, those of them on `grouped_matmul`) of the
        # gradient program as traced last: counted while tracing.
        self._expert_matmuls = (0, 0)
        if pipe_schedule not in ("gpipe", "1f1b-mem"):
            raise ValueError(f"unknown pipe_schedule {pipe_schedule!r}")
        self.pipe_schedule = pipe_schedule
        if self._pp_mesh is not None and pipe_schedule == "1f1b-mem":
            self._pp_microbatches = self._pp_mesh.shape[
                sharding.PIPE_AXIS
            ]
        # Ways the log-prob head splits the vocabulary under this mesh
        # (ops/functional.fused_next_token_logprobs decides the same way
        # when traced); a critic has no such head.
        self.head_vocab_shards = (
            1
            if cfg.is_critic
            else sharding.head_vocab_shards(mesh, cfg.vocab_size)
        )
        # Lazy byte-size cache for perf_counters(): param/opt global
        # bytes never change shape after init, so sum the leaves once.
        self._tree_bytes: Optional[Tuple[int, int]] = None

    def _opt_state_shardings(self):
        """A leaf of the optimizer state whose tree path ends in a param's
        path (mu['blocks']['wq'] ...) is that param's moment."""
        by_path = dict(
            jax.tree_util.tree_flatten_with_path(self.param_shardings)[0]
        )
        replicated = sharding.named(self.mesh, P())

        def pick(path, leaf):
            for i in range(len(path)):
                if path[i:] in by_path:
                    return by_path[path[i:]]
            return replicated

        return jax.tree_util.tree_map_with_path(
            pick, jax.eval_shape(self.optimizer.init, self.params)
        )

    def perf_counters(self) -> Dict[str, int]:
        """Memory/compile counters for the worker's MFC spans (profile
        store fields; analysis/profile.py _WATERMARK_ARGS): global
        param/optimizer bytes plus the engine's jit-trace surface."""
        if self._tree_bytes is None:
            self._tree_bytes = (
                sum(int(x.nbytes) for x in jax.tree.leaves(self.params)),
                sum(int(x.nbytes) for x in jax.tree.leaves(self.opt_state)),
            )
        compiles = 0
        for gf, gaf in self._grad_fns.values():
            compiles += gf._cache_size() + gaf._cache_size()
        for fn in (self._apply_fn, self._scaled_apply_fn):
            if fn is not None:
                compiles += fn._cache_size()
        return {
            "param_bytes": self._tree_bytes[0],
            "opt_bytes": self._tree_bytes[1],
            "compiles": compiles,
        }

    # ---------------- core jitted fns ----------------

    # Two streams a sequence (`cfg.block_length`: `packing._pack_streams`).

    def _mb_split(self, mb_spec: MicroBatchSpec) -> MicroBatchSpec:
        """How a batch is split into micro-batches before it is packed: by
        the plan's tokens a micro-batch — but where a sequence lies in its
        row as two streams, by `n_mbs` alone: the budget counts STREAM
        slots, which `pack_sample` fills a row with, and `_pack_row_chunks`
        hands the gradient program a row a device at a time."""
        if not self.cfg.block_length:
            return mb_spec
        return MicroBatchSpec(n_mbs=mb_spec.n_mbs)

    def _stream_args(self, extra_keys) -> Dict[str, Any]:
        """`pack_sample`'s arguments for a model with `block_length`; the
        tokens that want a log-prob are the loss mask's where the call has
        one, else every token."""
        cfg = self.cfg
        if not cfg.block_length:
            return {}
        return dict(
            block_length=cfg.block_length, mask_token_id=cfg.mask_token_id,
            wanted_key="loss_mask" if "loss_mask" in extra_keys else None)

    @staticmethod
    def _stream_stats(packs) -> Dict[str, float]:
        """`last_pack_stats`' `bd/*` keys of a call's packs (none for a
        model without streams): slots of the clean and the masked streams,
        the pad that aligns a stream to a block, the rows the head read,
        and stream slots over the tokens trained (`bd/stream_overhead`)."""
        if not packs or not packs[0].stats:
            return {}
        total = {k: sum(p.stats[k] for p in packs) for k in packs[0].stats}
        slots = total["clean_slots"] + total["masked_slots"]
        tracer.counter("bd_pack", **total)
        return {
            **{f"bd/{k}": float(v) for k, v in total.items()},
            "bd/stream_overhead": slots / max(total["clean_slots"], 1),
        }

    def _pack_row_chunks(self, arrays, max_tokens: Optional[int] = None):
        """Rows per jitted step, capped at batch_shard in two cases.
        1f1b-mem schedule (batch_axes x P, i.e. exactly P in-flight
        microbatches of minimal size): peak activation memory per step sits
        at the 1F1B bound; the surrounding grad-accumulation loop supplies
        the amortization GPipe gets from 4P in-flight microbatches.  And a
        micro-batch whose rows are LONGER than `max_tokens` (the plan's
        tokens a micro-batch: `pack_sample` widens a row to the longest
        sequence, and a sample's group of sequences stays one micro-batch,
        so a group of four 13 k-token sequences is four rows of 13,312): a
        step then takes one row a device, and the step's tokens stay near
        the bound the plan set (the same loop accumulates)."""
        b, row_len = arrays["segment_ids"].shape
        long_rows = bool(max_tokens) and (
            row_len > max_tokens
            # Two streams a sequence: the micro-batch was split by tokens
            # and its rows hold stream slots, more than `max_tokens` of
            # them — a step takes a row a device all the same.
            or (bool(self.cfg.block_length) and b * row_len > max_tokens))
        pipelined = self.pipe_schedule == "1f1b-mem" and self._pp_mesh is not None
        if not (long_rows or pipelined):
            return [arrays]
        cap = self.batch_shard
        if b <= cap:
            return [arrays]
        return [
            {k: v[i : i + cap] for k, v in arrays.items()}
            for i in range(0, b, cap)
        ]

    def _grad_compiler_options(self) -> Dict[str, Any]:
        """What the gradient programs are compiled with beside the
        defaults: what the plan's kinds ask for (`Branch.grad_options`)."""
        out = {}
        for branch in tfm.branches_of(self.cfg).values():
            if branch.grad_options:
                out.update(branch.grad_options(self.cfg, self._row_kernel))
        return out

    def _get_grad_fn(self, loss_fn: Callable):
        if loss_fn in self._grad_fns:
            return self._grad_fns[loss_fn]
        cfg, compute_dtype, mesh = self.cfg, self.compute_dtype, self.mesh
        use_flash = self._use_flash
        cp_mesh = self._cp_mesh
        pp_mesh, pp_mbs = self._pp_mesh, self._pp_microbatches
        remat = self.remat_policy
        expert_kernel = self._expert_kernel
        row_kernel = self._row_kernel

        def _value_and_grad(params, batch, loss_scale):
            def losswrap(p):
                pc = _cast_tree(p, compute_dtype)
                traced = tfm.expert_matmuls_traced()
                x, aux, counts = tfm.hidden_states(
                    pc,
                    cfg,
                    batch["tokens"],
                    batch["segment_ids"],
                    positions=batch["positions"],
                    remat=remat,
                    use_flash=use_flash,
                    cp_mesh=cp_mesh,
                    pp_mesh=pp_mesh,
                    pp_microbatches=pp_mbs,
                    with_moe_counts=True,
                    expert_kernel=expert_kernel,
                    row_kernel=row_kernel,
                    stream_ids=batch.get("stream_ids"),
                )
                self._expert_matmuls = tuple(
                    b - a for a, b in zip(traced, tfm.expert_matmuls_traced())
                )
                # Loss fns receive per-token model outputs, never [B,S,V]
                # logits: critic -> values; LM -> fused chunked next-token
                # logprobs (the 152k-vocab memory/bandwidth fix).
                out = _model_out(pc, cfg, x, batch, mesh)
                loss, stats = loss_fn(out, batch)
                total = loss + cfg.moe_aux_loss_coef * aux
                if cfg.is_moe:
                    stats = {
                        **stats,
                        **_moe_stats(
                            aux, counts, cfg,
                            batch["tokens"].size * cfg.n_experts_per_tok,
                        ),
                    }
                for name, branch in tfm.branches_of(cfg).items():
                    if branch.train_stats:
                        stats = {**stats, **branch.train_stats(
                            cfg, cfg.plan.count(name), batch["segment_ids"],
                            row_kernel)}
                return total * loss_scale, stats

            with jax.named_scope("train/grad"):
                return jax.value_and_grad(losswrap, has_aux=True)(params)

        compact = self._grad_compiler_options()

        @functools.partial(jax.jit, compiler_options=compact)
        def grad_fn(params, batch, loss_scale):
            (loss, stats), grads = _value_and_grad(params, batch, loss_scale)
            return grads, loss, stats

        # Fused accumulate: the running grad sum is DONATED and updated
        # in-graph, so accumulation never holds two full grad trees — the
        # term that pushes large single-chip configs out of HBM.
        @functools.partial(
            jax.jit, donate_argnums=(3,), compiler_options=compact)
        def grad_acc_fn(params, batch, loss_scale, acc):
            (loss, stats), grads = _value_and_grad(params, batch, loss_scale)
            return jax.tree.map(jnp.add, acc, grads), loss, stats

        self._grad_fns[loss_fn] = (grad_fn, grad_acc_fn)
        return self._grad_fns[loss_fn]

    @jax.named_scope("train/apply")
    def _guarded_step(self, params, opt_state, grads, guard, loss_sum, ext_trip):
        """In-graph guarded optimizer step (traced inside the apply jits).

        Computes the anomaly verdict, applies the update ONLY when the
        verdict is clean (per-leaf `jnp.where` select, so the donated
        buffers stay reusable and a quarantined step returns the original
        params/opt_state bit-identically), and advances the grad-norm
        EWMA on clean steps.  Thresholds are Python constants captured at
        closure build time; everything data-dependent (verdict, guard,
        ext_trip) is traced — clean and quarantined steps share one trace.
        """
        optimizer = self.optimizer
        mult = self.anomaly_grad_norm_mult
        unorm_max = self.anomaly_update_norm_max
        warmup = float(self.anomaly_ewma_warmup)

        gnorm = optax.global_norm(grads)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        unorm = optax.global_norm(updates)

        ewma, count = guard[0], guard[1]
        finite = jnp.isfinite(gnorm) & jnp.isfinite(loss_sum)
        verdict = jnp.where(finite, 0, integrity.NONFINITE).astype(jnp.int32)
        if mult > 0.0:
            # NaN gnorm compares False, so a non-finite step never
            # double-counts as a spike.
            spike = (count >= warmup) & (gnorm > mult * ewma)
            verdict = verdict + jnp.where(spike, integrity.GRAD_SPIKE, 0)
        if unorm_max > 0.0:
            ceil = finite & (unorm > unorm_max)
            verdict = verdict + jnp.where(ceil, integrity.UPDATE_NORM, 0)

        ok = (verdict == 0) & (ext_trip == 0)
        out_params = jax.tree.map(
            lambda new, old: jnp.where(ok, new, old), new_params, params
        )
        out_opt = jax.tree.map(
            lambda new, old: jnp.where(ok, new, old), new_opt, opt_state
        )
        # The EWMA tracks CLEAN grad norms only: a quarantined spike must
        # not drag the baseline up, or a spike streak would self-absolve.
        new_ewma = jnp.where(
            ok,
            jnp.where(count > 0, 0.9 * ewma + 0.1 * gnorm, gnorm),
            ewma,
        )
        new_count = count + jnp.where(ok, 1.0, 0.0)
        new_guard = jnp.stack([new_ewma, new_count])
        packed = jnp.stack(
            [
                loss_sum.astype(jnp.float32),
                gnorm.astype(jnp.float32),
                unorm.astype(jnp.float32),
                verdict.astype(jnp.float32),
            ]
        )
        return out_params, out_opt, new_guard, packed

    def _get_apply_fn(self):
        if self._apply_fn is not None:
            return self._apply_fn
        step = self._guarded_step

        # Donation: params/opt_state buffers are dead after the step —
        # without it the optimizer step transiently holds 2x params + 2x
        # Adam state, the peak-memory term for large models on one chip.
        # Grads are NOT donated: every param-shaped output is already
        # aliased to params/mu/nu, so a fourth donated set has no output to
        # land in and only earns jax's "donated buffers were not usable"
        # warning; the caller drops them right after the call.  The guarded
        # select keeps this safe on quarantined steps: jnp.where's output
        # may alias either input, and the original values only ever flow
        # out through the jit's own outputs.
        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1, 3),
            out_shardings=self._apply_out_shardings(),
        )
        def apply_fn(params, opt_state, grads, guard, loss_sum):
            return step(
                params, opt_state, grads, guard, loss_sum, jnp.float32(0.0)
            )

        self._apply_fn = apply_fn
        return apply_fn

    def _apply_out_shardings(self):
        """Output shardings for the guarded apply jits, pinned to the INPUT
        shardings of the state they round-trip.  Left unpinned, GSPMD is
        free to hand params back with collapsed specs (e.g. replicated on a
        1-device mesh), which changes the next call's cache key — the warm
        path would silently compile a second executable, and a checkpoint
        restore (device_put back to the canonical shardings) a third."""
        return (
            self.param_shardings,
            self.opt_shardings,
            sharding.named(self.mesh, P()),
            sharding.named(self.mesh, P()),
        )

    def _get_scaled_apply_fn(self):
        """Optimizer step for the streamed path: the grad sum was
        accumulated at unit loss_scale (the per-chunk weight is unknown
        until the stream closes), so scale by 1/total_weight here before
        clipping/AdamW.  Same donation story as `_get_apply_fn`; the
        extra `ext_trip` traced scalar lets the interface force a
        quarantine (batch-level sentinel tripped mid-stream) so the
        accumulated partial grads are discarded without a retrace."""
        if self._scaled_apply_fn is not None:
            return self._scaled_apply_fn
        step = self._guarded_step

        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1, 3),
            out_shardings=self._apply_out_shardings(),
        )
        def apply_fn(params, opt_state, grads, guard, loss_sum, scale, ext_trip):
            grads = jax.tree.map(lambda g: g * scale, grads)
            return step(params, opt_state, grads, guard, loss_sum, ext_trip)

        self._scaled_apply_fn = apply_fn
        return apply_fn

    def _guard(self):
        if self._guard_state is None:
            # Committed replicated placement, matching the apply jits'
            # pinned guard out_sharding — a fresh guard (first step, or a
            # post-rollback reset) keys identically to an evolved one.
            self._guard_state = jax.device_put(
                jnp.zeros(2, jnp.float32), sharding.named(self.mesh, P())
            )
        return self._guard_state

    def _poison_grads(self, acc):
        """`nan@point=train_grads` chaos hook: poison the accumulated
        grad sum in eager ops, outside every counted jit cache, so the
        injection itself cannot perturb trace-flatness accounting."""
        kind = self._faults.poison("train_grads") if self._faults else None
        if kind == "nan":
            logger.warning(
                "fault injection: NaN-poisoning grad sum (train_grads)"
            )
            return jax.tree.map(lambda g: g * np.float32("nan"), acc)
        return acc

    # ---------------- Engine API ----------------

    def train_batch(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: Callable,
        loss_weight_fn: Callable[[Dict[str, np.ndarray]], float],
        token_key: str = "packed_input_ids",
        extra_keys: Sequence[str] = (),
        version_steps: int = 0,
    ) -> Dict[str, float]:
        """Accumulate grads over micro-batches, then one optimizer step.

        loss_fn must return a *sum* over valid tokens; normalization across
        micro-batches uses `loss_weight_fn(batch) -> float` (e.g. number of
        loss tokens) so the final gradient equals the full-batch mean.
        """
        t_entry = time.monotonic()
        self._ensure_loaded()
        with tracer.span("pack", cat="host"):
            sharded_mbs = packing.split_sharded(
                sample, self._mb_split(mb_spec))
            packs = [
                packing.pack_sample(
                    mb,
                    token_key,
                    extra_keys=extra_keys,
                    n_rows_multiple=self.batch_shard,
                    max_tokens_per_row=mb_spec.max_tokens_per_mb,
                    shard_blocks=blocks,
                    **self._stream_args(extra_keys),
                )
                for mb, blocks in sharded_mbs
            ]
            # 1f1b-mem row chunking slices contiguous row ranges, which would
            # cut across the per-shard row blocks of a sharded batch; the two
            # compose only via the grad-accum loop, so skip chunking there.
            sharded = any(blocks for _, blocks in sharded_mbs)
            chunks = [
                c
                for pk in packs
                for c in (
                    [pk.arrays] if sharded else self._pack_row_chunks(
                        pk.arrays, mb_spec.max_tokens_per_mb)
                )
            ]
            total_weight = float(sum(loss_weight_fn(c) for c in chunks))
            total_weight = max(total_weight, 1.0)

            # Pack efficiency diagnostics: the MFU counter charges REAL
            # tokens, the MXU computes PADDED grids — the ratio is the
            # first thing to check when train MFU disappoints.
            grid = _grid_counts(chunks, self._flash_window)
            self.last_pack_stats = {
                **grid,
                "pack_efficiency": grid["real_tokens"]
                / max(grid["grid_tokens"], 1),
                "n_micro_batches": len(chunks),
                **self._stream_stats(packs),
            }

        grad_fn, grad_acc_fn = self._get_grad_fn(loss_fn)
        acc = None
        losses = []
        all_stats = []
        for arrays in chunks:
            with tracer.span("mb_upload", cat="comms"):
                batch = self._device_batch(arrays)
            with tracer.span("grad_dispatch", cat="compute"):
                scale = jnp.float32(1.0 / total_weight)
                if acc is None:
                    acc, loss, stats = grad_fn(self.params, batch, scale)
                else:
                    acc, loss, stats = grad_acc_fn(
                        self.params, batch, scale, acc
                    )
            losses.append(loss)
            all_stats.append(stats)

        with tracer.span("apply_dispatch", cat="compute"):
            acc = self._poison_grads(acc)
            loss_sum = jnp.sum(jnp.stack(losses))
            params, opt_state, self._guard_state, packed = (
                self._get_apply_fn()(
                    self.params, self.opt_state, acc, self._guard(),
                    loss_sum,
                )
            )
            self.params, self.opt_state = params, opt_state
            del acc  # a full grad tree: free it before the stats sync

        # Stats from loss_fn are summed across micro-batches then divided by
        # total weight where keys end in '_sum'; plain keys are averaged.
        # Both reductions happen ON DEVICE and ride the packed-verdict
        # vector, so the whole step pays exactly ONE device->host sync.
        keys = list(all_stats[0].keys()) if all_stats else []
        vec = [packed]
        if keys:
            with tracer.span("stats_reduce", cat="compute"):
                vec.append(
                    jnp.stack(
                        [
                            jnp.sum(jnp.stack([s[k] for s in all_stats]))
                            if k.endswith("_sum")
                            else jnp.mean(
                                jnp.stack([s[k] for s in all_stats])
                            )
                            for k in keys
                        ]
                    )
                )
        # Seconds the host itself spent on this step: everything before
        # it sits down to wait for the device's answer.
        self.last_pack_stats["host_s"] = time.monotonic() - t_entry
        tracer.counter("train_host", host_s=self.last_pack_stats["host_s"])
        with tracer.span("stats_sync", cat="compute"):
            host = np.asarray(jnp.concatenate(vec), np.float64)
        self.host_transfers += 1

        verdict = float(host[3])
        if verdict:
            integrity.record_anomaly(verdict)
        out: Dict[str, float] = {
            "loss": float(host[0]),
            "grad_norm": float(host[1]),
            "update_norm": float(host[2]),
            "anomaly_verdict": verdict,
            "quarantined": 1.0 if verdict else 0.0,
            "n_micro_batches": float(len(chunks)),
            "head/vocab_shards": float(self.head_vocab_shards),
        }
        if self.cfg.is_moe and self.cfg.moe_dispatch == "grouped":
            # Which kernel the gradient program's expert matmuls run on
            # (`grouped_matmul` where XLA's ragged kernel tiles the widths
            # badly): trace-time counts, no device work.
            calls, on_kernel = self._expert_matmuls
            out["moe/expert_matmul_calls"] = float(calls)
            out["moe/grouped_kernel_calls"] = float(on_kernel)
            tracer.counter(
                "moe_expert_kernel", expert_matmul_calls=calls,
                grouped_kernel_calls=on_kernel,
            )
        for i, k in enumerate(keys):
            v = float(host[4 + i])
            if k.endswith("_sum"):
                out[k[: -len("_sum")]] = v / total_weight
            else:
                out[k] = v
        return out

    # ---------------- streamed accumulation ----------------
    #
    # Pipeline-overlapped PPO feeds the trainer one rollout chunk at a
    # time while later chunks are still decoding; the donated grad-sum
    # loop above is reused as the accumulator, split across calls:
    #
    #   state = engine.train_stream_begin()
    #   for chunk: engine.train_stream_chunk(state, chunk_sample, ...)
    #   out = engine.train_stream_end(state)   # one optimizer step
    #
    # Chunks accumulate at unit loss_scale (the total token weight is
    # unknown mid-stream); `train_stream_end` scales the grad sum by
    # 1/total_weight inside the donated apply.  sum(g_i)/W equals the
    # barrier path's sum(g_i/W) up to float reassociation — the
    # bit-exact overlap-off guarantee comes from the master dispatching
    # window=1 steps through the unchanged `train_batch` path.

    def train_stream_begin(self) -> Dict[str, Any]:
        """Open a streamed accumulation window; returns mutable state."""
        self._ensure_loaded()
        return {
            "acc": None,
            "loss_sums": [],
            "stat_sums": {},
            "weight": 0.0,
            "n_micro_batches": 0,
            "n_chunks": 0,
            **dict.fromkeys(self._grid_keys, 0),
            "host_s": 0.0,
        }

    def train_stream_chunk(
        self,
        state: Dict[str, Any],
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: Callable,
        loss_weight_fn: Callable[[Dict[str, np.ndarray]], float],
        token_key: str = "packed_input_ids",
        extra_keys: Sequence[str] = (),
        version_steps: int = 0,
    ) -> Dict[str, float]:
        """Accumulate one chunk's grads into the stream's donated sum.

        Returns this chunk's raw stat sums (keys keep their `_sum`
        suffix) plus `chunk_weight` / `chunk_loss_sum` so callers can
        build `*_denominator`-weighted per-chunk stats.
        """
        t_entry = time.monotonic()
        with tracer.span("pack", cat="host"):
            sharded_mbs = packing.split_sharded(
                sample, self._mb_split(mb_spec))
            if any(blocks for _, blocks in sharded_mbs):
                raise ValueError(
                    "streamed accumulation does not compose with "
                    "shard-exact data placement (shard_of metadata); "
                    "broadcast chunk inputs or use the barrier train_batch "
                    "path"
                )
            packs = [
                packing.pack_sample(
                    mb,
                    token_key,
                    extra_keys=extra_keys,
                    n_rows_multiple=self.batch_shard,
                    max_tokens_per_row=mb_spec.max_tokens_per_mb,
                    **self._stream_args(extra_keys),
                )
                for mb, _ in sharded_mbs
            ]
            chunks = [
                c for pk in packs for c in self._pack_row_chunks(
                    pk.arrays, mb_spec.max_tokens_per_mb)
            ]
            chunk_weight = float(sum(loss_weight_fn(c) for c in chunks))
            for k, v in _grid_counts(chunks, self._flash_window).items():
                state[k] += v

        grad_fn, grad_acc_fn = self._get_grad_fn(loss_fn)
        scale = jnp.float32(1.0)  # traced arg: no retrace vs train_batch
        losses = []
        all_stats = []
        for arrays in chunks:
            with tracer.span("mb_upload", cat="comms"):
                batch = self._device_batch(arrays)
            with tracer.span("grad_dispatch", cat="compute"):
                if state["acc"] is None:
                    state["acc"], loss, stats = grad_fn(
                        self.params, batch, scale
                    )
                else:
                    state["acc"], loss, stats = grad_acc_fn(
                        self.params, batch, scale, state["acc"]
                    )
            losses.append(loss)
            all_stats.append(stats)
        # Host conversion AFTER the dispatch loop, as ONE batched
        # transfer (loss sum + every stat sum in a single stacked
        # vector): one sync per chunk, not per micro-batch or per stat;
        # the device-side sum also keeps the window=1 loss bit-identical
        # to train_batch's.
        chunk_loss = 0.0
        chunk_stats: Dict[str, float] = {}
        if losses:
            keys = list(all_stats[0].keys())
            vec = [jnp.sum(jnp.stack(losses))] + [
                jnp.sum(jnp.stack([s[k] for s in all_stats])) for k in keys
            ]
            state["host_s"] += time.monotonic() - t_entry
            with tracer.span("stats_sync", cat="compute"):
                host = np.asarray(jnp.stack(vec), np.float64)
            self.host_transfers += 1
            chunk_loss = float(host[0])
            chunk_stats = {k: float(host[1 + i]) for i, k in enumerate(keys)}

        state["weight"] += chunk_weight
        state["loss_sums"].append(chunk_loss)
        state["n_micro_batches"] += len(chunks)
        state["n_chunks"] += 1
        for k, v in chunk_stats.items():
            state["stat_sums"][k] = state["stat_sums"].get(k, 0.0) + v
        return {
            **chunk_stats,
            "chunk_weight": chunk_weight,
            "chunk_loss_sum": chunk_loss,
            "chunk_micro_batches": float(len(chunks)),
        }

    def train_stream_end(
        self, state: Dict[str, Any], quarantine: bool = False
    ) -> Dict[str, float]:
        """Close the stream: one scaled optimizer step over the grad sum.

        `quarantine=True` (a batch-level sentinel tripped mid-stream)
        forces the guarded apply to discard the accumulated partial
        grads: params/opt_state come back bit-identical, via the same
        traced select as an engine-level verdict — no retrace.
        """
        if state["acc"] is None:
            raise ValueError("train_stream_end before any train_stream_chunk")
        t_entry = time.monotonic()
        total_weight = max(state["weight"], 1.0)
        with tracer.span("apply_dispatch", cat="compute"):
            acc = self._poison_grads(state["acc"])
            loss_sum = jnp.float32(sum(state["loss_sums"]))
            params, opt_state, self._guard_state, packed = (
                self._get_scaled_apply_fn()(
                    self.params,
                    self.opt_state,
                    acc,
                    self._guard(),
                    loss_sum,
                    jnp.float32(1.0 / total_weight),
                    jnp.float32(1.0 if quarantine else 0.0),
                )
            )
            self.params, self.opt_state = params, opt_state
            state["acc"] = None  # consumed: free the grad tree

        self.last_pack_stats = {
            **{k: state[k] for k in self._grid_keys},
            "pack_efficiency": state["real_tokens"]
            / max(state["grid_tokens"], 1),
            "n_micro_batches": state["n_micro_batches"],
            "host_s": state["host_s"] + time.monotonic() - t_entry,
        }
        with tracer.span("stats_sync", cat="compute"):
            host = np.asarray(packed, np.float64)
        self.host_transfers += 1
        verdict = float(host[3])
        if verdict:
            integrity.record_anomaly(verdict)
        out: Dict[str, float] = {
            "loss": float(sum(state["loss_sums"])) / total_weight,
            "grad_norm": float(host[1]),
            "update_norm": float(host[2]),
            "anomaly_verdict": verdict,
            "quarantined": 1.0 if (verdict or quarantine) else 0.0,
            "n_micro_batches": float(state["n_micro_batches"]),
            "n_stream_chunks": float(state["n_chunks"]),
            "head/vocab_shards": float(self.head_vocab_shards),
        }
        for k, v in state["stat_sums"].items():
            if k.endswith("_sum"):
                out[k[: -len("_sum")]] = v / total_weight
            else:
                out[k] = v / max(state["n_micro_batches"], 1)
        return out

    def masked_moments(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        value_keys: Sequence[str],
        mask_key: str = "loss_mask",
        token_key: str = "packed_input_ids",
    ) -> Dict[str, Any]:
        """Exact batch-global masked reductions, computed ON DEVICE.

        Under sharded data dispatch each member's HOST arrays hold real
        values only for its own rows (the rest are zero-filled
        placeholders), but the PLACED arrays are globally real: every
        process contributes its own row block via
        `sharding.place_rows` / `jax.make_array_from_process_local_data`.
        A jitted global reduction over them is therefore exact and
        identical on every SPMD member — the in-mesh replacement for the
        full-batch redistribution that makes the reference's host-side
        batch statistics trivially global
        (realhf/system/data_manager.py:144-416).  PPO's batch-global
        advantage moments, ref-KL, and value-norm running moments ride
        this; without it those statistics would silently diverge across
        members (each seeing zeros for the others' rows).

        Returns {"count": N} plus, per value key, a float64 numpy vector
        `[masked_sum, masked_sum_of_squares, masked_abs_sum]`.  Values
        and mask must be token-aligned with `token_key`.
        """
        self._ensure_loaded()
        value_keys = tuple(value_keys)
        fn = _moments_fn(value_keys, mask_key)
        count = 0.0
        acc = {k: np.zeros(3, np.float64) for k in value_keys}
        for mb, blocks in packing.split_sharded(sample, mb_spec):
            pk = packing.pack_sample(
                mb,
                token_key,
                extra_keys=value_keys + (mask_key,),
                n_rows_multiple=self.batch_shard,
                max_tokens_per_row=mb_spec.max_tokens_per_mb,
                shard_blocks=blocks,
            )
            out = fn(self._device_batch(pk.arrays))
            count += float(out["count"])
            for k in value_keys:
                acc[k] += np.asarray(out[k], np.float64)
        acc["count"] = count
        return acc

    def forward(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        post_fn: Callable,
        output_key: str,
        token_key: str = "packed_input_ids",
        extra_keys: Sequence[str] = (),
        output_seqlens: Optional[list] = None,
    ) -> SequenceSample:
        """Forward-only pass; `post_fn(logits, batch) -> [B, S, ...]` runs
        inside jit (e.g. gather next-token logprobs).  Output is re-packed
        into a SequenceSample keyed `output_key`, token-aligned."""
        self._ensure_loaded()
        fwd = self._get_fwd_fn(post_fn)
        outs = []
        for mb, blocks in packing.split_sharded(
            sample, self._mb_split(mb_spec)
        ):
            pk = packing.pack_sample(
                mb,
                token_key,
                extra_keys=extra_keys,
                n_rows_multiple=self.batch_shard,
                max_tokens_per_row=mb_spec.max_tokens_per_mb,
                shard_blocks=blocks,
                **self._stream_args(extra_keys),
            )
            # Two streams a sequence: the pack's rows hold more stream
            # slots than the budget, a row a device a call (every other
            # model's pack is one call, the program it always was).
            chunks = [pk.arrays]
            if self.cfg.block_length and not blocks:
                chunks = self._pack_row_chunks(
                    pk.arrays, mb_spec.max_tokens_per_mb)
            dense = np.concatenate([
                to_host(fwd(self.params, self._device_batch(c)))
                for c in chunks])
            packed = pk.unpack(dense)
            out = SequenceSample(
                keys={output_key},
                ids=list(mb.ids),
                seqlens={output_key: [list(s) for s in mb.seqlens[token_key]]},
                data={output_key: packed},
            )
            outs.append(out)
        result = SequenceSample.gather(outs)
        # Restore original id order.
        order = {i: n for n, i in enumerate(result.ids)}
        return result.select_idx([order[i] for i in sample.ids])

    def _get_fwd_fn(self, post_fn):
        if post_fn in self._fwd_fns:
            return self._fwd_fns[post_fn]
        cfg, compute_dtype, mesh = self.cfg, self.compute_dtype, self.mesh
        use_flash = self._use_flash
        cp_mesh = self._cp_mesh
        pp_mesh, pp_mbs = self._pp_mesh, self._pp_microbatches
        row_kernel = self._row_kernel

        @jax.jit
        def fwd(params, batch):
            pc = _cast_tree(params, compute_dtype)
            x, _ = tfm.hidden_states(
                pc,
                cfg,
                batch["tokens"],
                batch["segment_ids"],
                positions=batch["positions"],
                use_flash=use_flash,
                cp_mesh=cp_mesh,
                pp_mesh=pp_mesh,
                pp_microbatches=pp_mbs,
                row_kernel=row_kernel,
                stream_ids=batch.get("stream_ids"),
            )
            return post_fn(_model_out(pc, cfg, x, batch, mesh), batch)

        self._fwd_fns[post_fn] = fwd
        return fwd

    def _device_batch(self, arrays: Dict[str, np.ndarray]):
        return {
            k: sharding.place_rows(
                self.mesh,
                v,
                sharding.batch_pspec()
                if v.ndim == 2
                else P(sharding.BATCH, "seq", None),
            )
            for k, v in arrays.items()
        }

    # ---------------- offload (HostOffloadMixin + optimizer state) ------

    def _offload_state(self):
        return (self.params, self.opt_state)

    def _restore_state(self, state):
        self.params, self.opt_state = state

    def _drop_state(self):
        self.params = None
        self.opt_state = None

    # ---------------- params / ckpt ----------------

    def get_params(self):
        self._ensure_loaded()
        return self.params

    def set_params(self, params) -> None:
        # Restore any offloaded state first (the optimizer state must
        # survive; the reloaded params are immediately replaced).
        self._ensure_loaded()
        self.params = realloc.reshard(
            params, self.param_shardings, self.master_dtype
        )

    def save_optimizer_state(self, path: str) -> None:
        import pickle

        self._ensure_loaded()

        # Host gather is collective on process-spanning meshes — every
        # group member calls it; only jax process 0 writes the file.
        host = jax.tree.map(to_host, self.opt_state)
        if not is_primary():
            return
        with open(path, "wb") as f:
            pickle.dump(host, f)

    def load_optimizer_state(self, path: str) -> None:
        import pickle

        self._ensure_loaded()

        with open(path, "rb") as f:
            host = pickle.load(f)
        self.opt_state = jax.tree.map(
            lambda h, cur: jax.device_put(jnp.asarray(h), cur.sharding),
            host,
            self.opt_state,
        )

    def hbm_owned(self) -> Dict[str, Any]:
        """`HostOffloadMixin.hbm_owned`, with Adam's moments (and whatever
        else the optimizer's state holds)."""
        return {"weights": self.params, "moments": self.opt_state}


"""Generation engine: two kinds of compiled generation program.

Capability parity: the reference's in-house generation stack
(realhf/impl/model/nn/real_llm_generate.py decode loop + CUDA-graph replay,
and the SGLang server backend realhf/impl/model/backend/sglang.py) — built
TPU-native:

- Static decode program (`_get_gen_fn`): the whole (prefill → sample →
  decode*) pipeline is ONE jitted function per (batch, prompt-bucket,
  total-bucket) shape over a dense KV window; `lax.while_loop` replaces the
  reference's CUDA-graph replay (XLA compiles the step once; no per-token
  Python).  Requests are length-sorted and packed into fixed-size batches so
  at most a handful of shapes ever compile.
- Serving chunk (`_get_serving_chunk_fn`): continuous batching over a paged
  KV pool — a fixed slot pool where finished rows retire and pending
  requests join between jitted T-step chunks; prompt slices, decode tokens,
  speculative verification and episode observations are all rows of one
  ragged token stream (reference: InflightBatchingGenerator,
  real_llm_generate.py:670).
- `generate()` picks between the two from what it observes (request count
  against slots, decode budget, stop sequences, `spec_decode_k`).
- Group sampling (n responses/prompt) expands prompts before batching.
- Weight hot-swap: `set_params` re-places the training params onto the
  generator's mesh/dtype (`parallel/realloc.reshard`: in place, one
  compiled on-device re-layout, or `device_put`, by where the bytes are)
  — the colocated-mesh equivalent of the reference's save-to-disk +
  update_weights_from_disk dance (model_worker.py:1040-1067).
"""

import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    Engine,
    GenerationHyperparameters,
    SlotGoneError,
)
from areal_tpu.base import logging, metrics, tracer
from areal_tpu.base.distributed import to_host
from areal_tpu.base.topology import batch_sharding_degree
from areal_tpu.engines.offload import HostOffloadMixin
from areal_tpu.engines.packing import decode_bucket_len as bucket_len
from areal_tpu.engines.paging import PageAllocator, PagePoolExhausted
from areal_tpu.models import mamba, transformer as tfm
from areal_tpu.models.branches import CallSums, LoopStep
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.sampling import sample_token
from areal_tpu.parallel import sharding

logger = logging.getLogger("generator")

# Tokens one prefill of the static decode program holds at most (b x sp,
# pads included): past it the rows go in waves (`_prefill_wave_rows`).
PREFILL_WAVE_TOKENS = 48 * 1024


def _cache_nbytes(cache) -> int:
    """Total byte footprint of a KV page pool (host-side metadata only)."""
    total = 0
    for a in (cache.k, cache.v, cache.k_scale, cache.v_scale):
        if a is not None:
            total += a.size * a.dtype.itemsize
    return total


# SlotGoneError (typed "your episode's slot was reclaimed" failure) lives
# in api/model_api.py so HTTP/ZMQ clients can raise the same type without
# importing the engines layer; re-exported here for engine-side callers.


def _find_stop_end(toks, scan_from: int, stop_seqs) -> Optional[int]:
    """Earliest index just PAST a completed stop sequence whose match
    ends after `scan_from` — so a sequence straddling two decode chunks
    is still caught, exactly once.  None when nothing matches."""
    best = None
    for seq in stop_seqs:
        L = len(seq)
        if L == 0 or len(toks) < L:
            continue
        target = list(seq)
        for i in range(max(0, scan_from - L + 1), len(toks) - L + 1):
            if toks[i : i + L] == target:
                end = i + L
                if best is None or end < best:
                    best = end
                break
    return best


# What a plan with recurrent state on the serving plane cannot take yet:
# a slot's state is one value a request, with no snapshot to roll back to
# or to share (ROADMAP B-I 3).
_NO_STATE_SPEC = (
    "speculative decoding (spec_decode_k > 0) on a plan with Mamba-2 "
    "layers: a rejected draft would have to roll the slot's recurrent "
    "state back, and the serving plane keeps no snapshot of it"
)
_NO_STATE_EPISODES = (
    "agent episodes on a plan with Mamba-2 layers: an episode's prefix "
    "pages are shared and republished across turns, and a slot's recurrent "
    "state has no snapshot at a page boundary to share"
)
_NO_STATE_REPLAY = (
    "the push-time resume replay on a plan with Mamba-2 layers: "
    "`_replay_tails` recomputes a tail's K/V from its tokens, and a slot's "
    "recurrent state would have to be recomputed from position 0 or from a "
    "snapshot; the serving loop does not park such a plan (an interrupt "
    "drains the call, as on the static program)"
)


def _new_state_stats() -> Dict[str, int]:
    """Per-generate() counters of the slots' recurrent state (a plan with
    Mamba-2 layers on the serving plane; `last_pool_stats` `ssm_*`)."""
    return {
        "ssm_live_slot_chunks": 0, "ssm_slots_zeroed": 0,
        "ssm_prefix_would_share": 0, "ssm_lanes_decode": 0,
        "ssm_lanes_prefill": 0, "ssm_slot_steps_live": 0,
        "ssm_lanes_made": 0, "ssm_interrupts_drained": 0,
    }


@dataclasses.dataclass
class _EpisodeSlot:
    """Host bookkeeping for one live episode pinned to a serving slot.

    The transcript itself lives in the shared session (`slot_prompt[s]`
    holds every forwarded token, the page table holds its KV); this
    records the episode-level state machine: turn count, per-turn decode
    budget, the stop-scan low-water mark, and whether an interrupt
    parked the episode mid-turn."""

    ep_id: str
    slot: int
    gconfig: GenerationHyperparameters
    token_budget: int  # max transcript tokens; 0 = session default
    turns: int = 0
    seq: int = 0  # LRU tick (bumped on every touch; eviction takes min)
    turn_start_len: int = 0  # transcript tokens when this turn began
    scan_from: int = 0  # stop-scan position within the current turn
    last_admit_tokens: int = 0  # teacher-forced tokens this call
    turn_max_new: int = 0  # effective per-turn budget (after clamp)
    budget_limited: bool = False  # turn_max_new was clamped by budget
    parked_mid_turn: bool = False  # interrupted inside a turn


@dataclasses.dataclass
class _PagedGenSession:
    """State of one serving-plane generate call (parked on interrupt).

    Everything the chunk loop carries between iterations, host AND device
    side, so `resume_generate()` can replay each live slot's last chunk
    under fresh weights and continue exactly where the loop stopped.  The
    PRNG key rides along and the replay consumes no keys, so an
    interrupted-then-resumed run under unchanged weights is token-
    identical to an uninterrupted one."""

    gconfig: GenerationHyperparameters
    key: Any  # jax PRNG key (chunk-split chain continues on resume)
    results: Dict
    n_slots: int
    n_pages: int
    max_pages: int
    chunk_t: int
    alloc: PageAllocator
    pool: Any  # device PagedKVCache
    logits_buf: Any  # device [n_slots, vocab] f32
    cache_len: np.ndarray
    gen_count: np.ndarray
    done_host: np.ndarray
    active: List[Optional[Tuple[int, int]]]
    toks_acc: Dict[int, List[int]]
    logps_acc: Dict[int, List[float]]
    pending: List
    # Per-slot prompt tokens + last chunk's emission count — together they
    # define the tail to replay on resume (history = prompt + toks_acc).
    slot_prompt: Dict[int, np.ndarray]
    last_emit: np.ndarray
    # Assembly context, filled by generate() at park time so
    # resume_generate() can return a finished SequenceSample.
    sample: Any = None
    prompt_key: str = "packed_prompts"
    prompt_lens: Any = None
    n: int = 1
    # ---- chunked prefill ----
    # Per-row prefill progress lives HERE, not in a second compiled
    # program: prompt_buf[slot] holds the not-yet-forwarded prompt
    # remainder, prefill_rem counts tokens still to consume, prompt_off
    # indexes the next prompt_buf read.  A row with prefill_rem > 0 is
    # an admitting row inside the serving chunk; 0 means decoding.
    prefill_chunk: int = 1  # W = query lanes per row per inner step
    prompt_buf: Any = None  # host np [n_slots, pbw] int32
    prefill_rem: Any = None  # host np [n_slots] int32
    prompt_off: Any = None  # host np [n_slots] int32
    # First PRIVATE flat token position per slot (shared prompt pages
    # end here): 0 for owners, sp*page_size for prefix-cache followers.
    # Resume replay must never write below it.
    shared_from: Any = None  # host np [n_slots] int32
    slot_hash: Any = None  # Dict[slot, bytes] prompt hash per live slot
    # hash -> owner slot currently prefilling it; followers stay pending
    # until the owner registers the prefix (keeps a GRPO group's k
    # members sharing instead of racing k private prefills).
    inflight_prefix: Any = None  # Dict[bytes, int]
    peak_live: int = 0  # max simultaneously live slots (capacity sweep)
    # Speculative decoding through the serving chunk (spec_decode_k > 0):
    # device-resident history buffer (prompt + emitted, read by the
    # in-chunk n-gram proposer) and the one sampled-but-unverified token
    # per row.  Always allocated (cheap) — trace-time K>0 branches in the
    # chunk fn decide whether they are consumed.
    tokens_buf: Any = None  # device [n_slots, buf_w] int32
    pending_tok: Any = None  # device [n_slots] int32
    # ---- agent-serving episodes (engine-lifetime session only) ----
    # ep_id -> _EpisodeSlot for every episode currently pinning a slot;
    # active[s] holds the ep_id string (any non-None marks the slot
    # live for the shared privatize/reserve helpers).
    episodes: Any = None  # Dict[str, _EpisodeSlot]
    ep_seq: int = 0  # monotonic LRU tick source
    ep_budget: int = 0  # session default per-episode token budget
    # The requests each slot served, in order (`serving_rollout`'s check
    # of what the chunk leaves in a reused slot).
    served: Any = None  # Dict[slot, List[(prompt index, repeat)]]


def _spec_emit(
    cfg, g, eos, rows, logits, drafts, sub, pending, cache_len, gen_count,
    done, out_toks, out_logps, out_fill, tokens_buf, active=None,
    n_valid=None,
):
    """Post-forward bookkeeping for one speculative decode step:
    min-length EOS masking, exact accept/reject (`spec_accept`), first-EOS
    truncation, appends into the chunk output buffers and the
    device-resident history buffer.

    `active` [B] bool (default: ~done) masks rows that should emit this
    step — the ragged serving chunk passes (~done) & (~is_pref) & got-
    lanes so prefilling rows and lane-starved rows carry their state
    untouched.  `n_valid` [B] int32 forwards to `spec_accept` for lane-
    truncated verification (row b only forwarded n_valid[b] positions).

    Returns (tokens_buf, pending, cache_len, gen_count, done, out_toks,
    out_logps, out_fill) — the post-step carry pieces."""
    from areal_tpu.ops.sampling import spec_accept

    K = g.spec_decode_k
    if active is None:
        active = ~done
    if g.min_new_tokens > 0:
        not_enough = (
            gen_count[:, None] + jnp.arange(K + 1)[None, :]
        ) < g.min_new_tokens
        logits = jnp.where(
            not_enough[:, :, None]
            & (jnp.arange(cfg.vocab_size) == eos)[None, None, :],
            -1e10,
            logits,
        )
    emitted, logps, n_emit = spec_accept(
        logits, drafts, sub,
        temperature=g.temperature, top_k=g.top_k, top_p=g.top_p,
        greedy=g.greedy, n_valid=n_valid,
    )
    n_emit = jnp.where(active, n_emit, 0)
    # Truncate at the first EOS (inclusive).
    j_idx = jnp.arange(K + 1)[None, :]
    is_eos = (emitted == eos) & (j_idx < n_emit[:, None])
    eos_pos = jnp.min(jnp.where(is_eos, j_idx, K + 1), axis=1)
    n_emit = jnp.minimum(n_emit, eos_pos + 1)
    new_done = done | (active & jnp.any(is_eos, axis=1))
    valid = j_idx < n_emit[:, None]
    # Append to the output buffers at per-row fill offsets.
    cols = out_fill[:, None] + j_idx
    out_toks = out_toks.at[rows[:, None], cols].set(
        jnp.where(valid, emitted, -1)
    )
    out_logps = out_logps.at[rows[:, None], cols].set(
        jnp.where(valid, logps, 0.0)
    )
    out_fill = out_fill + n_emit
    # History: emitted tokens live at positions L+1..L+n_emit.
    bcols = jnp.minimum(
        cache_len[:, None] + 1 + j_idx, tokens_buf.shape[1] - 1
    )
    cur = tokens_buf[rows[:, None], bcols]
    tokens_buf = tokens_buf.at[rows[:, None], bcols].set(
        jnp.where(valid, emitted, cur)
    )
    new_pending = jnp.take_along_axis(
        emitted, jnp.clip(n_emit - 1, 0, K)[:, None], axis=1
    )[:, 0]
    pending2 = jnp.where(done | (n_emit == 0), pending, new_pending)
    return (
        tokens_buf, pending2, cache_len + n_emit, gen_count + n_emit,
        new_done, out_toks, out_logps, out_fill,
    )


def _new_chunk_stats() -> Dict[str, Any]:
    return {
        "chunks": 0, "admitted": 0, "retired": 0, "chunk_host_s": 0.0,
        "admit_waits": [], "n_waited": 0, "admit_passed_over": 0,
    }


class GeneratorEngine(HostOffloadMixin, Engine):
    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        mesh: Mesh,
        eos_token_id: int,
        pad_token_id: Optional[int] = None,
        compute_dtype=jnp.bfloat16,
        max_decode_batch: int = 64,
        donation_safe_swap: bool = True,
        kv_cache_dtype: str = "auto",
        kv_page_size: int = 128,
        kv_pool_pages: int = 0,
        prefill_chunk_tokens: int = 8,
        kv_share_prefix: bool = True,
        serving_admit_lanes: int = 0,
    ):
        if cfg.is_critic:
            raise ValueError("cannot generate from a critic model")
        self.cfg = cfg
        self.mesh = mesh
        self.eos_token_id = int(eos_token_id)
        self.pad_token_id = int(pad_token_id or eos_token_id)
        if jax.default_backend() == "cpu":
            compute_dtype = jnp.float32
        self.compute_dtype = compute_dtype
        self.max_decode_batch = max_decode_batch
        # Decode budget above which generate() refuses the static
        # single-program path even when every request fits one pool (see
        # generate() routing): 2048 steps ≈ tens of seconds in one
        # program the host cannot interrupt or retire rows from.
        self.static_path_max_new = 2048
        # "auto" = compute dtype; "int8" halves KV HBM per token (the
        # long-context capacity bound — see models.transformer.KVCache).
        # Applies to the serving plane's page pool: chunked admission
        # quantizes fresh KV once per chunk and all query lanes attend
        # the dequantized pool (spec stays distribution-exact because
        # drafts and verification score against the same quantized-cache
        # model).  The static short-decode program keeps full precision
        # (its windows are small).
        # Validated here because YAML/gen_backend_args bypass the CLI's
        # argparse choices — a silently ignored "INT8"/"int4" would OOM
        # the exact 16k decode the flag exists to make fit.
        if kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'auto' or 'int8', "
                f"got {kv_cache_dtype!r}"
            )
        self.kv_cache_dtype = kv_cache_dtype
        # The serving plane's KV lives in a fixed-size page pool with a
        # host free-list allocator: zero cache copies, exactly one decode
        # compilation per generate call, retired slots' pages recycled
        # into new admits.
        if kv_page_size < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {kv_page_size}")
        if kv_pool_pages < 0:
            raise ValueError(
                f"kv_pool_pages must be >= 0 (0 = auto), got {kv_pool_pages}"
            )
        self.kv_page_size = int(kv_page_size)
        # 0 = auto: size the pool for the worst case (every slot at
        # prompt + max_new_tokens).  A positive value caps pool HBM and
        # makes admission wait for freed pages (PagePoolExhausted if a
        # LIVE slot cannot grow).
        self.kv_pool_pages = int(kv_pool_pages)
        # Serving plane: admitted prompts consume their tokens in W-sized
        # slices INSIDE the same ragged chunk step that advances live
        # decodes — no stop-the-world prefill program, no admission-shape
        # zoo, decode_compiles stays 1 under continuous admission.  W > 1
        # rides the decode step's streamed weights (decode is bandwidth-
        # bound; extra query lanes reuse the stream, same economics as
        # spec decode).  Validated here because YAML/gen_backend_args
        # bypass the CLI.
        if prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1 (the prefill slice "
                f"width W of the serving chunk), got "
                f"{prefill_chunk_tokens}; the two-program admit path that "
                f"0 selected was removed"
            )
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        # Copy-on-write prompt sharing (serving plane only): a GRPO
        # group's k responses — and any cross-request repeat of the same
        # prompt — map the owner's full prompt pages and re-forward only
        # the sub-page tail, multiplying effective pool capacity by the
        # group size.
        self.kv_share_prefix = bool(kv_share_prefix)
        # Serving-chunk lane budget headroom A: the packed token stream is
        # T = min(n_slots + A, n_slots * Wmax) lanes wide (rounded up to a
        # batch-shard multiple), where Wmax = max(W, K+1).  Every live row
        # always gets >= 1 lane (T >= n_slots); the A spare lanes are
        # shared by rows that want more (prefill slices, spec verify).
        # 0 = auto (4 * Wmax).  Undersizing is graceful: contended rows
        # progress slower, never wrong.
        if serving_admit_lanes < 0:
            raise ValueError(
                f"serving_admit_lanes must be >= 0 (0 = auto), "
                f"got {serving_admit_lanes}"
            )
        self.serving_admit_lanes = int(serving_admit_lanes)
        # Lane budget of the most recently compiled serving chunk fn
        # (T above) — bench/regression tooling reads it.
        self.serving_lane_budget = 0
        # When True (default), set_params COPIES any leaf whose buffers
        # alias the source tree — required when generation can overlap a
        # train step that donates those buffers (rollout_ahead).  In a
        # strictly synchronous colocated trial the alias is safe (nothing
        # decodes between the optimizer's donation and the rebind), and
        # skipping the copy saves a full extra parameter footprint in HBM
        # — the difference between a 1.5B model fitting or OOMing on one
        # 16 GB chip.
        self.donation_safe_swap = donation_safe_swap
        # Generation has no CP/PP path (decode is token-at-a-time and
        # latency-bound); only the flash half of the shared dispatch policy
        # applies to prefill.  A pipelined allocation is accepted by folding
        # its pipe axis into model: same chips, params stay sharded, no
        # bubble — the TPU answer to the reference's pipelined generation
        # (GenerateSchedule, static_schedule.py:199; see
        # topology.fold_pipe_into_model).
        self._use_flash, _, pp_mesh, _, _ = sharding.attn_dispatch(mesh, cfg)
        if pp_mesh is not None:
            from areal_tpu.base.topology import fold_pipe_into_model

            mesh = fold_pipe_into_model(mesh)
            self.mesh = mesh
            self._use_flash, _, pp_mesh, _, _ = sharding.attn_dispatch(
                mesh, cfg
            )
            assert pp_mesh is None
        self.batch_shard = batch_sharding_degree(mesh)
        self._gen_fns: Dict[Tuple, Any] = {}
        # Device dispatches spent admitting requests into freed slots
        # during the LAST generate() call — tests assert batching (one
        # dispatch per refill cycle, not one per admission).
        self.prefill_dispatches = 0
        # Per-generate() perf counters (reset in generate(); the bench
        # and the recompile-regression tests read them): decode-program
        # compilations, bytes moved by whole-cache grow copies, and the
        # last call's KV-memory utilization stats.
        self.decode_compiles = 0
        self.cache_copy_bytes = 0
        self.last_pool_stats: Dict[str, Any] = {}
        self._decode_sums = self._zero_decode_sums()
        self._bd_counts = 0.0
        self._kv_tiles = [0, 0]
        # Serving-plane chunk counters of the current generate() call
        # (see _serving_counters); folded into last_pool_stats at its end.
        self._chunk_stats: Dict[str, Any] = _new_chunk_stats()
        self._state_stats: Dict[str, int] = _new_state_stats()
        self._gen_t0 = time.monotonic()
        # Ragged-stream lane accounting (serving chunk only; reset in
        # generate()): lanes_dispatched = query lanes launched (chunk
        # steps x T), lanes_live = lanes carrying a real token,
        # lanes_slack = budgeted-but-idle lanes (compute eliminated, not
        # masked — the packed stream simply ends before them), and
        # dead_live_lanes = lanes that were live but mapped to no row /
        # an out-of-grant qpos.  The last is structurally zero; the bench
        # invariant leg asserts it ("dead-lane compute exactly 0").
        # pages_addressed = lanes x page-table width, pages_live = the
        # pages under the live lanes' windows (sum of ceil(window /
        # page_size)): the share of the addressed pages the chunk's
        # attention had to read.
        self.lanes_dispatched = 0
        self.lanes_live = 0
        self.lanes_slack = 0
        self.dead_live_lanes = 0
        self.pages_live = 0
        self.pages_addressed = 0
        # Interruptible generation (async RL): interrupt() makes the
        # serving loop park at its next chunk boundary (generate() then
        # returns None); resume_generate() replays each live slot's last
        # chunk under the CURRENT weights — rewriting the tail KV on its
        # already-mapped pages and refreshing the next-token logits —
        # then continues the loop.  The static program ignores the event
        # and runs to completion, so a weight push there degrades to a
        # full drain.
        self._interrupt_evt = threading.Event()
        self._session: Optional[_PagedGenSession] = None
        self.resume_replays = 0
        # Agent-serving episodes: an engine-LIFETIME serving session
        # (slot pool + page pool) that multi-turn episodes pin slots in;
        # created lazily by the first episode_start().  Counters are
        # cumulative (never reset by generate()) — the agents check leg
        # reads deltas.
        self._ep_session: Optional[_PagedGenSession] = None
        self.episodes_started = 0
        self.episodes_evicted = 0
        self.episode_prefix_hits = 0
        self.episode_prefix_misses = 0
        # Load gauges for gen_server /health queue-depth-aware balancing:
        # slots live in the current chunk loop and the last sampled
        # KV-pool utilization.  `load_state` is the atomically replaced
        # (live_slots, kv_utilization) pair — a single tuple assignment,
        # so a cross-thread health poll can never see the two fields
        # from different chunk boundaries.
        reg = metrics.default_registry()
        self._m_tokens = reg.counter(
            "areal_gen_tokens_total", "response tokens generated"
        )
        self._m_prefill_rows = reg.counter(
            "areal_gen_prefill_rows_total",
            "rows the static program prefilled (a shared prompt once)",
        )
        self._m_prefill_rows_requested = reg.counter(
            "areal_gen_prefill_rows_requested_total",
            "rows the static program was asked to prefill",
        )
        self._m_goodput = reg.gauge(
            "areal_gen_goodput_tokens_per_second",
            "tokens/s over the last completed generate call",
        )
        self._m_decode_compiles = reg.counter(
            "areal_gen_decode_compiles_total",
            "jitted decode-chunk program compiles",
        )
        self._m_kv_util = reg.gauge(
            "areal_gen_kv_utilization_ratio",
            "live KV tokens / allocated cache tokens, last chunk",
        )
        self._m_kv_live = reg.gauge(
            "areal_gen_kv_live_tokens", "live KV tokens, last chunk"
        )
        self._m_kv_alloc = reg.gauge(
            "areal_gen_kv_allocated_tokens",
            "allocated KV cache tokens, last chunk",
        )
        self._m_live_slots = reg.gauge(
            "areal_gen_live_slots", "slots live in the current chunk loop"
        )
        self.kv_utilization = 0.0
        self.live_slots = 0
        self.load_state = (0, 0.0)
        self.set_params(params)

    def _set_live_slots(self, n: int) -> None:
        self.live_slots = int(n)
        self.load_state = (int(n), self.kv_utilization)
        self._m_live_slots.set(n)

    def perf_counters(self) -> Dict[str, int]:
        """Memory/compile counters for the worker's MFC spans (profile
        store fields; analysis/profile.py _WATERMARK_ARGS)."""
        out = {"compiles": int(self.decode_compiles)}
        if self.params is not None:
            out["param_bytes"] = int(
                sum(int(x.nbytes) for x in jax.tree.leaves(self.params))
            )
        ps = self.last_pool_stats
        if ps.get("pool_bytes") is not None:
            out["pool_bytes"] = int(ps["pool_bytes"])
        if ps.get("peak_allocated_bytes") is not None:
            out["pool_peak_bytes"] = int(ps["peak_allocated_bytes"])
        return out

    # ---------------- interruption (async weight sync) ----------------

    def interrupt(self) -> None:
        """Request the running generate() to park at the next chunk
        boundary.  Safe from any thread; a no-op for the static program."""
        self._interrupt_evt.set()

    def clear_interrupt(self) -> None:
        self._interrupt_evt.clear()

    @property
    def interrupted(self) -> bool:
        """True iff a parked session is waiting for resume_generate()."""
        return self._session is not None

    @property
    def interrupt_requested(self) -> bool:
        """True while an interrupt is pending (set, not yet cleared) —
        episode drivers poll this before episode_resume() so a resume
        doesn't immediately re-park."""
        return self._interrupt_evt.is_set()

    @property
    def page_budget_tokens(self) -> Optional[int]:
        """Token capacity of an explicitly sized page pool (None when
        the pool is auto-sized) — the admission budget gen_server splits
        request groups against."""
        if self.kv_pool_pages == 0:
            return None
        return self.kv_pool_pages * self.kv_page_size

    def group_footprint_tokens(
        self, prompt_len: int, max_new_tokens: int, n: int
    ) -> int:
        """Worst-case KV pool footprint (in tokens) of a group of `n`
        same-prompt requests, CoW-aware: when the serving plane shares
        prompt pages, the prompt's full pages are paid ONCE and each
        member adds only the sub-page tail plus its new-token budget —
        gen_server splits request groups against page_budget_tokens
        using this instead of the dense n*(prompt+new) product."""
        plen, mnew, n = int(prompt_len), int(max_new_tokens), int(n)
        if not self.kv_share_prefix or n <= 1:
            return n * (plen + mnew)
        sp = max(0, (plen - 1) // self.kv_page_size)
        return sp * self.kv_page_size + n * ((plen - sp * self.kv_page_size) + mnew)

    # ---------------- weights ----------------

    def set_params(self, params) -> None:
        """Hot-swap weights (HostOffloadMixin._take_params).  Synchronous
        trials opt out of the alias copy (donation_safe_swap=False): the
        alias is never read between donation and the post-step rebind,
        and the saved copy is a full parameter footprint of HBM."""
        self._take_params(params, copy_aliases=self.donation_safe_swap)

    def get_params(self):
        self._ensure_loaded()
        self._require_params()
        return self.params

    def release_params(self) -> None:
        """Drop the weight reference (colocated synchronous loops).

        With donation_safe_swap=False the generator aliases the train
        master's buffers; a live alias blocks the optimizer step's buffer
        donation (XLA refuses to donate a referenced buffer, costing a
        transient extra parameter copy).  Between the last generate() and
        the post-step set_params() the weights are dead — release them so
        the optimizer updates in place.  Any offloaded host copy is stale
        by the same argument and is dropped too.  Any engine call before
        the next set_params() raises, which is the intended misuse
        signal."""
        self.params = None
        self._host_offload = None
        self._offload_shardings = None

    def _require_params(self) -> None:
        if self.params is None:
            raise RuntimeError(
                "GeneratorEngine weights were release_params()-ed; call "
                "set_params() before using the engine again"
            )

    # ---------------- generation ----------------

    def train_batch(self, *a, **k):
        raise NotImplementedError("GeneratorEngine is generation-only")

    def forward(self, *a, **k):
        raise NotImplementedError("GeneratorEngine is generation-only")

    def generate(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        gconfig: GenerationHyperparameters,
        prompt_key: str = "packed_prompts",
        seed: int = 0,
        inflight: Optional[bool] = None,
    ) -> SequenceSample:
        """Group-sample `gconfig.n` responses per prompt.

        Two generation programs:
        - static: length-sorted fixed-shape chunks (one jitted
          prefill+while-loop program per shape) — best when lengths are
          uniform;
        - inflight (the serving plane, continuous batching): a fixed slot
          pool where finished sequences retire and pending requests join
          between jitted T-step ragged chunks — one straggler no longer
          stalls the whole chunk (reference: InflightBatchingGenerator,
          realhf/impl/model/nn/real_llm_generate.py:670).
        Default: chosen from the requests (see below); `inflight=` forces
        either program, which is how tests reach both at toy sizes.

        Returns a SequenceSample (one element per prompt, `n` sequences per
        element — the reference's group layout, data_api docstring) with:
          packed_input_ids  — prompt+response tokens
          packed_logprobs   — seqlen-1 per sequence; response positions carry
                              the behavior logprobs, prompt positions 0
          prompt_mask       — True on prompt tokens
          seq_no_eos_mask   — 1.0 per sequence iff truncated (no EOS)
        """
        self._ensure_loaded()
        self._require_params()
        if self._session is not None:
            raise RuntimeError(
                "an interrupted generation is parked; call "
                "resume_generate() before starting a new one"
            )
        self._reset_call_counters()
        prompt_lens = sample.seqlens_of(prompt_key)
        bounds = sample.cu_seqlens(prompt_key)
        prompts = np.asarray(sample.data[prompt_key])
        n = gconfig.n

        # Expand ×n and sort by length (desc) to minimize padding waste.
        reqs = []  # (orig_idx, rep, tokens)
        for i in range(sample.bs):
            toks = prompts[bounds[i] : bounds[i + 1]]
            for r in range(n):
                reqs.append((i, r, toks))
        order = sorted(range(len(reqs)), key=lambda j: -len(reqs[j][2]))

        results: Dict[Tuple[int, int], Tuple[np.ndarray, np.ndarray, bool]] = {}
        key = jax.random.PRNGKey(seed)
        b_cap = max(self.batch_shard, self.max_decode_batch)
        if gconfig.spec_decode_k > 0:
            inflight = True  # spec verification is a row of the serving chunk
        elif gconfig.stop:
            # Stop sequences are scanned host-side at chunk boundaries;
            # the static path is one fused device program with no such
            # boundary, so stop-bearing requests always go inflight.
            inflight = True
        elif inflight is None:
            # Static chunks win when every request fits one pool (uniform
            # lengths, no refills, zero per-chunk host round-trips);
            # inflight wins when stragglers would otherwise stall retired
            # slots.  Long decodes ALWAYS go inflight: the static path is
            # one device program whose while_loop runs the whole decode
            # (minutes on-device at 16k+ steps, with no chunk boundary for
            # the host to act at) and allocates the full final KV
            # window from step 0, streaming depth it doesn't need yet on
            # every step; the serving loop keeps each program ~chunk_t
            # steps and maps pages as rows lengthen.
            inflight = (
                len(reqs) > b_cap
                or gconfig.max_new_tokens > self.static_path_max_new
            )
        refusal = tfm.plan_refusal(self.cfg, serving=True)
        if inflight and refusal:
            # Never a silent fallback to a plane that would drop a state
            # or has no pages for a population.
            raise type(refusal)(
                f"{refusal}; this call has {len(reqs)} requests "
                f"for {b_cap} slots, max_new_tokens "
                f"{gconfig.max_new_tokens} (static_path_max_new "
                f"{self.static_path_max_new}), stop={bool(gconfig.stop)}, "
                f"spec_decode_k={gconfig.spec_decode_k}, inflight={inflight}"
            )
        if gconfig.spec_decode_k > 0 and self._has_state:
            raise tfm.HybridLayoutError(_NO_STATE_SPEC)
        # Uncategorized envelope span (the inner prefill/decode spans carry
        # cat="compute"; host assembly gaps inside show as idle).
        with tracer.span(
            "generate",
            n_prompts=sample.bs,
            n_reqs=len(reqs),
            inflight=bool(inflight),
        ):
            if inflight:
                self._generate_inflight_serving(
                    [reqs[j] for j in order], gconfig, key, results
                )
                if self._session is not None:
                    # Parked on interrupt: stash the assembly context so
                    # resume_generate() can finish the call.  None tells
                    # the caller no sample was produced yet.
                    st = self._session
                    st.sample = sample
                    st.prompt_key = prompt_key
                    st.prompt_lens = prompt_lens
                    st.n = n
                    return None
            else:
                for start in range(0, len(order), b_cap):
                    chunk = [reqs[j] for j in order[start : start + b_cap]]
                    key, sub = jax.random.split(key)
                    self._generate_chunk(chunk, gconfig, sub, results)

            return self._assemble(sample, prompt_key, prompt_lens, results, n)

    def _zero_decode_sums(self) -> Dict[str, CallSums]:
        """The kinds' decode counters of a generate call, summed on the
        device inside the decode loop (`Branch.counter`), by name."""
        return {name: CallSums() for name in tfm.decode_counters(self.cfg)}

    def _reset_call_counters(self) -> None:
        """The per-call counters, at the start of a generate call."""
        self.prefill_dispatches = 0
        self.decode_compiles = 0
        self.cache_copy_bytes = 0
        self.last_pool_stats = {}
        self._decode_sums = self._zero_decode_sums()
        self._bd_counts = 0.0  # `_block_rollout`'s, summed over its chunks
        self._kv_tiles = [0, 0]  # `_kv_stats`: slot tiles live, allocated
        self.lanes_dispatched = 0
        self.lanes_live = 0
        self.lanes_slack = 0
        self.dead_live_lanes = 0
        self.pages_live = 0
        self.pages_addressed = 0
        self._gen_t0 = time.monotonic()
        self._chunk_stats = _new_chunk_stats()
        self._state_stats = _new_state_stats()

    def serving_rollout(self, prompts, gconfig, key):
        """One call of the SERVING plane over `prompts` (token arrays, one
        response each, admitted in the order given; more of them than
        slots go in waves) -> ({prompt index: (new tokens, their
        log-probs)}, the `PagedKVCache` the chunk loop left on the device —
        pages and, for a plan with state, every slot's recurrent state and
        conv tail as its LAST request left them — and {slot: the prompt
        indices it served, in order}).  For a check that holds what the
        chunk leaves to a reference, as `static_rollout(with_cache=True)`
        is for the static program."""
        self._ensure_loaded()
        self._require_params()
        self._reset_call_counters()
        reqs = [(i, 0, np.asarray(t, np.int32)) for i, t in enumerate(prompts)]
        results: Dict = {}
        st = self._generate_inflight_serving(reqs, gconfig, key, results)
        out = {i: (toks, logps) for (i, _), (toks, logps, _) in results.items()}
        served = {s: [i for i, _ in took] for s, took in st.served.items()}
        return out, st.pool, served

    def resume_generate(self) -> Optional[SequenceSample]:
        """Continue a parked generate() under the engine's CURRENT
        weights.  Re-prefills only each live slot's last chunk of tokens
        (teacher-forced through its existing page table, overwriting the
        tail KV in place and refreshing the next-token logits), then
        re-enters the chunk loop — so a weight push costs one chunk of
        forward, not a drain + full re-prefill.  Returns the finished
        SequenceSample, or None if interrupted again."""
        st = self._session
        if st is None:
            raise RuntimeError("no interrupted generation to resume")
        self._ensure_loaded()
        self._require_params()
        self._session = None
        live = [s for s in range(st.n_slots) if st.active[s] is not None]
        with tracer.span("resume_replay", cat="compute", n=len(live)):
            self._replay_tails(st, live)
        self.resume_replays += 1
        # The weight push invalidated every cached prompt KV: drop the
        # prefix-cache holds so post-resume admissions re-prefill under
        # the new weights instead of sharing stale pages (live followers
        # keep their mappings — their whole history KV is equally
        # pre-push, the accepted resume approximation).
        st.alloc.prefix_clear()
        st.inflight_prefix.clear()
        # Rows live across the push carry mixed-weight KV; if one later
        # finishes its prefill it must NOT register the prefix (followers
        # would inherit the mix — a fresh admission re-prefills cleanly
        # instead).
        st.slot_hash.clear()
        if not self._run_serving_loop(st):
            return None
        return self._assemble(
            st.sample, st.prompt_key, st.prompt_lens, st.results, st.n
        )

    def _replay_tails(self, st: "_PagedGenSession", slots) -> None:
        """Teacher-forced tail replay for resume: each slot's last chunk
        of history tokens goes through its existing page table again as
        a row of the ragged step (q_len = r, the way the serving chunk
        forwards a prefill slice), overwriting the tail KV in place and
        refreshing the slot's next-token logits.  Consumes no PRNG keys."""
        if self._has_state:
            raise tfm.HybridLayoutError(_NO_STATE_REPLAY)
        Q = st.chunk_t
        T = st.n_slots * Q
        tokens = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        # Lanes past the packed tails are DEAD (row_of == n_slots): their
        # cache writes drop and their attention is empty, so a short
        # replay can never scribble k/v past a row's valid tail.
        row_of = np.full((T,), st.n_slots, np.int32)
        last_lane = np.zeros((st.n_slots,), np.int32)
        live_mask = np.zeros((st.n_slots,), bool)
        fill = 0
        for s in slots:
            hist = np.concatenate(
                [st.slot_prompt[s], np.asarray(st.toks_acc[s], np.int32)]
            )
            # One KV per FORWARDED token: L == len(hist) for decoding
            # rows; a row parked mid-prefill has only hist[:L] in cache
            # (the rest still waits in prompt_buf) and replays from that
            # prefix.
            L = int(st.cache_len[s])
            # Replay window: the last chunk's emissions (>= 1 so the
            # fresh logits always come from a real forward).  SHARED
            # prompt pages (prefix-cache followers) are read-only: clamp
            # the window to the slot's private region so the teacher-
            # forced rewrite can never touch a page other rows map.
            priv = int(st.shared_from[s])
            r = int(min(max(int(st.last_emit[s]), 1), Q, L - priv))
            if r <= 0:
                continue  # nothing private to replay (cannot happen for
                # rows that ran a chunk; kept as a guard)
            tokens[fill : fill + r] = hist[L - r : L]
            positions[fill : fill + r] = np.arange(L - r, L)
            row_of[fill : fill + r] = s
            last_lane[s] = fill + r - 1
            live_mask[s] = True
            fill += r
        if fill == 0:
            return
        st.logits_buf, st.pool = self._get_paged_replay_fn(
            st.n_slots, st.n_pages, st.max_pages, Q
        )(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            st.pool, jnp.asarray(st.alloc.table), jnp.asarray(row_of),
            st.logits_buf, jnp.asarray(last_lane), jnp.asarray(live_mask),
        )

    def _get_paged_replay_fn(
        self, n_slots: int, n_pages: int, max_pages: int, chunk_t: int
    ):
        in_place = self._expert_leaves_in_place
        paged_kernel = self._paged_kernel
        sig = ("paged_replay", n_slots, n_pages, max_pages, chunk_t, in_place)
        if sig in self._gen_fns:
            return self._gen_fns[sig]
        cfg = self.cfg

        @functools.partial(jax.jit, donate_argnums=(3, 6))
        def fn(params, tokens, positions, pool, page_table, row_of,
               logits_buf, last_lane, live_mask):
            logits_pk, pool = tfm.decode_step_ragged_paged(
                params, cfg, tokens, positions, pool, page_table, row_of,
                experts_in_place=in_place, paged_kernel=paged_kernel,
            )
            logits_buf = jnp.where(
                live_mask[:, None],
                logits_pk[last_lane].astype(logits_buf.dtype),
                logits_buf,
            )
            return logits_buf, pool

        self._gen_fns[sig] = fn
        return fn

    def _drain_chunk_outputs(
        self, out_toks, out_logps, new_done, active, toks_acc, logps_acc,
        results, done_host, cache_len, max_new: int, on_retire=None,
        stop_seqs=(),
    ) -> None:
        """Serving-loop bookkeeping (generate() and episode turns): append
        each live slot's chunk output (rows are contiguous, -1-terminated),
        finish on EOS, a matched stop sequence (the stop tokens stay in
        the output), or the token budget, retire finished slots (a dead
        slot must not hold pages).  `on_retire(slot)` fires when a slot
        finishes — the loop hooks it to recycle the slot's pages into the
        free list."""
        for s in range(len(active)):
            if active[s] is None:
                continue
            row = out_toks[s]
            stop = np.flatnonzero(row < 0)  # -1-terminated within the chunk
            limit = int(stop[0]) if stop.size else row.shape[0]
            limit = min(limit, max(0, max_new - len(toks_acc[s])))
            eos = np.flatnonzero(row[:limit] == self.eos_token_id)
            if eos.size:  # keep the EOS token itself, drop the tail
                limit = int(eos[0]) + 1
            # One batched host conversion per slot per chunk — a per-token
            # float()/int() here would be a per-scalar sync if a caller
            # ever passed device arrays (rule host-sync).
            prev_len = len(toks_acc[s])
            toks_acc[s].extend(row[:limit].tolist())
            logps_acc[s].extend(out_logps[s, :limit].tolist())
            # Stop sequences are a HOST-side contract (the compiled chunk
            # keys only on geometry + sampling knobs, so adding a stop
            # set never recompiles): scan the accumulated tail, truncate
            # just past the match.
            cut = (
                _find_stop_end(toks_acc[s], prev_len, stop_seqs)
                if stop_seqs
                else None
            )
            if cut is not None:
                del toks_acc[s][cut:]
                del logps_acc[s][cut:]
            finished = (
                cut is not None
                or len(toks_acc[s]) >= max_new
                or (toks_acc[s] and toks_acc[s][-1] == self.eos_token_id)
            )
            if finished:
                i, rep = active[s]
                gtoks = np.asarray(toks_acc[s], np.int32)
                glogps = np.asarray(logps_acc[s], np.float32)
                no_eos = not (len(gtoks) and gtoks[-1] == self.eos_token_id)
                results[(i, rep)] = (gtoks, glogps, no_eos)
                active[s] = None
                done_host[s] = True
                cache_len[s] = 0
                if on_retire is not None:
                    on_retire(s)
            else:
                done_host[s] = new_done[s]

    def _accum_pool_stats(
        self, live_tokens: int, allocated_tokens: int
    ) -> None:
        """Accumulate per-chunk KV-memory utilization (live tokens /
        allocated cache tokens) into last_pool_stats — the benchmark
        reports this next to tokens/s."""
        st = self.last_pool_stats
        if "samples" not in st:
            st.update(
                kind="paged", samples=0, live_tokens=0, allocated_tokens=0
            )
        st["samples"] += 1
        st["live_tokens"] += int(live_tokens)
        st["allocated_tokens"] += int(allocated_tokens)
        st["utilization"] = st["live_tokens"] / max(st["allocated_tokens"], 1)
        # Instantaneous utilization, exposed through gen_server /health.
        self.kv_utilization = int(live_tokens) / max(int(allocated_tokens), 1)
        self.load_state = (self.live_slots, self.kv_utilization)
        self._m_kv_util.set(self.kv_utilization)
        self._m_kv_live.set(int(live_tokens))
        self._m_kv_alloc.set(int(allocated_tokens))
        # Per-chunk sampled gauge: KV pool pressure over time in the trace.
        tracer.counter(
            "kv_pool",
            live_tokens=int(live_tokens),
            allocated_tokens=int(allocated_tokens),
            utilization=int(live_tokens) / max(int(allocated_tokens), 1),
        )

    # -- serving plane (paged pool, chunked prefill, CoW page sharing) --

    def _paged_kv_dtype(self):
        return "int8" if self.kv_cache_dtype == "int8" else self.compute_dtype

    def _generate_inflight_serving(
        self, reqs, gconfig, key, results
    ) -> "_PagedGenSession":
        """Fixed slot pool; retire finished rows and admit pending requests
        between jitted T-step chunks.  Continuous batching over a paged KV
        pool with admission folded INTO the chunk step: the pool and the chunk program have ONE fixed
        shape for the whole generate call (compiled exactly once), window
        growth is a host-side page-index append, and retired slots' pages
        are recycled into new admits.  An admitted prompt is consumed in
        `prefill_chunk_tokens` (W)-sized slices by the same ragged
        compiled program that advances live decodes, so admission never
        stalls running rows behind a stop-the-world prefill and never
        compiles a second program — decode_compiles stays 1 under
        continuous admission.  Same-prompt
        repeats (a GRPO group's k responses) share the owner's full prompt
        pages copy-on-write via the allocator's prefix cache, multiplying
        the pool's effective concurrency by ~the group size."""
        n_slots = min(max(self.batch_shard, self.max_decode_batch), len(reqs))
        while n_slots % self.batch_shard:
            n_slots += 1
        ps = self.kv_page_size
        chunk_t = min(32, gconfig.max_new_tokens)
        K = gconfig.spec_decode_k
        max_prompt = max(len(t) for (_, _, t) in reqs)
        max_pages = -(
            -(max_prompt + gconfig.max_new_tokens + chunk_t + K) // ps
        )
        n_pages = self.kv_pool_pages or n_slots * max_pages
        pbw = max(max_prompt, 1)
        buf_w = max_prompt + gconfig.max_new_tokens + K + 2
        st = _PagedGenSession(
            gconfig=gconfig,
            key=key,
            results=results,
            n_slots=n_slots,
            n_pages=n_pages,
            max_pages=max_pages,
            chunk_t=chunk_t,
            alloc=PageAllocator(n_pages, ps, n_slots, max_pages),
            pool=tfm.init_paged_kv_cache(
                self.cfg, n_pages, ps, dtype=self._paged_kv_dtype(),
                n_slots=n_slots,
            ),
            logits_buf=jnp.zeros((n_slots, self.cfg.vocab_size), jnp.float32),
            cache_len=np.zeros((n_slots,), np.int32),
            gen_count=np.zeros((n_slots,), np.int32),
            done_host=np.ones((n_slots,), bool),
            active=[None] * n_slots,
            toks_acc={},
            logps_acc={},
            pending=list(reversed(reqs)),
            slot_prompt={},
            last_emit=np.zeros((n_slots,), np.int32),
            prefill_chunk=self.prefill_chunk_tokens,
            prompt_buf=np.full((n_slots, pbw), self.pad_token_id, np.int32),
            prefill_rem=np.zeros((n_slots,), np.int32),
            prompt_off=np.zeros((n_slots,), np.int32),
            shared_from=np.zeros((n_slots,), np.int32),
            slot_hash={},
            inflight_prefix={},
            tokens_buf=jnp.zeros((n_slots, buf_w), jnp.int32),
            pending_tok=jnp.zeros((n_slots,), jnp.int32),
            served={},
        )
        st.alloc.page_bytes = _cache_nbytes(st.pool) // n_pages
        self._run_serving_loop(st)
        return st

    def _run_serving_loop(self, st: "_PagedGenSession") -> bool:
        """The serving chunk loop: every iteration admits into free slots
        (host bookkeeping only — no device dispatch), maps pages for the
        chunk's worst-case advance, privatises any shared page a write
        could touch (CoW safety net), then runs ONE compiled ragged chunk
        in which prefilling rows consume W prompt tokens per inner step
        while decoding rows emit one token.  Interruptible at chunk
        boundaries: checks the interrupt event at the top of every
        iteration and parks the whole session (device pool + host
        bookkeeping) when set.  Returns True when all requests finished,
        False when parked (self._session then holds the state for
        resume_generate())."""
        gconfig = st.gconfig
        alloc = st.alloc
        n_slots, ps, chunk_t = st.n_slots, alloc.page_size, st.chunk_t
        W = st.prefill_chunk
        pbw = st.prompt_buf.shape[1]
        chunk_fn = self._get_serving_chunk_fn(
            n_slots, st.n_pages, st.max_pages, chunk_t, W, pbw, gconfig
        )
        while st.pending or any(a is not None for a in st.active):
            if self._interrupt_evt.is_set() and self._has_state:
                # No replay can refresh a slot's state (`_NO_STATE_REPLAY`):
                # the call drains under the weights it holds.
                self._state_stats["ssm_interrupts_drained"] = 1
            elif self._interrupt_evt.is_set():
                self._session = st
                tracer.counter(
                    "gen_interrupt",
                    parked_live=sum(a is not None for a in st.active),
                    parked_pending=len(st.pending),
                )
                return False
            cs = self._chunk_stats
            t_host = time.monotonic()
            with tracer.span("chunk_host", cat="host"):
                self._take_admits_serving(st)
                # Map pages covering this chunk's worst-case advance per live
                # slot: a prefilling row consumes up to chunk_t*Wmax prompt
                # tokens (but never more than its remainder + the decode
                # steps that may follow); a decoding row advances at most
                # chunk_t (plain) or chunk_t*(K+1) (spec), clamped to its
                # remaining emission budget + K draft-scratch positions —
                # tokens past max_new are drained away anyway, so reserving
                # for them would make a nearly-finished row hold pages it
                # never usefully writes (over-budget writes drop via the
                # sentinel; the positions they would have filled are only
                # ever attended by tokens that are themselves over budget
                # and discarded at drain).  Host-side int appends only.
                max_new = gconfig.max_new_tokens
                K = gconfig.spec_decode_k
                Wmax = max(W, K + 1)
                for s in range(n_slots):
                    if st.active[s] is not None:
                        rem = int(st.prefill_rem[s])
                        left = max(0, max_new - int(st.gen_count[s]))
                        target = int(st.cache_len[s]) + max(
                            1, min(
                                chunk_t * Wmax,
                                rem + chunk_t * (K + 1),
                                rem + left + K,
                            )
                        )
                        self._reserve_with_evict(alloc, s, target)
                self._privatize_write_windows(st)
                self._accum_pool_stats(
                    int(st.cache_len.sum()), alloc.allocated_pages() * ps
                )

                st.key, sub = jax.random.split(st.key)
                prev_gen = st.gen_count.copy()
                prev_rem = st.prefill_rem.copy()
            cs["chunk_host_s"] += time.monotonic() - t_host
            with tracer.span(
                "serving_chunk", cat="compute", t=chunk_t, w=W
            ):
                with tracer.span("chunk_dispatch", cat="compute"):
                    (
                        out_toks, out_logps, st.logits_buf, st.pool,
                        new_cache_len, new_gen_count, new_done, new_rem,
                        new_off, st.tokens_buf, st.pending_tok, lane_acc,
                    ) = chunk_fn(
                        self.params, st.pool, st.logits_buf,
                        jnp.asarray(alloc.table),
                        jnp.asarray(st.prompt_buf),
                        jnp.asarray(st.prompt_off),
                        jnp.asarray(st.prefill_rem),
                        jnp.asarray(st.cache_len),
                        jnp.asarray(st.gen_count),
                        jnp.asarray(st.done_host), st.tokens_buf,
                        st.pending_tok, sub,
                    )
                # ONE host-sync block per chunk (the done/eos flags must
                # be exact before the next admission round) — the lane
                # counters ride it rather than adding a sync of their
                # own.
                with tracer.span("chunk_wait", cat="compute"):
                    out_toks = to_host(out_toks)
                    out_logps = to_host(out_logps)
                    lane_acc = to_host(lane_acc)
            cs["chunks"] += 1
            t_host = time.monotonic()
            with tracer.span("chunk_host", cat="host"):
                st.cache_len = to_host(new_cache_len).copy()
                st.gen_count = to_host(new_gen_count).copy()
                st.prefill_rem = to_host(new_rem).copy()
                st.prompt_off = to_host(new_off).copy()
                st.last_emit = st.gen_count - prev_gen
                self._count_lanes(lane_acc, chunk_t, st.max_pages)
                if self._has_state:
                    ss = self._state_stats
                    ss["ssm_live_slot_chunks"] += self.live_slots
                    ss["ssm_lanes_decode"] += int(lane_acc[4])
                    ss["ssm_lanes_prefill"] += int(lane_acc[5])
                    ss["ssm_slot_steps_live"] += int(lane_acc[6])
                    ss["ssm_lanes_made"] += int(lane_acc[7])

                # Register prefixes that FINISHED prefilling this chunk,
                # before any retirement below can release the owner's pages:
                # the cache's per-page holds then keep them alive for
                # followers regardless of when the owner finishes decoding.
                if self.kv_share_prefix:
                    for s in range(n_slots):
                        if (
                            st.active[s] is not None
                            and prev_rem[s] > 0
                            and st.prefill_rem[s] == 0
                        ):
                            self._register_prefix(st, s)

                def _retire(s):
                    cs["retired"] += 1
                    alloc.release(s)
                    st.slot_prompt.pop(s, None)
                    h = st.slot_hash.pop(s, None)
                    if h is not None and st.inflight_prefix.get(h) == s:
                        del st.inflight_prefix[h]

                self._drain_chunk_outputs(
                    out_toks, out_logps, to_host(new_done), st.active,
                    st.toks_acc, st.logps_acc, st.results, st.done_host,
                    st.cache_len, gconfig.max_new_tokens, on_retire=_retire,
                    stop_seqs=gconfig.stop,
                )
            cs["chunk_host_s"] += time.monotonic() - t_host
        self.last_pool_stats.update(self._serving_counters())
        self.last_pool_stats.update(
            pool_pages=st.n_pages, page_size=ps,
            pages_recycled=alloc.pages_recycled,
            peak_pages_used=alloc.peak_pages_used,
            cow_copies=alloc.cow_copies,
            shared_mappings=alloc.shared_mappings,
            prefix_hits=alloc.prefix_hits,
            prefix_misses=alloc.prefix_misses,
            peak_live_slots=st.peak_live,
            # int8-aware: page_bytes is measured off the real device
            # pool, so an int8 pool reports ~1/2 the bf16 bytes (codes
            # + per-token scales), not a dtype guess.
            pool_bytes=alloc.pool_bytes(),
            peak_allocated_bytes=alloc.peak_pages_used * alloc.page_bytes,
        )
        if self._has_state:
            nbytes = {
                "ssm_state_bytes": sum(int(a.nbytes) for a in st.pool.state),
                "ssm_conv_bytes": sum(int(a.nbytes) for a in st.pool.conv),
            }
            self.last_pool_stats.update(self._state_stats, **nbytes)
            tracer.counter("ssm_slots", **self._state_stats, **nbytes)
        self._set_live_slots(0)
        return True

    def _take_admits_serving(self, st: "_PagedGenSession") -> int:
        """Admission for the serving loop: pure host bookkeeping (the
        compiled chunk does the prompt forwards).  Each free slot takes
        the FIRST queued request that can be admitted now, in queue order
        (one cursor walks the queue once a round: what a round passes
        over stays unadmittable for the rest of it).  A request whose
        prompt hash is in the prefix cache maps the cached FULL prompt
        pages (refcount bump, zero copies) and re-forwards only the
        sub-page tail — its marginal footprint is tail + decode budget
        instead of prompt + decode budget.  A request whose hash an
        in-flight owner is still prefilling is PASSED OVER (admitting it
        now would duplicate the owner's pages): it keeps its place in
        the queue, the round goes on with the requests behind it, and it
        is admitted, sharing, in the first round after the owner
        registers.  The owner is live, so that wait is bounded by one
        prefill and cannot deadlock.  It costs no allocator look-up:
        prefix_misses counts owners, not rounds waited.  A request that
        does not fit the POOL still ends the round (first come, first
        served under memory pressure).  Raises PagePoolExhausted via
        reserve() when nothing is live and that request still cannot fit
        (undersized pool)."""
        alloc, gconfig = st.alloc, st.gconfig
        n_slots, ps, chunk_t = st.n_slots, alloc.page_size, st.chunk_t
        slack = chunk_t + gconfig.spec_decode_k
        admitted = passed_over = 0
        free_slots = (s for s in range(n_slots) if st.active[s] is None)
        s = next(free_slots, None)
        at = len(st.pending) - 1  # the queue's head is the list's tail
        unfit = None  # prompt length that ended the round on pool pressure
        while s is not None and at >= 0:
            i, rep, toks = st.pending[at]
            toks = np.asarray(toks, np.int32)
            plen = len(toks)
            # Only FULL pages are shareable, and the tail must keep >= 1
            # token so the follower's re-forward produces its own
            # end-of-prompt logits: sp = (plen-1)//ps pages cover
            # positions [0, sp*ps), the follower prefills [sp*ps, plen).
            sp = (plen - 1) // ps
            h = toks.tobytes() if (self.kv_share_prefix and sp > 0) else None
            dup = None
            if h is not None and self._has_state:
                # A follower would need the owner's STATE at the page
                # boundary beside its pages, and there is no snapshot: it
                # maps no page and prefills its whole prompt.
                dup, h = h, None
            if h is not None and h in st.inflight_prefix:
                passed_over += 1  # its owner registers at a chunk's end
                at -= 1
                continue
            shared = alloc.prefix_lookup(h) if h is not None else None
            if shared is not None:
                need = alloc.pages_for(plen + slack) - len(shared)
                if need > len(alloc.free):
                    alloc.prefix_evict(need)
                if need > len(alloc.free):
                    unfit = plen
                    break
                alloc.share(s, shared)
                start = sp * ps
                alloc.reserve(s, plen + slack)
            else:
                if not alloc.can_reserve(s, plen + slack):
                    alloc.prefix_evict(
                        alloc.pages_for(plen + slack) - int(alloc.used[s])
                    )
                if not alloc.can_reserve(s, plen + slack):
                    unfit = plen
                    break
                alloc.reserve(s, plen + slack)
                start = 0
                if h is not None:
                    st.inflight_prefix[h] = s
                    st.slot_hash[s] = h
            del st.pending[at]
            at -= 1
            st.active[s] = (i, rep)
            if st.served is not None:
                st.served.setdefault(s, []).append((i, rep))
            st.cache_len[s] = start
            st.gen_count[s] = 0
            st.done_host[s] = False
            st.toks_acc[s] = []
            st.logps_acc[s] = []
            st.slot_prompt[s] = toks
            st.shared_from[s] = start
            rem = plen - start
            st.prompt_buf[s, :] = self.pad_token_id
            st.prompt_buf[s, :rem] = toks[start:]
            st.prefill_rem[s] = rem
            st.prompt_off[s] = 0
            st.last_emit[s] = 0
            admitted += 1
            if self._has_state:
                # The slot's first lane is at position 0: the recurrence
                # starts it from zero state and tail (`mamba.ssm_ragged`).
                self._state_stats["ssm_slots_zeroed"] += 1
            if dup is not None and dup in st.inflight_prefix:
                # Counted: admitted requests whose prompt an earlier
                # request of this call had (what sharing would have hit).
                self._state_stats["ssm_prefix_would_share"] += 1
            elif dup is not None:
                st.inflight_prefix[dup] = -1  # seen; owns no page
            s = next(free_slots, None)
        self._note_admits(admitted)
        self._chunk_stats["admit_passed_over"] += passed_over
        if unfit is not None and not any(a is not None for a in st.active):
            # Nothing live to retire and this request does not fit:
            # waiting would spin forever.  (The admission loop above
            # already tried prefix eviction, and nothing is passed over
            # here — owners are by definition live.)  reserve() raises
            # the clean capacity error.
            alloc.reserve(0, unfit + slack)  # raises
        self._set_live_slots(sum(a is not None for a in st.active))
        st.peak_live = max(st.peak_live, self.live_slots)
        tracer.counter(
            "gen_slots", live=self.live_slots, pending=len(st.pending),
            passed_over=passed_over,
        )
        return admitted

    def _note_admits(self, admitted: int) -> None:
        """Admission counters of this generate() call: how many requests
        got a slot in this round and how long after generate() began —
        requests admitted after the first chunk WAITED for a retirement."""
        cs = self._chunk_stats
        if not admitted:
            return
        cs["admitted"] += admitted
        wait = time.monotonic() - self._gen_t0
        cs["admit_waits"] += [wait] * admitted
        if cs["chunks"]:
            cs["n_waited"] += admitted

    def _count_lanes(self, lane_acc, chunk_t: int, max_pages: int) -> None:
        """One chunk's `lane_acc` (live lanes, slack lanes, misassigned
        lanes, live pages) into the lane and page counters."""
        lanes = chunk_t * self.serving_lane_budget
        self.lanes_dispatched += lanes
        self.lanes_live += int(lane_acc[0])
        self.lanes_slack += int(lane_acc[1])
        self.dead_live_lanes += int(lane_acc[2])
        self.pages_live += int(lane_acc[3])
        self.pages_addressed += lanes * max_pages

    def _serving_counters(self) -> Dict[str, float]:
        """The serving loop's per-generate() counters as last_pool_stats
        keys: chunks run, requests admitted and retired, host seconds at
        chunk boundaries, the spread of the seconds from generate()'s
        start to each request's admission, and the pages the chunks'
        attention addressed and had to read."""
        cs = self._chunk_stats
        waits = sorted(cs["admit_waits"])
        out = {
            "chunks": cs["chunks"], "admitted": cs["admitted"],
            "retired": cs["retired"], "chunk_host_s": cs["chunk_host_s"],
            "n_waited": cs["n_waited"],
            "admit_passed_over": cs["admit_passed_over"],
            "admit_wait_mean_s": sum(waits) / max(len(waits), 1),
            "admit_wait_p50_s": waits[len(waits) // 2] if waits else 0.0,
            "admit_wait_max_s": waits[-1] if waits else 0.0,
            "pages_live": self.pages_live,
            "pages_addressed": self.pages_addressed,
        }
        tracer.counter("serving_chunks", **out)
        return out

    def _register_prefix(self, st: "_PagedGenSession", s: int) -> None:
        """Publish slot `s`'s full prompt pages in the prefix cache (one
        hold per page) now that its prefill is complete — followers with
        the same prompt hash admit against these pages from the next
        chunk on.  Only owners carry a slot_hash entry; a no-op for
        followers and for slots admitted before a weight push (resume
        clears slot_hash so mixed-weight KV is never published)."""
        h = st.slot_hash.get(s)
        if h is None:
            return
        alloc = st.alloc
        sp = (len(st.slot_prompt[s]) - 1) // alloc.page_size
        if sp > 0:
            alloc.prefix_insert(h, alloc.table[s, :sp])
        st.inflight_prefix.pop(h, None)
        del st.slot_hash[s]

    def _reserve_with_evict(
        self, alloc: PageAllocator, s: int, tokens: int
    ) -> None:
        """reserve() that first evicts LRU prefix-cache holds when the
        free list is short — a live slot's growth outranks cached
        prefixes.  Still raises PagePoolExhausted when eviction cannot
        free enough (pool genuinely too small for what is live)."""
        if not alloc.can_reserve(s, tokens):
            alloc.prefix_evict(
                alloc.pages_for(tokens) - int(alloc.used[s])
            )
        alloc.reserve(s, tokens)

    def _privatize_write_windows(self, st: "_PagedGenSession") -> None:
        """Copy-on-write safety net, run before every chunk: privatise
        any SHARED page inside a live row's write window [cache_len,
        used*page_size) and execute the page copies on device.  By
        construction the serving plane never maps a shared page at or
        past a row's write cursor (followers share only pages strictly
        below their starting cache_len), so the steady state is zero
        pairs — but the read-only contract for shared pages is enforced
        here rather than assumed."""
        alloc = st.alloc
        pairs: List[Tuple[int, int]] = []
        for s in range(st.n_slots):
            if st.active[s] is None:
                continue
            pairs.extend(
                alloc.ensure_writable(
                    s,
                    int(st.cache_len[s]),
                    int(alloc.used[s]) * alloc.page_size,
                )
            )
        if not pairs:
            return
        fn = self._get_copy_pages_fn()
        width = 16  # fixed batch width: one compiled shape, sentinel-padded
        for lo in range(0, len(pairs), width):
            batch = pairs[lo : lo + width]
            src = np.full((width,), alloc.sentinel, np.int32)
            dst = np.full((width,), alloc.sentinel, np.int32)
            for j, (a, b) in enumerate(batch):
                src[j], dst[j] = a, b
            st.pool = fn(st.pool, jnp.asarray(src), jnp.asarray(dst))

    def _get_copy_pages_fn(self):
        sig = ("copy_pages",)
        if sig in self._gen_fns:
            return self._gen_fns[sig]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fn(pool, src, dst):
            return tfm.copy_pages(pool, src, dst)

        self._gen_fns[sig] = fn
        return fn

    def _get_serving_chunk_fn(
        self, n_slots: int, n_pages: int, max_pages: int, chunk_t: int,
        W: int, pbw: int, g: GenerationHyperparameters,
    ):
        """The unified serving chunk over a PACKED ragged token stream:
        chunk_t inner steps, each ONE `decode_step_ragged_paged` forward
        of a static [T]-lane stream in which every row occupies exactly
        the q_len it needs this step — a prefilling row teacher-forces up
        to W prompt tokens, a plain decoding row forwards its 1 sampled
        token, a speculating row (K > 0) forwards its pending token plus
        K n-gram drafts for exact verification, and a done/parked row
        occupies ZERO lanes.  Dead query lanes are ELIMINATED, not
        masked: the stream simply ends at `total` live lanes, and the
        slack tail carries sentinel rows whose compute the ragged kernel
        skips (its flash loop runs zero KV blocks for them).  Extra
        query lanes ride the decode step's streamed weights — decode is
        bandwidth-bound, so prefill slices AND spec verification share
        one weight stream (the spec-decode economics, now one program).

        Lane budget: T = min(n_slots + A, n_slots * Wmax) rounded up to
        a batch-shard multiple, Wmax = max(W, K+1), A the admit-lane
        headroom knob.  Every live row is guaranteed >= 1 lane (T >=
        n_slots); rows wanting more split the spare lanes front-to-back.
        An undersized budget degrades THROUGHPUT only: a lane-starved
        prefill row consumes fewer prompt tokens this step, a lane-
        starved spec row verifies fewer drafts (`spec_accept` n_valid
        truncation — distribution-exact at any grant).

        The signature depends only on pool geometry + hyperparameters,
        so it compiles EXACTLY ONCE per generate call even under
        continuous admission of mixed prefill/decode/spec rows.

        Emission is FILL-INDEXED, not step-indexed: a row's sampled
        tokens pack contiguously from column 0 of its out row whatever
        inner steps it spent prefilling, preserving the -1-termination
        contract `_drain_chunk_outputs` relies on."""
        K = g.spec_decode_k
        Wmax = max(W, K + 1)
        A = self.serving_admit_lanes or 4 * Wmax
        T = min(n_slots + A, n_slots * Wmax)
        while T % self.batch_shard:
            T += 1
        self.serving_lane_budget = T
        in_place = self._expert_leaves_in_place
        paged_kernel = self._paged_kernel
        ps = self.kv_page_size
        sig = (
            "serving_chunk", n_slots, n_pages, max_pages, chunk_t, W, pbw,
            K, g.spec_ngram, T,
            g.min_new_tokens, g.greedy, g.top_p, g.top_k, g.temperature,
            in_place, g.max_new_tokens if self._has_state else None,
        )
        if sig in self._gen_fns:
            return self._gen_fns[sig]
        cfg = self.cfg
        has_state = self._has_state
        slab_kernel = has_state and mamba.slab_kernel_form(cfg, paged_kernel)
        eos = self.eos_token_id
        # A spec row can emit up to K+1 tokens per inner step, plus one
        # fresh first token the step it leaves prefill.
        out_w = chunk_t * (K + 1) + 1 if K > 0 else chunk_t
        if K > 0:
            from areal_tpu.ops.ngram import propose_ngram

        @functools.partial(jax.jit, donate_argnums=(1, 2, 10))
        @jax.named_scope("gen/serving_chunk")
        def fn(params, pool, logits, page_table, prompt_buf, prompt_off,
               prefill_rem, cache_len, gen_count, done, tokens_buf,
               pending, key):
            out_toks = jnp.full((n_slots, out_w), -1, jnp.int32)
            out_logps = jnp.zeros((n_slots, out_w), jnp.float32)
            out_fill = jnp.zeros((n_slots,), jnp.int32)
            # (live lanes, slack lanes, live-but-misassigned lanes, live
            # pages) — the third is structurally zero; the bench
            # invariant leg asserts it stays so ("dead-lane compute
            # exactly 0").  A plan with state adds the lanes its
            # recurrence took, by kind: (decode, prefill), and the (slot,
            # inner step) pairs in which the slot held a lane — the state
            # tiles the recurrence has to step; a slot without one is
            # skipped (`ops/pallas/ssm_slab.py`) — and the slab lanes a
            # layer's chunk terms were made for (`mamba.slab_lanes_made`).
            lane_acc = jnp.zeros((8 if has_state else 4,), jnp.int32)
            rows = jnp.arange(n_slots)
            lanes = jnp.arange(Wmax)
            lane_ids = jnp.arange(T)
            buf_w = tokens_buf.shape[1]

            def body(t, st):
                (logits, pool, cache_len, gen_count, done, prefill_rem,
                 prompt_off, tokens_buf, pending, out_toks, out_logps,
                 out_fill, lane_acc) = st
                is_pref = prefill_rem > 0
                sub = jax.random.fold_in(key, t)
                if K > 0:
                    sub, sub_v = jax.random.split(sub)
                lg = logits
                if g.min_new_tokens > 0:
                    lg = jnp.where(
                        (gen_count < g.min_new_tokens)[:, None]
                        & (jnp.arange(cfg.vocab_size) == eos)[None, :],
                        -1e10,
                        lg,
                    )
                # Sampling consumes one fold_in(key, t) per inner step
                # regardless of row mode, so a decode row's key chain does
                # not depend on what other rows are doing (prefilling
                # rows' samples are discarded below).
                tok, logp = sample_token(
                    lg, sub,
                    temperature=g.temperature, top_k=g.top_k, top_p=g.top_p,
                    greedy=g.greedy,
                )
                if K > 0:
                    # K>0: the carry sample only seeds rows FRESH out of
                    # prefill (their first pending token, emitted now);
                    # speculating rows emit via spec_accept below.
                    emitting = (~done) & (~is_pref) & (gen_count == 0)
                else:
                    emitting = (~done) & (~is_pref)
                out_toks = out_toks.at[rows, out_fill].set(
                    jnp.where(emitting, tok, out_toks[rows, out_fill])
                )
                out_logps = out_logps.at[rows, out_fill].set(
                    jnp.where(emitting, logp, out_logps[rows, out_fill])
                )
                out_fill = out_fill + emitting.astype(jnp.int32)
                if K > 0:
                    done = done | (emitting & (tok == eos))
                    gen_count = gen_count + emitting.astype(jnp.int32)
                    pending = jnp.where(emitting, tok, pending)
                    # History invariant for speculating rows: cache_len =
                    # plen + gen_count - 1 and tokens_buf[cache_len] is
                    # the pending (sampled, not yet forwarded) token.
                    bp0 = jnp.clip(cache_len, 0, buf_w - 1)
                    tokens_buf = tokens_buf.at[rows, bp0].set(
                        jnp.where(emitting, tok, tokens_buf[rows, bp0])
                    )
                    drafts = propose_ngram(
                        tokens_buf, cache_len + 1, K, g.spec_ngram
                    )  # [n_slots, K]
                # Per-row lane want: a done/parked row wants ZERO lanes
                # (its compute is eliminated from the stream), a prefilling
                # row wants its next W-slice, a decoding row 1 (plain) or K+1
                # (pending + drafts).  Everybody gets their base lane
                # (T >= n_slots); the spare splits front-to-back.
                want = jnp.where(
                    done, 0,
                    jnp.where(
                        is_pref, jnp.minimum(prefill_rem, W), K + 1
                    ),
                ).astype(jnp.int32)
                base = (want > 0).astype(jnp.int32)
                extra = want - base
                spare = T - jnp.sum(base)
                excl = jnp.cumsum(extra) - extra
                c = base + jnp.clip(spare - excl, 0, extra)
                c = jnp.where(want > 0, c, 0)
                # Pack: row r owns stream lanes [starts[r], starts[r]+c[r]).
                cu = jnp.cumsum(c)
                starts = cu - c
                total = cu[-1]
                row_of = jnp.searchsorted(
                    cu, lane_ids, side="right"
                ).astype(jnp.int32)
                lane_live = lane_ids < total
                rid = jnp.minimum(row_of, n_slots - 1)
                qpos = lane_ids - starts[rid]
                badlane = lane_live & (
                    (row_of >= n_slots) | (qpos < 0) | (qpos >= c[rid])
                )
                qv = jnp.clip(qpos, 0, Wmax - 1)
                stream_pos = jnp.where(
                    lane_live, cache_len[rid] + qv, 0
                )
                lane_acc = lane_acc + jnp.stack([
                    total, T - total,
                    jnp.sum(badlane.astype(jnp.int32)),
                    # A live lane's window is [0, its position].
                    jnp.sum(jnp.where(lane_live, stream_pos // ps + 1, 0)),
                ] + ([
                    jnp.sum(jnp.where(is_pref, 0, c)),
                    jnp.sum(jnp.where(is_pref, c, 0)),
                    jnp.sum((c > 0).astype(jnp.int32)),
                    mamba.slab_lanes_made(c, Wmax, slab_kernel),
                ] if has_state else []))
                # Per-row lane-token slab, gathered into the stream.
                idx = jnp.minimum(
                    prompt_off[:, None] + lanes[None, :], pbw - 1
                )
                pref_toks = jnp.take_along_axis(prompt_buf, idx, axis=1)
                if K > 0:
                    dec = jnp.concatenate(
                        [pending[:, None], drafts], axis=1
                    )
                    if Wmax > K + 1:
                        dec = jnp.pad(dec, [(0, 0), (0, Wmax - (K + 1))])
                    slab = jnp.where(is_pref[:, None], pref_toks, dec)
                    # Prefill rows record their granted prompt slice into
                    # the history buffer (the n-gram proposer reads it).
                    lv = is_pref[:, None] & (lanes[None, :] < c[:, None])
                    bcols = jnp.clip(
                        cache_len[:, None] + lanes[None, :], 0, buf_w - 1
                    )
                    cur = tokens_buf[rows[:, None], bcols]
                    tokens_buf = tokens_buf.at[rows[:, None], bcols].set(
                        jnp.where(lv, pref_toks, cur)
                    )
                else:
                    slab = jnp.where(is_pref[:, None], pref_toks, 0)
                    slab = slab.at[:, 0].set(
                        jnp.where(is_pref, pref_toks[:, 0], tok)
                    )
                stream_tok = jnp.where(lane_live, slab[rid, qv], 0)
                logits_pk, pool2 = tfm.decode_step_ragged_paged(
                    params, cfg, stream_tok, stream_pos, pool,
                    page_table, row_of, experts_in_place=in_place,
                    paged_kernel=paged_kernel,
                    slot_lanes=Wmax if has_state else None,
                )  # [T, V]
                # Next-step carry = each granted row's LAST lane logits
                # (end-of-slice for prefill, post-token for decode);
                # zero-lane rows keep their carry untouched.
                last = jnp.clip(starts + c - 1, 0, T - 1)
                logits = jnp.where(
                    (c > 0)[:, None], logits_pk[last], logits
                )
                if K > 0:
                    # Ragged verification: row r's K+1 spec positions are
                    # lanes starts[r]..starts[r]+K; only the first c[r]
                    # were forwarded (n_valid truncation in spec_accept).
                    gidx = jnp.clip(
                        starts[:, None] + jnp.arange(K + 1)[None, :],
                        0, T - 1,
                    )
                    spec_lg = logits_pk[gidx]  # [n_slots, K+1, V]
                    active_m = (~done) & (~is_pref) & (c > 0)
                    (tokens_buf, pending, cache_len_s, gen_count, done,
                     out_toks, out_logps, out_fill) = _spec_emit(
                        cfg, g, eos, rows, spec_lg, drafts, sub_v,
                        pending, cache_len, gen_count, done, out_toks,
                        out_logps, out_fill, tokens_buf,
                        active=active_m, n_valid=c,
                    )
                    cache_len = jnp.where(
                        is_pref, cache_len + c, cache_len_s
                    )
                else:
                    done = jnp.where(is_pref, done, done | (tok == eos))
                    # Decode rows advance by their emission (a row
                    # emitting its EOS still wrote that token); done rows
                    # hold zero lanes and stay put.
                    cache_len = cache_len + c
                    gen_count = gen_count + emitting.astype(jnp.int32)
                    if has_state:
                        # A row at its budget holds no further lane: what
                        # it would emit is drained away, and the state it
                        # leaves is the one after its last kept token.
                        done = done | (gen_count >= g.max_new_tokens)
                prompt_off = prompt_off + jnp.where(is_pref, c, 0)
                prefill_rem = prefill_rem - jnp.where(is_pref, c, 0)
                return (logits, pool2, cache_len, gen_count, done,
                        prefill_rem, prompt_off, tokens_buf, pending,
                        out_toks, out_logps, out_fill, lane_acc)

            st = (logits, pool, cache_len, gen_count, done, prefill_rem,
                  prompt_off, tokens_buf, pending, out_toks, out_logps,
                  out_fill, lane_acc)
            st = jax.lax.fori_loop(0, chunk_t, body, st)
            (logits, pool, cache_len, gen_count, done, prefill_rem,
             prompt_off, tokens_buf, pending, out_toks, out_logps, _,
             lane_acc) = st
            return (
                out_toks, out_logps, logits, pool, cache_len, gen_count,
                done, prefill_rem, prompt_off, tokens_buf, pending,
                lane_acc,
            )

        self._gen_fns[sig] = fn
        self.decode_compiles += 1
        self._m_decode_compiles.inc()
        logger.info(
            f"compiled serving chunk n_slots={n_slots} "
            f"pool={n_pages}x{self.kv_page_size} chunk={chunk_t} W={W} "
            f"K={K} lanes={T}"
        )
        return fn

    # -- agent-serving episodes (multi-turn tool use on persistent KV) --

    def _episode_session_get(
        self, gconfig: GenerationHyperparameters, token_budget: int,
        seed: int,
    ) -> "_PagedGenSession":
        """Lazily create the engine-LIFETIME episode session: one slot
        pool + one page pool shared by every live episode.  Geometry is
        fixed at first use, so the serving chunk program compiles ONCE
        and every later turn of every episode reuses it — the agents
        check leg asserts decode_compiles stays 1 across a whole
        multi-episode run."""
        if self._ep_session is not None:
            return self._ep_session
        if self._has_state:
            raise tfm.HybridLayoutError(_NO_STATE_EPISODES)
        n_slots = max(self.batch_shard, self.max_decode_batch)
        while n_slots % self.batch_shard:
            n_slots += 1
        ps = self.kv_page_size
        chunk_t = min(32, gconfig.max_new_tokens)
        budget = int(token_budget) or 2048
        # The admission width bounds any single teacher-forced slab; a
        # conversation re-admitted after SlotGone is the worst case (the
        # whole budget), so pbw == budget keeps that path recompile-free.
        pbw = budget
        K = gconfig.spec_decode_k
        max_pages = -(-(budget + chunk_t + K) // ps)
        n_pages = self.kv_pool_pages or n_slots * max_pages
        st = _PagedGenSession(
            gconfig=gconfig,
            key=jax.random.PRNGKey(seed),
            results={},
            n_slots=n_slots,
            n_pages=n_pages,
            max_pages=max_pages,
            chunk_t=chunk_t,
            alloc=PageAllocator(n_pages, ps, n_slots, max_pages),
            pool=tfm.init_paged_kv_cache(
                self.cfg, n_pages, ps, dtype=self._paged_kv_dtype()
            ),
            logits_buf=jnp.zeros(
                (n_slots, self.cfg.vocab_size), jnp.float32
            ),
            cache_len=np.zeros((n_slots,), np.int32),
            gen_count=np.zeros((n_slots,), np.int32),
            done_host=np.ones((n_slots,), bool),
            active=[None] * n_slots,
            toks_acc={},
            logps_acc={},
            pending=[],
            slot_prompt={},
            last_emit=np.zeros((n_slots,), np.int32),
            prefill_chunk=self.prefill_chunk_tokens,
            prompt_buf=np.full((n_slots, pbw), self.pad_token_id, np.int32),
            prefill_rem=np.zeros((n_slots,), np.int32),
            prompt_off=np.zeros((n_slots,), np.int32),
            shared_from=np.zeros((n_slots,), np.int32),
            slot_hash={},
            inflight_prefix={},
            episodes={},
            ep_budget=budget,
            tokens_buf=jnp.zeros((n_slots, budget + K + 2), jnp.int32),
            pending_tok=jnp.zeros((n_slots,), jnp.int32),
        )
        st.alloc.page_bytes = _cache_nbytes(st.pool) // n_pages
        self._ep_session = st
        logger.info(
            f"episode session: {n_slots} slots, pool {n_pages}x{ps}, "
            f"chunk={chunk_t}, budget={budget}"
        )
        return st

    def episode_start(
        self,
        ep_id: str,
        prompt_ids,
        gconfig: GenerationHyperparameters,
        token_budget: int = 0,
        seed: int = 0,
    ) -> Optional[Dict[str, Any]]:
        """Open an episode: pin a serving slot, admit the conversation
        through the chunked-prefill serving program (the longest
        page-aligned transcript prefix already published rides the
        prefix cache — shared system prompts and post-SlotGone
        re-admissions both land here), decode turn 0 until a stop
        sequence / EOS / budget, then PARK the slot with its KV pages
        held.  Returns the turn dict, or None when an interrupt parked
        the call mid-turn (episode_resume() continues it)."""
        self._ensure_loaded()
        self._require_params()
        st = self._episode_session_get(gconfig, token_budget, seed)
        if ep_id in st.episodes:
            raise ValueError(f"episode {ep_id!r} already live")
        toks = np.asarray(list(map(int, prompt_ids)), np.int32)
        budget = int(token_budget) or st.ep_budget
        if len(toks) == 0:
            raise ValueError("episode_start needs a non-empty prompt")
        if len(toks) + 1 > budget:
            raise ValueError(
                f"episode prompt ({len(toks)} tokens) leaves no room in "
                f"the token budget ({budget})"
            )
        if len(toks) > st.prompt_buf.shape[1]:
            raise ValueError(
                f"episode prompt ({len(toks)} tokens) exceeds the "
                f"admission width ({st.prompt_buf.shape[1]})"
            )
        s = self._episode_free_slot(st)
        ep = _EpisodeSlot(
            ep_id=ep_id, slot=s, gconfig=gconfig, token_budget=budget,
        )
        st.episodes[ep_id] = ep
        self.episodes_started += 1
        self._episode_admit(st, ep, toks, fresh=True)
        return self._run_episode_turn(st, ep)

    def episode_extend(
        self, ep_id: str, obs_ids
    ) -> Optional[Dict[str, Any]]:
        """Append a tool result / observation onto the episode's SAME
        slot — a chunked-prefill admission over its existing KV pages,
        so nothing already in cache is ever re-forwarded — and decode
        the next turn.  Raises SlotGoneError when the slot was
        reclaimed; the controller then re-admits the full conversation
        via episode_start (the prefix cache pays for most of it)."""
        self._ensure_loaded()
        self._require_params()
        st = self._ep_session
        if st is None or ep_id not in st.episodes:
            raise SlotGoneError(
                ep_id,
                "engine has no episode session" if st is None
                else "slot reclaimed",
            )
        ep = st.episodes[ep_id]
        if ep.parked_mid_turn:
            raise RuntimeError(
                f"episode {ep_id!r} is parked mid-turn; call "
                "episode_resume() first"
            )
        obs = np.asarray(list(map(int, obs_ids)), np.int32)
        if len(obs) == 0:
            raise ValueError("episode_extend needs a non-empty observation")
        if len(obs) > st.prompt_buf.shape[1]:
            raise ValueError(
                f"observation ({len(obs)} tokens) exceeds the admission "
                f"width ({st.prompt_buf.shape[1]})"
            )
        if (
            ep.token_budget
            and int(st.cache_len[ep.slot]) + len(obs) + 1 > ep.token_budget
        ):
            # The observation alone busts the budget: a terminal
            # zero-token turn, no admission (the slot keeps its pages so
            # the transcript stays readable until release).
            ep.turns += 1
            return {
                "episode_id": ep.ep_id,
                "turn_index": ep.turns - 1,
                "tokens": [],
                "logprobs": [],
                "stop_reason": "budget",
                "transcript_len": int(st.cache_len[ep.slot]),
                "prefill_tokens": 0,
                "shared_prefix_tokens": int(st.shared_from[ep.slot]),
                "slot": ep.slot,
            }
        self._episode_admit(st, ep, obs, fresh=False)
        return self._run_episode_turn(st, ep)

    def episode_resume(self, ep_id: str) -> Optional[Dict[str, Any]]:
        """Continue a mid-turn-parked episode under the CURRENT weights:
        replay the slot's last chunk tail through its existing page
        table (resume_generate mechanics, one row), drop the prefix
        cache (stale-weight KV must not be shared into new admissions),
        then re-enter the turn loop."""
        self._ensure_loaded()
        self._require_params()
        st = self._ep_session
        if st is None or ep_id not in st.episodes:
            raise SlotGoneError(
                ep_id,
                "engine has no episode session" if st is None
                else "slot reclaimed",
            )
        ep = st.episodes[ep_id]
        if not ep.parked_mid_turn:
            raise RuntimeError(
                f"episode {ep_id!r} is not parked mid-turn"
            )
        ep.parked_mid_turn = False
        with tracer.span("episode_resume_replay", cat="compute", n=1):
            self._replay_tails(st, [ep.slot])
        self.resume_replays += 1
        st.alloc.prefix_clear()
        st.inflight_prefix.clear()
        st.slot_hash.clear()
        return self._run_episode_turn(st, ep)

    def episode_release(self, ep_id: str) -> bool:
        """Retire an episode: release its pages (prefix-cache holds on
        published transcript prefixes survive) and free the slot.
        Returns False when the episode is already gone."""
        st = self._ep_session
        if st is None or ep_id not in st.episodes:
            return False
        self._drop_episode(st, st.episodes[ep_id])
        return True

    def episode_stats(self) -> Dict[str, Any]:
        """Episode-plane load snapshot (gen_server /health + checks)."""
        st = self._ep_session
        out = {
            "active": 0,
            "parked_mid_turn": 0,
            "started": self.episodes_started,
            "evicted": self.episodes_evicted,
            "prefix_hits": self.episode_prefix_hits,
            "prefix_misses": self.episode_prefix_misses,
        }
        if st is not None:
            out["active"] = len(st.episodes)
            out["parked_mid_turn"] = sum(
                1 for e in st.episodes.values() if e.parked_mid_turn
            )
            out["pool_pages"] = st.n_pages
            out["pages_allocated"] = st.alloc.allocated_pages()
        return out

    def _episode_free_slot(self, st: "_PagedGenSession") -> int:
        for s in range(st.n_slots):
            if st.active[s] is None:
                return s
        # Every slot is pinned: reclaim the least-recently-touched
        # parked episode — its controller sees SlotGoneError on the next
        # continuation and re-admits via the prefix cache.
        if not self._evict_parked_episode(st):
            raise RuntimeError(
                "no free episode slot and nothing parked to evict"
            )
        return next(s for s in range(st.n_slots) if st.active[s] is None)

    def _evict_parked_episode(
        self, st: "_PagedGenSession", exclude: str = ""
    ) -> bool:
        """Reclaim the LRU parked episode's slot + pages.  Mid-turn
        parked episodes are exempt (their resume path owns the slot)."""
        cands = [
            ep for ep in st.episodes.values()
            if not ep.parked_mid_turn and ep.ep_id != exclude
        ]
        if not cands:
            return False
        victim = min(cands, key=lambda e: e.seq)
        logger.info(
            f"evicting parked episode {victim.ep_id!r} "
            f"(slot {victim.slot}, {victim.turns} turns)"
        )
        self._drop_episode(st, victim)
        self.episodes_evicted += 1
        return True

    def _drop_episode(self, st: "_PagedGenSession", ep: _EpisodeSlot):
        s = ep.slot
        st.alloc.release(s)
        st.active[s] = None
        st.done_host[s] = True
        st.cache_len[s] = 0
        st.gen_count[s] = 0
        st.prefill_rem[s] = 0
        st.prompt_off[s] = 0
        st.last_emit[s] = 0
        st.shared_from[s] = 0
        st.slot_prompt.pop(s, None)
        st.toks_acc.pop(s, None)
        st.logps_acc.pop(s, None)
        st.episodes.pop(ep.ep_id, None)

    def _episode_admit(
        self, st: "_PagedGenSession", ep: _EpisodeSlot, toks: np.ndarray,
        fresh: bool,
    ) -> None:
        """Admission is pure host bookkeeping (the serving chunk does
        the forwards).  fresh=True maps a slot for a full conversation:
        the LONGEST page-aligned transcript prefix published in the
        prefix cache is mapped copy-on-write (refcount bump, zero
        copies) and only the tail teacher-forces — this is what makes
        shared system prompts and post-SlotGone re-admission cheap.
        fresh=False appends an observation onto the SAME slot's live
        pages: the new tokens prefill from position cache_len onward,
        overwriting any tail KV a stop-sequence rewind left behind."""
        alloc = st.alloc
        s = ep.slot
        ps = alloc.page_size
        g = ep.gconfig
        # Chunk-advance slack past the transcript: decode steps plus the
        # K draft-scratch positions a speculating row writes past its
        # last accepted token.
        slack = st.chunk_t + g.spec_decode_k
        st.ep_seq += 1
        ep.seq = st.ep_seq
        if fresh:
            plen = len(toks)
            start = 0
            if self.kv_share_prefix and plen > ps:
                # Probe longest-first: published keys are page-aligned
                # transcript prefixes, so the first hit is the best hit.
                # The tail keeps >= 1 token — the re-forward must
                # produce this conversation's own end-of-prompt logits.
                for k in range((plen - 1) // ps, 0, -1):
                    shared = alloc.prefix_lookup(
                        b"ep:" + toks[: k * ps].tobytes()
                    )
                    if shared is None:
                        continue
                    need = alloc.pages_for(plen + slack) - len(shared)
                    if need > len(alloc.free):
                        alloc.prefix_evict(need)
                    if need > len(alloc.free):
                        break  # pool too tight to extend past the share
                    alloc.share(s, shared)
                    start = k * ps
                    break
            if start > 0:
                self.episode_prefix_hits += 1
            else:
                self.episode_prefix_misses += 1
            try:
                self._reserve_with_evict(alloc, s, plen + slack)
            except PagePoolExhausted:
                if not self._evict_parked_episode(st, exclude=ep.ep_id):
                    raise
                self._reserve_with_evict(alloc, s, plen + slack)
            st.active[s] = ep.ep_id
            st.cache_len[s] = start
            st.shared_from[s] = start
            st.slot_prompt[s] = toks
            rem = plen - start
        else:
            # Observation append: teacher-force everything past the KV
            # cursor.  For K > 0 the cursor parks ONE token short of the
            # kept transcript (the final kept token was a pending spec
            # token whose KV was never forwarded — see
            # _finish_episode_turn), so the tail re-forwards it along
            # with the observation.
            st.slot_prompt[s] = np.concatenate([st.slot_prompt[s], toks])
            start = int(st.cache_len[s])
            toks = st.slot_prompt[s][start:]
            rem = len(toks)
        st.toks_acc[s] = []
        st.logps_acc[s] = []
        st.gen_count[s] = 0
        st.done_host[s] = False
        st.prompt_buf[s, :] = self.pad_token_id
        st.prompt_buf[s, :rem] = toks[len(toks) - rem :]
        st.prefill_rem[s] = rem
        st.prompt_off[s] = 0
        st.last_emit[s] = 0
        ep.last_admit_tokens = rem
        ep.turn_start_len = start + rem
        ep.scan_from = 0
        # Per-turn decode budget, clamped so the transcript can never
        # outgrow the episode's token budget (the page reservation and
        # the admission width both rely on that bound).  Callers
        # pre-check, so this is >= 1 here.
        left = (
            ep.token_budget - ep.turn_start_len
            if ep.token_budget
            else g.max_new_tokens
        )
        ep.turn_max_new = max(0, min(g.max_new_tokens, left))
        ep.budget_limited = ep.turn_max_new < g.max_new_tokens

    def _run_episode_turn(
        self, st: "_PagedGenSession", ep: _EpisodeSlot
    ) -> Optional[Dict[str, Any]]:
        """Drive serving chunks until THIS episode's turn ends (stop
        sequence, EOS, per-turn length, or episode budget).  Other
        episodes' slots ride along as done rows — dead queries whose
        writes drop, exactly like retired slots in the batch loop.
        Checks the interrupt event at every chunk boundary: a weight
        push parks the turn in place (returns None) and
        episode_resume() replays the last chunk tail on the same pages
        before continuing."""
        g = ep.gconfig
        s = ep.slot
        alloc = st.alloc
        n_slots, chunk_t, W = st.n_slots, st.chunk_t, st.prefill_chunk
        pbw = st.prompt_buf.shape[1]
        chunk_fn = self._get_serving_chunk_fn(
            n_slots, st.n_pages, st.max_pages, chunk_t, W, pbw, g
        )
        max_new = ep.turn_max_new
        stop_seqs = g.stop
        reason = None
        while reason is None:
            if self._interrupt_evt.is_set():
                ep.parked_mid_turn = True
                tracer.counter(
                    "episode_interrupt",
                    slot=s,
                    cache_len=int(st.cache_len[s]),
                )
                return None
            rem = int(st.prefill_rem[s])
            left = max(0, max_new - int(st.gen_count[s]))
            K = g.spec_decode_k
            Wmax = max(W, K + 1)
            target = int(st.cache_len[s]) + max(
                1, min(
                    chunk_t * Wmax,
                    rem + chunk_t * (K + 1),
                    rem + left + K,
                )
            )
            try:
                self._reserve_with_evict(alloc, s, target)
            except PagePoolExhausted:
                if not self._evict_parked_episode(st, exclude=ep.ep_id):
                    raise
                self._reserve_with_evict(alloc, s, target)
            self._privatize_write_windows(st)
            self._accum_pool_stats(
                int(st.cache_len.sum()),
                alloc.allocated_pages() * alloc.page_size,
            )
            st.key, sub = jax.random.split(st.key)
            prev_gen = st.gen_count.copy()
            with tracer.span(
                "episode_chunk", cat="compute", t=chunk_t, w=W
            ):
                (
                    out_toks, out_logps, st.logits_buf, st.pool,
                    new_cache_len, new_gen_count, new_done, new_rem,
                    new_off, st.tokens_buf, st.pending_tok, lane_acc,
                ) = chunk_fn(
                    self.params, st.pool, st.logits_buf,
                    jnp.asarray(alloc.table), jnp.asarray(st.prompt_buf),
                    jnp.asarray(st.prompt_off),
                    jnp.asarray(st.prefill_rem),
                    jnp.asarray(st.cache_len), jnp.asarray(st.gen_count),
                    jnp.asarray(st.done_host), st.tokens_buf,
                    st.pending_tok, sub,
                )
                out_toks = to_host(out_toks)
                out_logps = to_host(out_logps)
                lane_acc = to_host(lane_acc)
            self._count_lanes(lane_acc, chunk_t, st.max_pages)
            st.cache_len = to_host(new_cache_len).copy()
            st.gen_count = to_host(new_gen_count).copy()
            st.prefill_rem = to_host(new_rem).copy()
            st.prompt_off = to_host(new_off).copy()
            st.done_host = to_host(new_done).copy()
            st.last_emit = st.gen_count - prev_gen
            # Drain THIS slot only (parked rows emit nothing).
            row = out_toks[s]
            term = np.flatnonzero(row < 0)
            limit = int(term[0]) if term.size else row.shape[0]
            limit = min(limit, max(0, max_new - len(st.toks_acc[s])))
            eos_at = np.flatnonzero(row[:limit] == self.eos_token_id)
            if eos_at.size:
                limit = int(eos_at[0]) + 1
            prev_len = len(st.toks_acc[s])
            st.toks_acc[s].extend(row[:limit].tolist())
            st.logps_acc[s].extend(out_logps[s, :limit].tolist())
            cut = (
                _find_stop_end(st.toks_acc[s], prev_len, stop_seqs)
                if stop_seqs
                else None
            )
            if cut is not None:
                del st.toks_acc[s][cut:]
                del st.logps_acc[s][cut:]
                reason = "stop"
            elif (
                st.toks_acc[s]
                and st.toks_acc[s][-1] == self.eos_token_id
            ):
                reason = "eos"
            elif (
                int(st.prefill_rem[s]) == 0
                and len(st.toks_acc[s]) >= max_new
            ):
                reason = "budget" if ep.budget_limited else "length"
        return self._finish_episode_turn(st, ep, reason)

    def _finish_episode_turn(
        self, st: "_PagedGenSession", ep: _EpisodeSlot, reason: str
    ) -> Dict[str, Any]:
        s = ep.slot
        kept = len(st.toks_acc[s])
        # Rewind: tokens sampled past the kept boundary (after a stop
        # sequence, or over the turn budget) left KV at positions the
        # transcript no longer covers.  Pulling cache_len back is pure
        # host bookkeeping — attention never reads past a row's write
        # cursor, and the next admission teacher-forces over those
        # positions in place.  With spec decoding the final kept token
        # may be a still-PENDING token (sampled, never forwarded, so no
        # KV exists for it) — park one short and let the next
        # observation admit teacher-force it with the obs tail.
        if ep.gconfig.spec_decode_k > 0:
            st.cache_len[s] = ep.turn_start_len + max(0, kept - 1)
        else:
            st.cache_len[s] = ep.turn_start_len + kept
        st.done_host[s] = True
        st.prefill_rem[s] = 0
        turn_toks = [int(t) for t in st.toks_acc[s]]
        turn_lps = [float(x) for x in st.logps_acc[s]]
        if turn_toks:
            st.slot_prompt[s] = np.concatenate(
                [st.slot_prompt[s], np.asarray(turn_toks, np.int32)]
            )
        st.toks_acc[s] = []
        st.logps_acc[s] = []
        ep.turns += 1
        self._episode_publish_prefix(st, s)
        self._set_live_slots(len(st.episodes))
        return {
            "episode_id": ep.ep_id,
            "turn_index": ep.turns - 1,
            "tokens": turn_toks,
            "logprobs": turn_lps,
            "stop_reason": reason,
            "transcript_len": int(ep.turn_start_len + kept),
            "prefill_tokens": int(ep.last_admit_tokens),
            "shared_prefix_tokens": int(st.shared_from[s]),
            "slot": s,
        }

    def _episode_publish_prefix(
        self, st: "_PagedGenSession", s: int
    ) -> None:
        """Publish the slot's page-aligned transcript prefix so a future
        conversation sharing it — another episode with the same system
        prompt, or a post-SlotGone re-admission of this very transcript
        — maps the pages instead of re-prefilling.  Keys are the prefix
        token bytes, page-aligned, so admission probes longest-first."""
        if not self.kv_share_prefix:
            return
        alloc = st.alloc
        sp = int(st.cache_len[s]) // alloc.page_size
        if sp <= 0:
            return
        alloc.prefix_insert(
            b"ep:" + st.slot_prompt[s][: sp * alloc.page_size].tobytes(),
            alloc.table[s, :sp],
        )

    @functools.cached_property
    def _has_state(self) -> bool:
        """Whether a request holds a slot of recurrent state beside its
        pages on the serving plane (a kind the chunk runs keeps a `state`:
        Mamba-2 layers).  Asked several times a chunk: read off the table
        once."""
        return any(
            "state" in b.cache and b.serve
            for b in tfm.branches_of(self.cfg).values())

    @property
    def _paged_kernel(self) -> Optional[bool]:
        """Which form of the paged attention the serving programs take:
        None, the platform's, on one device; the XLA form where the mesh
        spreads the pool's heads or the stream's lanes over more — the
        kernel is one device's program and is not `shard_map`ped yet."""
        return None if self.mesh.size == 1 else False

    @property
    def _row_kernel(self):
        """What the static program's per-row cache kernels take (latent
        decode attention, `ops/attention.latent_decode_attention`; the
        Gated DeltaNet step, `linear_attention.linear_attn_step`): None,
        the backend's form, on one device; the MESH where the rows are
        spread over more, so that the kernel runs per device on its own
        rows."""
        return None if self.mesh.size == 1 else self.mesh

    def _kv_stats(self, s_total: int, valid_from, ends) -> Dict[str, float]:
        """What a static call's softmax attention ran on, as
        `last_pool_stats` keys, on the host from numbers the call has:
        `gen/kv_kernel` — 1.0 where the program's attention is the kernel
        `kv_decode` (`tfm.kv_kernel_form`, the program's own chooser on the
        same inputs), else 0.0 — and with the kernel `gen/kv_live_tile_share`
        — the slot tiles inside a row's window [valid_from, end), summed
        over the rows and the forwards of the generate call so far (`ends`:
        each forward's `valid_to`), over the tiles allocated: what the
        kernel reads of what the XLA form read."""
        if not tfm.kv_kernel_form(self.cfg, self._row_kernel, s_total):
            return {"gen/kv_kernel": 0.0}
        from areal_tpu.ops.pallas.kv_decode import BLOCK_S as tile

        lo = np.asarray(valid_from, np.int64)[:, None]
        hi = np.asarray(ends, np.int64)[None, :]
        live = np.where(hi > lo, (hi - 1) // tile - lo // tile + 1, 0)
        self._kv_tiles[0] += int(live.sum())
        self._kv_tiles[1] += live.size * -(-s_total // tile)
        return {
            "gen/kv_kernel": 1.0,
            "gen/kv_live_tile_share":
                self._kv_tiles[0] / max(self._kv_tiles[1], 1),
        }

    # The two loops' windows are made HERE and not in `static_rollout` /
    # `_block_rollout`: with the token loop's three lines of numpy in
    # `static_rollout`'s own body, the process's FIRST `jit(gen)` lowered in
    # 4.7 s where it lowers in 2.9 — in a cell that never takes the kernel,
    # before the lines ever ran (`nemo3n-rollout64-512`, twelve probes; PERF.md
    # section 7, A2 (0)(iii)).  A call with plain arguments keeps 2.9.

    def _kv_token_stats(self, s_total, sp, prompt_len, gen_len):
        """`_kv_stats` of the token loop: it ran until its last row was
        done, and step t attends [valid_from, sp + t + 1)."""
        steps = int(gen_len.max(initial=0))
        return self._kv_stats(
            s_total, sp - prompt_len, sp + 1 + np.arange(steps))

    def _kv_block_stats(self, s_total, sp, whole_len, blocks):
        """`_kv_stats` of the block loop: the first block's log-prob
        forward, then a block's denoising forwards and its commit, each
        over [valid_from, the block's end)."""
        cfg = self.cfg
        ends = sp + cfg.block_length * np.arange(1, int(blocks) + 1)
        return self._kv_stats(
            s_total, sp - whole_len,
            np.concatenate([ends[:1], np.repeat(
                ends, cfg.denoising_forwards + 1)]))

    @property
    def _expert_kernel(self) -> Optional[bool]:
        """What the static program's in-place expert matmuls take: None,
        the backend's choice, on one device; XLA's ragged kernel on a mesh
        (the Pallas grouped matmul is one device's program)."""
        return None if self.mesh.size == 1 else False

    @property
    def _expert_leaves_in_place(self) -> bool:
        """Whether the decode programs hand the ragged kernels the stacked
        expert leaves themselves — asked of the placed params, outside
        the trace (`tfm.expert_leaves_in_place`); False for dense models."""
        return tfm.expert_leaves_in_place(self.cfg, self.params["blocks"])

    # -- one fixed-shape chunk --

    def _generate_chunk(self, chunk, gconfig, key, results) -> None:
        # A row's source: the chunk row that first carries its prompt.
        first: Dict[int, int] = {}
        src = [first.setdefault(i, r) for r, (i, _, _) in enumerate(chunk)]
        toks, logps, gen_len = self.static_rollout(
            [t for (_, _, t) in chunk], gconfig, key, src=src
        )
        for r, (i, rep, _) in enumerate(chunk):
            gl = int(gen_len[r])
            no_eos = gl == gconfig.max_new_tokens and (
                gl == 0 or toks[r, gl - 1] != self.eos_token_id
            )
            results[(i, rep)] = (toks[r, :gl], logps[r, :gl], no_eos)

    def _block_rollout(
        self, prompts, gconfig, key, with_cache=False, steps=False
    ):
        """`static_rollout` of a model that generates by diffusion over
        blocks (`cfg.block_length`; `engines/block_diffusion.py`): the same
        call and the same results — `max_new_tokens` tokens a row at most,
        their log-probs, `gen_len` — from the program's loop over BLOCKS.
        A row's whole prompt blocks end at the bucket `sp`, its tail rides
        in its first block at slots [sp, sp + B): with `with_cache`, row
        r's tokens lie in the cache's slots from sp - B floor(len / B) on.
        `steps`: also the denoising step that revealed each token [b,
        max_new]."""
        from areal_tpu.engines import block_diffusion as bd

        cfg = self.cfg
        bd.refuse(cfg, gconfig)
        b_real = len(prompts)
        b = -(-b_real // self.batch_shard) * self.batch_shard
        sp = bucket_len(max(len(t) for t in prompts))
        whole_tok, whole_len, tail_tok, tail_len = bd.split_prompts(
            cfg, prompts, sp, self.pad_token_id, b)
        nb = bd.n_blocks(cfg, gconfig.max_new_tokens, tail_len[:b_real])
        # Every forward streams the whole window: the cache is cut to the
        # next 128 slots past the last block, not to the token loop's
        # bucket (a two-token tail would make 772 slots 1,024).
        s_total = -(-(sp + nb * cfg.block_length) // 128) * 128
        g = gconfig
        sig = (
            "blocks", b, sp, s_total, nb, g.max_new_tokens, g.greedy, g.top_p,
            g.top_k, g.temperature, self._expert_leaves_in_place, with_cache,
        )
        if sig not in self._gen_fns:
            self._gen_fns[sig] = bd.build(
                self, b, sp, s_total, nb, g, with_cache)
            logger.info(
                f"compiled block generator for shape b={b} sp={sp} "
                f"s_total={s_total} blocks={nb}")
        fn = self._gen_fns[sig]
        stats = self.last_pool_stats
        for name in ("prefill_rows", "prefill_rows_requested"):
            stats[name] = stats.get(name, 0) + b_real
        self._m_prefill_rows.inc(b_real)
        self._m_prefill_rows_requested.inc(b_real)
        with tracer.span(
            "gen_chunk", cat="compute", b=b_real, sp=sp, prefill_rows=b_real,
            prefill_rows_requested=b_real, blocks=nb,
        ):
            with tracer.span("gen_dispatch", cat="compute"):
                toks, logps, gen_len, sums, counts, step_of, *cache = fn(
                    self.params, whole_tok, whole_len, tail_tok, tail_len, key)
            with tracer.span("gen_wait", cat="compute"):
                toks, logps, gen_len, counts = (
                    to_host(toks), to_host(logps), to_host(gen_len),
                    to_host(counts))
                for name, counter in tfm.decode_counters(cfg).items():
                    self._decode_sums[name] += to_host(
                        sums[name]).astype(float)
                    stats.update(counter.report(
                        self._decode_sums[name], cfg, self.params))
        self._bd_counts = self._bd_counts + counts.astype(float)
        stats.update(bd.report(cfg, self._bd_counts, b))
        stats.update(self._kv_block_stats(s_total, sp, whole_len, counts[0]))
        tracer.counter("bd", **{
            k.split("/")[1]: v for k, v in stats.items()
            if k.startswith("bd/") and not isinstance(v, list)})
        out = (toks, logps, gen_len)
        if steps:
            out += (to_host(step_of),)
        return out + tuple(cache)

    def static_rollout(
        self, prompts, gconfig, key, with_cache=False, src=None
    ):
        """One call of the static decode program over `prompts` (token
        arrays, at most one batch of them) -> host arrays (tokens [b,
        max_new], their log-probs, generated lengths [b]); rows past
        `len(prompts)` pad the batch to the mesh's batch sharding.

        `src`: for each prompt the first row that carries the SAME prompt
        (`src[r] <= r`, `src[src[r]] == src[r]`; None: every row its own).
        Where the program shares a prefill (`_shared_rows`) a repeated
        prompt is prefilled once and lands at every row of its group; the
        rows still sample apart.

        `with_cache`: also the `KVCache` the program leaves, on the device
        (one more output of the same program, for a check that holds the
        cache to a reference): row r's prompt lies in slots [sp - len, sp),
        sp = `bucket_len` of the longest prompt, its new tokens from sp.

        A model that generates by diffusion over blocks
        (`cfg.block_length`) runs the program's loop over BLOCKS
        (`_block_rollout`): the same call, the same results."""
        if self.cfg.block_length:
            return self._block_rollout(prompts, gconfig, key, with_cache)
        b_real = len(prompts)
        b = b_real
        while b % self.batch_shard:
            b += 1
        sp = bucket_len(max(len(t) for t in prompts))
        s_total = bucket_len(sp + gconfig.max_new_tokens)

        # Right-aligned prompts: every row's next token lands at the SAME
        # cache slot (sp + step), so the decode KV write is one
        # dynamic_update_slice instead of a per-row scatter.
        prompt_tok = np.full((b, sp), self.pad_token_id, np.int32)
        prompt_len = np.zeros((b,), np.int32)
        for r, toks in enumerate(prompts):
            prompt_tok[r, sp - len(toks):] = toks
            prompt_len[r] = len(toks)

        cfg = self.cfg
        if src is not None and not all(
            s <= r and np.array_equal(prompts[s], prompts[r])
            for r, s in enumerate(src)
        ):
            raise ValueError(
                f"src {list(src)} names a row that does not come first or "
                f"does not carry the same prompt")
        src = self._shared_rows(b, sp, gconfig.max_new_tokens, src)
        fn = self._get_gen_fn(b, sp, s_total, gconfig, with_cache, src)
        prefilled = b_real if src is None else len(set(src[:b_real]))
        stats = self.last_pool_stats
        stats["prefill_rows"] = stats.get("prefill_rows", 0) + prefilled
        stats["prefill_rows_requested"] = (
            stats.get("prefill_rows_requested", 0) + b_real)
        self._m_prefill_rows.inc(prefilled)
        self._m_prefill_rows_requested.inc(b_real)
        # What the cache holds beside k/v, from shapes alone.
        cache = jax.eval_shape(
            lambda: tfm.init_kv_cache(cfg, b, s_total, dtype=self.compute_dtype)
        )
        for branch in tfm.branches_of(cfg).values():
            if branch.cache_stats:
                self.last_pool_stats.update(
                    branch.cache_stats(cfg, cache, b, s_total))
        with tracer.span(
            "gen_chunk", cat="compute", b=b_real, sp=sp,
            prefill_rows=prefilled, prefill_rows_requested=b_real,
        ):
            with tracer.span("gen_dispatch", cat="compute"):
                toks, logps, gen_len, sums, *cache = fn(
                    self.params, prompt_tok, prompt_len, key
                )
            with tracer.span("gen_wait", cat="compute"):
                toks, logps, gen_len = (
                    to_host(toks),
                    to_host(logps),
                    to_host(gen_len),
                )
                for name, counter in tfm.decode_counters(cfg).items():
                    self._decode_sums[name] += to_host(
                        sums[name]).astype(float)
                    self.last_pool_stats.update(counter.report(
                        self._decode_sums[name], cfg, self.params))
        stats.update(self._kv_token_stats(s_total, sp, prompt_len, gen_len))
        if with_cache:
            return toks, logps, gen_len, cache[0]
        return toks, logps, gen_len

    def _get_gen_fn(
        self, b, sp, s_total, g: GenerationHyperparameters, with_cache=False,
        src: Optional[Tuple[int, ...]] = None,
    ):
        """The static program of one shape.  `src`: what `_shared_rows`
        gave — None, every row prefilled, or each row's source row."""
        in_place = self._expert_leaves_in_place
        sig = (
            b, sp, s_total, g.max_new_tokens, g.min_new_tokens, g.greedy,
            g.top_p, g.top_k, g.temperature, in_place, with_cache, src,
        )
        if sig in self._gen_fns:
            return self._gen_fns[sig]
        cfg = self.cfg
        eos = self.eos_token_id
        max_new = g.max_new_tokens
        row_kernel = self._row_kernel
        expert_kernel = self._expert_kernel
        counters = tfm.decode_counters(cfg)
        wave = self._prefill_wave_rows(b, sp)

        @jax.jit
        def gen(params, prompt_tok, prompt_len, key):
            bsz = prompt_tok.shape[0]
            seg = (
                jnp.arange(sp)[None, :] >= (sp - prompt_len)[:, None]
            ).astype(jnp.int32)
            valid_from = sp - prompt_len  # [B] first live cache slot
            cache = tfm.init_kv_cache(cfg, bsz, s_total, dtype=self.compute_dtype)
            # prefill returns logits at each row's last prompt token — the
            # distribution over the first response token.
            if src is not None:
                logits0, cache = self._prefill_distinct(
                    params, prompt_tok, seg, cache, src
                )
            elif wave == bsz:
                logits0, cache = tfm.prefill(
                    params, cfg, prompt_tok, seg, cache,
                    use_flash=self._use_flash,
                )
            else:
                logits0, cache = self._prefill_in_waves(
                    params, prompt_tok, seg, cache, wave
                )

            out_toks = jnp.zeros((bsz, max_new), jnp.int32)
            out_logps = jnp.zeros((bsz, max_new), jnp.float32)
            done = jnp.zeros((bsz,), bool)
            gen_len = jnp.zeros((bsz,), jnp.int32)

            def cond(state):
                step, _, _, done, *_ = state
                return (step < max_new) & ~jnp.all(done)

            def body(state):
                (step, logits, key, done, gen_len, out_toks, out_logps,
                 cache, sums) = state
                key, sub = jax.random.split(key)
                if g.min_new_tokens > 0:
                    logits = jnp.where(
                        (step < g.min_new_tokens)
                        & (jnp.arange(logits.shape[-1]) == eos)[None, :],
                        -1e10,
                        logits,
                    )
                tok, logp = sample_token(
                    logits, sub,
                    temperature=g.temperature, top_k=g.top_k, top_p=g.top_p,
                    greedy=g.greedy,
                )
                tok = jnp.where(done, eos, tok)
                out_toks = out_toks.at[:, step].set(jnp.where(done, 0, tok))
                out_logps = out_logps.at[:, step].set(jnp.where(done, 0.0, logp))
                gen_len = gen_len + (~done).astype(jnp.int32)
                new_done = done | (tok == eos)
                pos = prompt_len + step  # RoPE position per row
                slot = sp + step
                next_logits, cache, given = tfm.decode_step(
                    params, cfg, tok, pos, cache, slot, valid_from,
                    with_counts=True, experts_in_place=in_place,
                    row_kernel=row_kernel, expert_kernel=expert_kernel,
                )
                # What each kind's counter makes of this step: summed here,
                # no sync.
                at = LoopStep(slot, valid_from, cache, bsz)
                sums = {
                    name: sums[name] + counter.step(given.get(name), cfg, at)
                    for name, counter in counters.items()
                }
                return (
                    step + 1, next_logits, key, new_done, gen_len,
                    out_toks, out_logps, cache, sums,
                )

            sums = {
                name: jnp.zeros((counter.width(cfg, bsz),), jnp.float32)
                for name, counter in counters.items()
            }
            state = jax.lax.while_loop(cond, body, (
                0, logits0, key, done, gen_len, out_toks, out_logps, cache,
                sums))
            _, _, _, _, gen_len, out_toks, out_logps, cache, sums = state
            # `with_cache`: what the loop leaves in the cache, last.
            return (out_toks, out_logps, gen_len, sums) + (
                (cache,) if with_cache else ()
            )

        self._gen_fns[sig] = gen
        logger.info(
            f"compiled generator for shape b={b} sp={sp} s_total={s_total}"
        )
        return gen

    def _prefill_wave_rows(self, b: int, sp: int) -> int:
        """Rows the static program prefills at a time: all `b` where b x sp
        tokens fit `PREFILL_WAVE_TOKENS` (every batch of short prompts:
        the one prefill it always was), else the largest divisor of b that
        does.  A prefill's temporaries grow with its tokens — the q/k/v
        and attention output of every row, and under a mixture of experts
        the gathered (row, choice) pairs: 0.36 GB a 4,096-token row at 8
        choices of 2,304 wide, 11.6 GB at 32 rows where the chip has 16
        (compiled for a described v5e, PR 44) — and a wave bounds them.
        One device only: a wave slices the batch axis a mesh shards."""
        if self.mesh.size > 1 or b * sp <= PREFILL_WAVE_TOKENS:
            return b
        return max(
            (r for r in range(1, b) if b % r == 0
             and r * sp <= PREFILL_WAVE_TOKENS),
            default=1,
        )

    def _shared_rows(
        self, b: int, sp: int, max_new: int, src
    ) -> Optional[Tuple[int, ...]]:
        """Each of the `b` rows' source row where the program shares a
        prefill, else None.  It shares where the caller says which rows
        repeat (`src`), one does, and the prefill is what the call spends:
        the batch goes in waves, or it fits one and the prompt bucket is
        longer than the decode budget (`sp > max_new`) on one device (a
        mesh shards the batch axis the landing slices).  Every other batch
        keeps the program it had.  Rows past `src` (the pad to the batch
        sharding) are their own source."""
        if src is None:
            return None
        heavy = self.mesh.size == 1 and sp > max_new
        if self._prefill_wave_rows(b, sp) == b and not heavy:
            return None
        src = tuple(int(s) for s in src)
        src += tuple(range(len(src), b))
        return None if len(set(src)) == b else src

    def _prefill_distinct(self, params, prompt_tok, seg, cache, src):
        """The wave path over the DISTINCT rows (`src`'s own values, in
        row order), each landing at every row of its group -> (logits [B,
        V], the cache).  A prefill has no random part: the rows of a
        group got the same logits and cache, computed once each.

        One scan, as `_prefill_in_waves`: a wave's part cache lands in the
        whole one inside the wave, row by row at the rows a table names.
        The scan makes one trip more than there are waves, and that trip
        does nothing.  With one wave — every cell so far — a scan of its
        trips alone is a loop of one trip: XLA:TPU inlines it, assigns the
        decode loop's operands to other memory spaces than with the prefill
        in a loop before it, and rounds a decode matmul differently (chip
        runs, PR 61: lfm2's prefill bit for bit the parent's and its rows'
        samples parting from step 19 on).  With the spare trip the loop
        stays and the compiled decode loop is the parent's."""
        cfg = self.cfg
        bsz, sp = prompt_tok.shape
        first = sorted(set(src))
        rows = self._prefill_wave_rows(len(first), sp)
        waves = len(first) // rows
        # Where a distinct row lands: its group's rows, the last of them
        # again up to the widest group's count (written twice, the same).
        groups = [[r for r, s in enumerate(src) if s == f] for f in first]
        width = max(len(g) for g in groups)
        to = np.asarray(
            [g + g[-1:] * (width - len(g)) for g in groups], np.int32)

        def trips(x):  # [waves * rows, ...] -> [waves + 1, rows, ...]
            x = x.reshape(waves, rows, *x.shape[1:])
            return jnp.concatenate([x, jnp.zeros_like(x[:1])])

        def wave(carry, xs):
            i, tok, sg, to = xs

            def run(carry):
                cache, logits = carry
                part = tfm.init_kv_cache(
                    cfg, rows, cache.s_max, dtype=self.compute_dtype)
                new, part = tfm.prefill(
                    params, cfg, tok, sg, part, use_flash=self._use_flash)
                for j in range(rows):
                    for at in to[j]:
                        cache = jax.tree.map(
                            lambda whole, p: jax.lax.dynamic_update_slice_in_dim(
                                whole, p[:, j:j + 1], at, axis=1),
                            cache, part,
                        )
                        logits = jax.lax.dynamic_update_slice_in_dim(
                            logits, new[j:j + 1], at, axis=0)
                return cache, logits

            return jax.lax.cond(i < waves, run, lambda c: c, carry), None

        rows_of = np.asarray(first)
        (cache, logits), _ = jax.lax.scan(
            wave,
            (cache, jnp.zeros((bsz, cfg.vocab_size), jnp.float32)),
            (jnp.arange(waves + 1), trips(prompt_tok[rows_of]),
             trips(seg[rows_of]), trips(jnp.asarray(to))),
        )
        return logits, cache

    def _prefill_in_waves(self, params, prompt_tok, seg, cache, rows: int):
        """`tfm.prefill` over `rows` rows at a time -> (logits [B, V], the
        cache): a scan over the waves, each through a cache of its own
        rows which lands in the whole one at its rows (axis 1 of every
        population)."""
        cfg = self.cfg
        bsz, sp = prompt_tok.shape
        waves = bsz // rows

        def wave(cache, xs):
            i, tok, sg = xs
            part = tfm.init_kv_cache(
                cfg, rows, cache.s_max, dtype=self.compute_dtype)
            logits, part = tfm.prefill(
                params, cfg, tok, sg, part, use_flash=self._use_flash)
            cache = jax.tree.map(
                lambda whole, new: jax.lax.dynamic_update_slice_in_dim(
                    whole, new, i * rows, axis=1),
                cache, part,
            )
            return cache, logits

        cache, logits = jax.lax.scan(
            wave, cache,
            (jnp.arange(waves), prompt_tok.reshape(waves, rows, sp),
             seg.reshape(waves, rows, sp)),
        )
        return logits.reshape(bsz, -1), cache

    # -- output assembly --

    def _assemble(self, sample, prompt_key, prompt_lens, results, n):
        toks = sum(len(t[0]) for t in results.values())
        self._m_tokens.inc(toks)
        dt = time.monotonic() - self._gen_t0
        if dt > 0:
            # Wall-clock goodput of the whole call, park time included —
            # the per-server throughput the fleet table reports.
            self._m_goodput.set(toks / dt)
        return assemble_rollout(
            sample, prompt_key, n,
            lambda i, r: results[(i, r)],
            prompt_lens=prompt_lens,
        )


def assemble_rollout(
    sample: SequenceSample,
    prompt_key: str,
    n: int,
    fetch,  # (prompt_idx, response_idx) -> (gen_tokens, gen_logprobs, no_eos)
    prompt_lens: "Optional[List[int]]" = None,
) -> SequenceSample:
    """THE rollout packing layout, shared by the in-process generator and
    the remote generation client (system/gen_server.py) so the two can
    never drift: per response, full = prompt + generated tokens;
    prompt_mask covers the prompt; packed_logprobs is length len(full)-1
    with the generated-token logprobs at [pl-1, pl-1+len(gen))."""
    bs = sample.bs
    prompts = np.asarray(sample.data[prompt_key])
    bounds = sample.cu_seqlens(prompt_key)
    if prompt_lens is None:
        prompt_lens = [int(bounds[i + 1] - bounds[i]) for i in range(bs)]
    seq_ids, seq_logps, seq_masks = [], [], []
    seqlens_full: List[List[int]] = []
    seqlens_lp: List[List[int]] = []
    no_eos: List[List[float]] = []
    for i in range(bs):
        lens_i, lens_lp_i, noeos_i = [], [], []
        ptoks = prompts[bounds[i] : bounds[i + 1]]
        pl = prompt_lens[i]
        for r in range(n):
            gtoks, glogps, ne = fetch(i, r)
            gtoks = np.asarray(gtoks, np.int32)
            glogps = np.asarray(glogps, np.float32)
            full = np.concatenate([ptoks, gtoks]).astype(np.int32)
            seq_ids.append(full)
            mask = np.zeros(len(full), bool)
            mask[:pl] = True
            seq_masks.append(mask)
            lp = np.zeros(max(len(full) - 1, 0), np.float32)
            lp[pl - 1 : pl - 1 + len(gtoks)] = glogps
            seq_logps.append(lp)
            lens_i.append(len(full))
            lens_lp_i.append(max(len(full) - 1, 0))
            noeos_i.append(1.0 if ne else 0.0)
        seqlens_full.append(lens_i)
        seqlens_lp.append(lens_lp_i)
        no_eos.append(noeos_i)
    return SequenceSample(
        keys={
            "packed_input_ids", "packed_logprobs", "prompt_mask",
            "seq_no_eos_mask",
        },
        ids=list(sample.ids),
        seqlens={
            "packed_input_ids": seqlens_full,
            "prompt_mask": [list(x) for x in seqlens_full],
            "packed_logprobs": seqlens_lp,
            "seq_no_eos_mask": [[1] * n for _ in range(bs)],
        },
        data={
            "packed_input_ids": np.concatenate(seq_ids),
            "prompt_mask": np.concatenate(seq_masks),
            "packed_logprobs": np.concatenate(seq_logps)
            if seq_logps
            else np.zeros(0, np.float32),
            "seq_no_eos_mask": np.asarray(
                [x for row in no_eos for x in row], np.float32
            ),
        },
    )


def _hbm_owned(self) -> Dict[str, Any]:
    """`HostOffloadMixin.hbm_owned`, with the `cache` a parked session
    keeps between calls (page pool, recurrent state and conv tails inside
    it, the logits and token buffers); a call that ran to its end keeps
    none.  Down here, and not in the class, so that no line above moves:
    a Mosaic kernel's module carries its callers' lines into the compile
    cache's key."""
    parked = [s for s in (self._session, self._ep_session) if s is not None]
    return {
        "weights": self.params,
        "cache": [
            (s.pool, s.logits_buf, s.tokens_buf, s.pending_tok)
            for s in parked
        ],
    }


GeneratorEngine.hbm_owned = _hbm_owned

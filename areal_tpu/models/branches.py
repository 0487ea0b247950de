"""A branch kind, stated once.

A layer is a tuple of residual branches x += f(norm(x)) (`config.LayerPlan`)
and `BRANCHES` holds ONE record a kind: everything the programs, the engines
and the FLOP counts have to know of the kind is a field of its `Branch`, and
each of them walks the plan and reads the table — none names a kind.  A
record is defined beside the kind's own code (`mamba.py`,
`linear_attention.py`, `short_conv.py`, `lightning.py`; the attention and
MLP kinds' in `transformer.py`, which sees every record and puts them in the
table, in the order a plan's first refusal speaks and a cache's populations
are made).
"""

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class HybridLayoutError(NotImplementedError):
    """A layout or plane a hybrid layer pattern (linear-attention layers
    with recurrent state beside softmax-attention layers) cannot run on
    yet, refused by name rather than run wrong."""


class LatentLayoutError(NotImplementedError):
    """A layout or plane latent attention (a cache of latent rows, an
    absorbed decode step, leading dense layers before the scan) cannot run
    on yet, refused by name rather than run wrong."""


class WindowLayoutError(NotImplementedError):
    """A layout or plane a mix of sliding-window and full-attention layers
    (a ring of `attn_window` slots beside a cache of every slot, two
    rotary tables) cannot run on yet, refused by name rather than run as
    full attention."""


class Refusal(NamedTuple):
    """What a kind cannot run on yet: `layout`, the words for a mesh split
    over `model`, `seq` or `pipe`; `serving`, the words for the serving
    plane, None where its chunk runs the kind (`Branch.serve`)."""

    error: type
    layout: str
    serving: Optional[str]

    def of(self, serving: bool):
        """-> the error to raise, or None."""
        text = self.serving if serving else self.layout
        return self.error(text) if text else None


class Counter(NamedTuple):
    """Sums a kind adds to the static decode loop, with no host sync:
    `step(given, cfg, at)` reduces one decode step — `given`, what the
    kind's layers put out of `decode_step` under `name` ([layers, ...];
    None for a kind that gives nothing), `at` the loop's `LoopStep` — to
    f32 [width(cfg, at.rows)], the loop adds them up under `name`, and
    `report(sums, cfg, params)` turns a generate call's sums (`CallSums`)
    into `last_pool_stats` keys."""

    name: str
    width: Callable
    step: Callable
    report: Callable


class CallSums(np.ndarray):
    """A generate call's sums of one `Counter`, on the host.  The call's
    programs may carry vectors of different widths (`Counter.width` reads
    the loop's rows): `+=` adds a vector to the slots it has, so a slot
    that only some programs carry sums over those."""

    def __new__(cls):
        return np.zeros((0,)).view(cls)

    def __iadd__(self, more):
        wide = max(self.size, more.size)
        return (
            np.pad(self, (0, wide - self.size))
            + np.pad(more, (0, wide - more.size))
        ).view(CallSums)


class LoopStep(NamedTuple):
    """One iteration of the static decode loop, as a `Counter` sees it."""

    slot: Any  # the cache slot the step wrote
    valid_from: Any  # [B] first live slot a row
    cache: Any  # the `KVCache` the step left
    rows: int


@dataclasses.dataclass(frozen=True)
class Ctx:
    """What one forward hands every branch.  Over packed rows (the train
    stack, `prefill`): the rows' segments, the rotary tables (`window_rope`:
    the window layers' own, in a plan that has them), `use_flash`, the
    context-parallel region, `with_state` (prefill: a recurrent branch also
    leaves its final state and tail), `ring` / `ck_slots` (the entries of
    the cache's rings and compressed-key rows, where the caller keeps what
    a layer leaves), `backward` (the caller differentiates the stack under
    a remat policy).  One token a row (`decode_step`): `slot`, `valid_from`
    and the rings' `live` entries.  The serving chunk: `pages` and the
    slots' `lanes`.  The kernel choices: `expert_kernel` (resolved),
    `row_kernel` (None | bool | Mesh), `paged_kernel`; `stacked`: a grouped
    MoE model's expert leaves where the ragged kernels read them in place."""

    cfg: Any
    cos: Any
    sin: Any
    segment_ids: Any = None
    window_rope: Any = None
    use_flash: Optional[bool] = None
    cp_mesh: Any = None
    cp_manual: Any = None
    cp_zigzag: bool = False
    with_state: bool = False
    ring: Optional[int] = None
    ck_slots: Optional[int] = None
    backward: bool = False
    expert_kernel: bool = False
    row_kernel: Any = None
    stacked: Any = None
    slot: Any = None
    valid_from: Any = None
    live: Any = None
    paged_kernel: Optional[bool] = None
    pages: Any = None
    lanes: Any = None
    # (block ids, stream ids) [B, S] of the block-causal mask over packed
    # rows (`cfg.block_length`).
    block_mask: Any = None


@dataclasses.dataclass(frozen=True)
class Branch:
    """One kind of residual branch.

    `leaves`: the block leaves it owns, stacked over the layers that have
    it; `init(cfg, key, n, dense)` draws `n` layers' (None: `init_params`
    draws the attention and MLP leaves itself, their key splits as they
    always were).
    `cache`: what ONE layer keeps in the static cache, `KVCache` field ->
    f(cfg, batch, s_max, dtype) -> (shape, dtype); a field two kinds keep
    is one population, in layer order.
    `packed(ctx, h, blk) -> (out, left)` over packed rows [B, S, D], `left`
    what else it gives by name (`aux`, `counts`, and what it leaves in the
    cache by `KVCache` field); `step(ctx, h, blk, cache, li) -> (out,
    cache, given)` one token a row through layer `li` of its population,
    `given` its decode counters' inputs by `Counter.name`;
    `serve(ctx, h, blk, pools, li, at) -> (out, pools)` a lane of the
    serving chunk a token, `at` (the layer's place among the unit's layers
    of the kind, the scan step) — None where the chunk cannot run the kind,
    and then `refusal.serving` says why.
    `saved_as`: the named checkpoint of its output (`remat="dots_small"`);
    `remat_alone`: its layers are rematerialised a branch at a time.
    `matmul_params(cfg)`: matmul parameters a token of ONE layer, a
    recurrence counted as its multiply-adds; `attn_flops(cfg, n_tokens,
    sum_sq_seqlens)`: ONE layer's score-and-value FLOPs beside them;
    `flash_window(cfg)`: the band of keys its flash schedule keeps (the
    trainer counts the tiles it visits beside the full layers').
    `counter`: its sums in the static decode loop; `cache_stats(cfg, cache,
    batch, s_max)`: the `last_pool_stats` keys of a static program's cache;
    `train_stats(cfg, n_layers, segment_ids, row_kernel)`: its keys of a
    train step's stats; `grad_options(cfg, row_kernel)`: the compiler options a gradient
    program with the kind asks for."""

    leaves: Tuple[str, ...] = ()
    init: Optional[Callable] = None
    cache: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    packed: Optional[Callable] = None
    step: Optional[Callable] = None
    serve: Optional[Callable] = None
    refusal: Optional[Refusal] = None
    saved_as: str = "attn_out"
    remat_alone: bool = False
    matmul_params: Callable = lambda cfg: 0
    attn_flops: Optional[Callable] = None
    flash_window: Optional[Callable] = None
    counter: Optional[Counter] = None
    cache_stats: Optional[Callable] = None
    train_stats: Optional[Callable] = None
    grad_options: Optional[Callable] = None


BRANCHES: Dict[str, Branch] = {}


def branches_of(cfg) -> Dict[str, Branch]:
    """The records of the kinds `cfg.plan` has, in the table's order."""
    plan = cfg.plan
    return {name: b for name, b in BRANCHES.items() if plan.count(name)}


def nbytes(*arrays) -> int:
    return sum(x.size * x.dtype.itemsize for x in arrays)


def segment_starts(segment_ids):
    """[B, S-1] bool: where a packed row starts another sequence — a
    restart of every recurrence and conv inside the row."""
    return (segment_ids[:, 1:] != segment_ids[:, :-1]) & (segment_ids[:, 1:] > 0)


def segment_restarts(segment_ids):
    """The sequences a batch of packed rows starts, f32 scalar."""
    starts = segment_starts(segment_ids)
    return (jnp.sum(segment_ids[:, 0] > 0) + jnp.sum(starts)).astype(
        jnp.float32)

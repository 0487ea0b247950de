"""Pallas TPU flash attention over packed rows (segment-aware, causal).

The hot op of the framework: replaces the reference's flash-attn varlen CUDA
dependency (realhf/impl/model/modules/attn.py:24) with a TPU kernel built
for the [B, S] packed-row layout (segment_ids delimit sequences; attention
is causal-within-segment).

Design (flash attention v2; a schedule of 128 x 128 blocks, walked in
trips of up to 512 keys):
- A packed row concatenates unrelated sequences with non-decreasing ids and
  attention is causal within a sequence, so of the row's (q block, k block)
  square only a band along the diagonal can hold an unmasked element: for a
  q block the live k blocks are ONE interval (from the block where its
  first sequence starts to the diagonal), and for a k block the live q
  blocks are one interval.  `live_schedule` derives both interval tables
  from `segment_ids` on the device — per block the smallest real id and the
  largest id, a tile being live when the two ranges meet at or below the
  diagonal — as traced int32 data (O(S / 128) integers a row, a function of
  the ids alone, so the same for every layer of a program).  A schedule is
  never a static argument: a new batch compiles nothing.
- The kernels get the tables by scalar prefetch and spend grid steps and
  K/V (Q/dO) fetches on live tiles only.  forward and dq: grid
  (B*H, nq, chunks), one step per q block of 128, with the row's K/V
  resident in VMEM and an in-kernel loop over its live keys; dkv: grid
  (B*H, nk, chunks), one step per k block of 128, with the row's Q, dO,
  logsumexp and delta resident and a loop over its live queries.  A dead
  tile costs no step, no fetch and no arithmetic; a tile inside an
  interval that is masked all the same (ids that are not monotonic) is an
  exact no-op, so the tables decide speed, never results.
- The loop walks a TRIP of several schedule blocks at a time (`_widen`):
  512 keys (queries, in dkv), and one block of 128 in the forward kernel
  at head_dim 256 (`_trip_blocks`: a function of the shapes alone, cut to
  a divisor of the row's blocks).  What a tile costs on a v5e is not its products but the
  vector work around them — the mask, the exponentials, and per trip the
  softmax's column operations (a [128, 1] fp32 column is 16 vregs with
  one lane in use, as dear as a whole 128 x 128 operation), the lane
  broadcasts and the accumulator's rescale — so a trip four blocks wide
  pays the per-trip part once for four (forward 0.48 -> 0.18-0.25 us a
  128 x 128 tile, dq 0.33 -> 0.14-0.21: PERF.md section 6, PR 46).  The
  mask inside a trip is exact and a trip with no live block is the same
  exact no-op, so the width moves the order of the sums and nothing else.
  Wider (1,024) measured slower, and so did a narrower trip under a
  `window` of 1,024 or over rows of many short sequences.
- dkv holds every tile TRANSPOSED, [keys, queries]: S^T = K Q^T, dP^T =
  V dO^T, dV += P^T dO, dK += dS^T Q are then plain products with no
  operand to turn, and the q ids, logsumexp and delta lie along the lanes,
  a trip to a row ([1 or 8, 512]: 32 bytes a token each in VMEM where a
  [512, 1] column took 512 and 64 vregs a load).  0.46 -> 0.16-0.26 us a
  tile, where the q-major form gained nothing from a wider trip.
- `chunks` is 1 for every row whose resident operands fit `RESIDENT_BYTES`
  of VMEM: in bf16 about 38,000 tokens at head_dim 128 and 19,000 at 256
  for forward and dq, which hold K/V and the k ids, 34,000 and 18,000 for
  dkv (Q, dO and three rows).  A longer row is cut into equal chunks of
  whole trips (the largest divisor of its trip count that fits): the
  accumulators persist across the chunk steps, each step loops over the
  part of the interval inside its chunk, and the chunk index is clamped to
  the interval's chunks, so a step with nothing to do re-uses the resident
  block and fetches nothing.
- A BLOCK CHOICE (`BlockChoice`, attention by selection: `ops/
  block_sparse.py`) is one more operand of all three kernels and one more
  term of `_tile_mask`, `chosen[q, key_block[k]]`; the schedule is the
  causal / segment one.  The term is a product on the MXU — the q block's
  choice over a WINDOW of 128 blocks by the trip's keys' one-hot, both
  0 / 1 in bf16 — because a key's block is aligned to no tile (a segment
  starts anywhere) and compares and lane broadcasts a block would be
  vector work, which is what bounds these kernels.  Without the operand a
  call traces the program it always did.
- Online-softmax accumulators (m, l, acc) live in VMEM scratch; output and
  logsumexp are written on the last chunk step.  The backward kernels
  recompute the probability tiles from the saved logsumexp instead of
  materializing [S, S] (O(S) memory).  GQA stays in the index maps.

Precision.  q, k, v and dO go to the MXU in the type they arrive in, and
every product accumulates in fp32 (`preferred_element_type`); P and dS,
fp32 inside the kernel, are rounded to that type right before the product
that consumes them (PV, P^T dO, dS K, dS^T Q), as the generator's two
attention kernels do on every decode step and serving chunk
(`paged_attention._ragged_paged_kernel`,
`latent_attention._latent_decode_kernel`).  The scores, the
mask, `exp`, the softmax statistics (m, l, alpha, logsumexp), dP - delta
and the four accumulators stay fp32.  With bf16 inputs this is what the
kernels always computed on the chip: Mosaic runs an fp32 x fp32 product
at one bf16 pass, so the casts that used to stand in front of every
product bought no digit (results bit-equal with and without them, PR 46).
With fp32 inputs (the CPU tests) nothing is rounded.

Interpret mode (CPU) is used automatically off-TPU, which is how the unit
tests exercise the same kernel code path hermetically.
"""

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# Keys (forward, dq) or queries (dkv) one trip of a kernel's inner loop
# walks at head_dim 128 (`_trip_blocks`).
TRIP_ROWS = 512

# VMEM one kernel may spend on the operands its inner loop walks (one
# buffer of each; Pallas double-buffers them).  A row that needs more is
# cut into chunks.
RESIDENT_BYTES = 20 << 20
# A [1, rows] fp32 or [8, rows] int32 operand: 8 sublanes of 4 bytes a lane.
_ROW_BYTES = 32


def _interpret() -> bool:
    from areal_tpu.base.distributed import is_tpu_backend

    return not is_tpu_backend()


def row_kernel_form(choice, fits: bool = True):
    """Which form a per-row cache kernel takes (rows independent: latent
    decode attention, the Gated DeltaNet step) -> (use the Pallas kernel,
    the mesh to `shard_map` it over or None).  `choice`: None, by what the
    code can see — the kernel on a TPU backend where the shapes fit it
    (`fits`), the XLA form elsewhere; a MESH whose batch axes spread the
    rows, the same choice with the kernel run per device on its own rows;
    a bool forces either (interpreted off a TPU)."""
    from jax.sharding import Mesh

    mesh = choice if isinstance(choice, Mesh) else None
    if choice is None or mesh is not None:
        choice = fits and not _interpret()
    return bool(choice), mesh


def named_call(name: str, kernel, **kw):
    """`pl.pallas_call` under its stable device name: the kernel's `name=`
    and a `jax.named_scope` of the same name around the call.  The scope
    is what a profile reader keys on — it is certain to reach `op_name`."""
    call = pl.pallas_call(kernel, name=name, **kw)

    def named(*args):
        with jax.named_scope(name):
            return call(*args)

    return named


# ---------------------------------------------------------------------------
# The live tiles of a packed row
# ---------------------------------------------------------------------------


class Schedule(NamedTuple):
    """Inclusive block intervals, flat over (batch row, block): q block i
    of row b visits k blocks k_lo[b * nq + i] .. k_hi[...], k block j
    visits q blocks q_lo[b * nk + j] .. q_hi[...].  An empty interval is
    (0, -1).  Flat because a 2-D int32 table in SMEM pads its minor dim to
    128 words a row."""

    k_lo: jax.Array
    k_hi: jax.Array
    q_lo: jax.Array
    q_hi: jax.Array


def live_schedule(
    seg: jax.Array, block_q: int, block_k: int, causal: bool,
    window: "int | None" = None,
) -> Schedule:
    """seg [B, S] int32 -> the intervals of tiles that can hold an unmasked
    element.  A block's real ids span [smallest id > 0, largest id]; a tile
    is live when its q and k spans meet and, under `causal`, its first key
    is not past its last query.  With ids non-decreasing along the row that
    is exact and the live tiles of a block are contiguous; with any other
    ids the interval from the first live tile to the last still covers
    them.  `window` (causal only; a trace-time constant, None = none): a
    query sees the last `window` keys, itself included, so a tile is live
    only if its LAST key lies within `window` of its first query — the
    band raises a q block's `k_lo` and lowers a k block's `q_hi`, and the
    kernels' grids are the same."""
    b, s = seg.shape
    big = jnp.iinfo(jnp.int32).max

    def spans(block):
        x = seg.reshape(b, s // block, block)
        return jnp.min(jnp.where(x > 0, x, big), axis=-1), jnp.max(x, axis=-1)

    q_min, q_max = spans(block_q)  # [B, nq]; a block of padding: (big, 0)
    k_min, k_max = spans(block_k)
    nq, nk = q_min.shape[1], k_min.shape[1]
    live = (k_min[:, None, :] <= q_max[:, :, None]) & (
        k_max[:, None, :] >= q_min[:, :, None]
    )  # [B, nq, nk]
    qi = jnp.arange(nq, dtype=jnp.int32)[:, None]
    ki = jnp.arange(nk, dtype=jnp.int32)[None, :]
    if causal:
        live &= ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        live &= qi * block_q - (ki * block_k + block_k - 1) < window

    def interval(idx, n, axis):
        lo = jnp.min(jnp.where(live, idx, n), axis=axis)
        hi = jnp.max(jnp.where(live, idx, -1), axis=axis)
        return jnp.where(hi < 0, 0, lo).reshape(-1), hi.reshape(-1)

    return Schedule(*interval(ki, nk, 2), *interval(qi, nq, 1))


def all_tiles_schedule(rows: int, nq: int, nk: int) -> Schedule:
    """Every tile of every row's square: what the tests and `chip_smoke.py`
    hold the live schedule's results to, bit for bit."""
    zq = jnp.zeros((rows * nq,), jnp.int32)
    zk = jnp.zeros((rows * nk,), jnp.int32)
    return Schedule(zq, zq + nk - 1, zk, zk + nq - 1)


def _largest_divisor(n: int, limit: int) -> int:
    """The largest divisor of n within `limit` (at least 1)."""
    return max(t for t in range(1, max(min(limit, n), 1) + 1) if n % t == 0)


def _resident_blocks(n_blocks: int, block: int, token_bytes: int) -> int:
    """Blocks of the inner loop's operands one grid step holds in VMEM: the
    largest divisor of `n_blocks` within RESIDENT_BYTES (chunks are equal,
    so no step reads past the row)."""
    return _largest_divisor(
        n_blocks, RESIDENT_BYTES // (block * token_bytes)
    )


def _trip_blocks(n_blocks: int, block: int, head_dim: int,
                 backward: bool) -> int:
    """Schedule blocks one trip of a kernel's inner loop walks, from what
    the call can see: `TRIP_ROWS` keys (queries, in dkv), but ONE block in
    the forward kernel past head_dim 128 — there the forward keeps the
    order of its sums, and with it the bits of every log-prob the trainer
    and prefill compute, as they were before trips (PERF.md section 6,
    PR 46: what the wider orders did to GLM's reference check); then the
    largest divisor of the row's `n_blocks` within that, so trips are
    whole."""
    rows = TRIP_ROWS if backward or head_dim <= 128 else block
    return _largest_divisor(n_blocks, rows // block)


def _widen(lo, hi, n_blocks, block, head_dim, backward, unit):
    """A schedule's intervals and block in the inner loop's trips: the
    trips that hold a live block (an empty (0, -1) stays empty), and the
    scope that tells a trace how wide a trip the call took (`unit`: "keys"
    or "queries")."""
    r = _trip_blocks(n_blocks, block, head_dim, backward)
    return lo // r, hi // r, block * r, f"{unit}{block * r}"


def _live_in_chunk(lo_ref, hi_ref, row, c, tiles):
    """The part of row's interval inside chunk c, as loop bounds."""
    return (
        jnp.maximum(lo_ref[row], c * tiles),
        jnp.minimum(hi_ref[row], (c + 1) * tiles - 1) + 1,
    )


def _chunk_index(n_chunks: int, tiles: int):
    """Index-map helper: chunk c clamped into the chunks row's interval
    touches, so a step with no live tile keeps the resident block."""

    def idx(lo_ref, hi_ref, row, c):
        if n_chunks == 1:
            return 0
        return jnp.maximum(
            lo_ref[row] // tiles, jnp.minimum(c, hi_ref[row] // tiles)
        )

    return idx


def _tile_mask(seg_q, seg_k, qi, ki, block_q, block_k, causal, window=None,
               k_major=False, picked=None, block_causal=False):
    """seg_q [bq, 1], seg_k [1, bk] -> the tile's [bq, bk] mask; `k_major`:
    seg_q [1, bq], seg_k [bk, 1] -> the same mask transposed, [bk, bq], for
    the kernel that walks k blocks (dkv).  A sequence's tokens are
    contiguous in the row, so the distance between two of its positions is
    the distance between their places in the row: `window` masks keys
    `window` or more places behind the query.  `picked`: the tile of a
    block choice (`_picked_tile`), one more term.  `block_causal`: the ids
    are `block_codes` and the mask the block-causal one."""
    if block_causal:
        return _block_tile_mask(seg_q, seg_k)
    mask = (seg_q == seg_k) & (seg_q > 0)
    if picked is not None:
        mask &= picked
    if causal:
        shape = (block_k, block_q) if k_major else (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 if k_major else 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0 if k_major else 1
        )
        mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
    return mask


# The block-causal mask (generation by diffusion over blocks): the kernels'
# trace-time `block_causal` is set and the id operand holds, a token, its
# sequence, stream and block in ONE int32 (`block_codes`), so the kernels
# take no operand they did not have and a call without `blocks` traces the
# program it always did.
_BLOCK_BITS, _STREAM_BIT = 16, 1 << 16


def block_codes(seg, block_ids, stream_ids):
    """[B, S] ids -> the kernels' id operand under `block_causal`: the
    sequence's id above bit 17, the stream (0 clean, 1 masked) in bit 16,
    the block below (65,536 blocks a sequence, 16,383 sequences a row)."""
    return (
        (seg.astype(jnp.int32) << (_BLOCK_BITS + 1))
        | ((stream_ids.astype(jnp.int32) & 1) << _BLOCK_BITS)
        | (block_ids.astype(jnp.int32) & (_STREAM_BIT - 1))
    )


def _block_tile_mask(code_q, code_k):
    """`_tile_mask` of codes: the same sequence, and the key a clean token
    of an EARLIER block or a token of the query's own stream and block.
    No term reads a place in the row, so a key may lie after its query."""
    low = 2 * _STREAM_BIT - 1  # stream and block
    same_seq = ((code_q >> (_BLOCK_BITS + 1)) == (code_k >> (_BLOCK_BITS + 1))
                ) & (code_q > low)
    earlier = ((code_k & _STREAM_BIT) == 0) & (
        (code_k & (_STREAM_BIT - 1)) < (code_q & (_STREAM_BIT - 1)))
    own = (code_q & low) == (code_k & low)
    return same_seq & (earlier | own)


def _tile_rows(i, block):
    return pl.ds(pl.multiple_of(i * block, block), block)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _call(name, trip, kernel, sched_refs, args, *, grid, in_specs, out_specs,
          out_shape, scratch_shapes, resident_bytes, choice=None):
    """The kernels' common `pallas_call`: two schedule tables by scalar
    prefetch (and a block choice's window table, `_with_choice`), a VMEM
    limit that holds the double-buffered resident operands beside Mosaic's
    default 16 MiB for everything else, and around the kernel's own scope
    the one that names its trip (`_widen`)."""
    from jax.experimental.pallas import tpu as pltpu

    if choice is not None:
        win, operands, specs, units = choice
        kernel = _with_choice(kernel, units)
        sched_refs = (*sched_refs, win)
        args = (*operands, *args)
        in_specs = [*specs, *in_specs]
    call = named_call(
        name,
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched_refs),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * resident_bytes + (16 << 20)
        ),
        interpret=_interpret(),
    )
    with jax.named_scope(trip):
        return call(*sched_refs, *args)


# ---------------------------------------------------------------------------
# A block choice: one more term of the mask
# ---------------------------------------------------------------------------


class BlockChoice(NamedTuple):
    """Attention by selection (`ops/block_sparse.py`): a query sees a key
    only where `chosen[b, q, kv head, key_block[b, k]]`, ANDed with the
    segment and causal terms.  A key head's query heads share its choice
    (GQA stays in the index maps).  `key_block` follows the row — over any
    trip of keys it moves by fewer than `CHOICE_BLOCKS / 2` blocks — but is
    aligned to nothing: a segment starts anywhere."""

    chosen: jax.Array  # [B, S, Hkv, NB] bool
    key_block: jax.Array  # [B, S] int32, in [0, NB)


# The blocks one WINDOW of a choice holds: a tile's term is a product on
# the MXU, choice[rows, blocks] @ one-hot[blocks, columns] (0 / 1 in bf16,
# exact), over the window of blocks that holds the tile's keys' — the cost
# of one more QK^T whatever the row's length, on the unit that has the
# slack (the kernels are bound by vector work), where `chosen[q,
# key_block[k]]` as compares and lane broadcasts a block would be vector
# work.  Windows start every half: a trip's blocks, fewer than that, lie
# whole inside the window that starts at or below its first.  A window is
# a leading index of its operand (a dynamic lane offset does not lower).
CHOICE_BLOCKS = 128


def _choice_windows(chosen: jax.Array) -> jax.Array:
    """[B, S, Hkv, NB] bool -> [B, S, Hkv, W, CHOICE_BLOCKS] bf16: window
    w the blocks from CHOICE_BLOCKS / 2 * w on (zeros past NB)."""
    half = CHOICE_BLOCKS // 2
    nb = chosen.shape[-1]
    w = -(-nb // half)
    x = jnp.pad(chosen, ((0, 0),) * 3 + ((0, (w + 1) * half - nb),))
    x = x.reshape(*chosen.shape[:3], w + 1, half)
    return jnp.concatenate(
        [x[..., :-1, :], x[..., 1:, :]], axis=-1
    ).astype(jnp.bfloat16)


def _key_windows(key_block: jax.Array, unit: int, n_windows: int):
    """[B, S] -> (the window of each run of `unit` keys, flat [B * S / unit]
    int32 as the schedule's tables; each key's block within that window,
    one-hot [B, S / unit, unit, CHOICE_BLOCKS] bf16)."""
    b, s = key_block.shape
    kb = key_block.reshape(b, s // unit, unit)
    win = jnp.clip(
        jnp.min(kb, axis=-1) // (CHOICE_BLOCKS // 2), 0, n_windows - 1
    )
    hot = jax.nn.one_hot(
        kb - win[..., None] * (CHOICE_BLOCKS // 2), CHOICE_BLOCKS,
        dtype=jnp.bfloat16,
    )
    return win.reshape(-1).astype(jnp.int32), hot


def _choice_tables(choice: BlockChoice, nq, block_q, hkv, unit):
    """-> (the choice by q block and window [B, nq, block_q, Hkv, W,
    CHOICE_BLOCKS] bf16, W, and `_key_windows` by runs of `unit` keys)."""
    sel = _choice_windows(choice.chosen)
    n_win = sel.shape[3]
    sel = sel.reshape(-1, nq, block_q, hkv, n_win, CHOICE_BLOCKS)
    return sel, n_win, *_key_windows(choice.key_block, unit, n_win)


def _picked_tile(choice, spread):
    """[rows, CHOICE_BLOCKS] @ [CHOICE_BLOCKS, columns] -> the tile's bool
    [rows, columns].  Forward and dq: the q block's choice by the trip's
    keys' one-hot; dkv: the k block's one-hot by the trip's queries'
    choice, the same term transposed."""
    return jax.lax.dot_general(
        choice, spread, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) > 0.5


def _picked_keys(choice, b, hq, ki, j):
    """Forward and dq, trip `ki` (the resident chunk's j-th) of grid row
    `b`: the q block's [bq, bk] term, or None — and nothing traced —
    without a choice."""
    if choice is None:
        return None
    win_ref, sel_ref, hot_ref, trips = choice
    win = win_ref[(b // hq) * trips + ki]
    return _picked_tile(sel_ref[0, 0, win], hot_ref[0, j])


def _with_choice(kernel, units: int):
    """`kernel` under a block choice: a third prefetched table (`units` a
    row: the window of each trip of keys; in dkv, of each k block, read by
    the index maps alone) and two inputs ahead of the kernel's own, handed
    to it as `choice`."""

    def choosing(lo_ref, hi_ref, win_ref, sel_ref, hot_ref, *refs):
        return kernel(
            lo_ref, hi_ref, *refs, choice=(win_ref, sel_ref, hot_ref, units)
        )

    return choosing


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    k_lo_ref, k_hi_ref,  # scalar prefetch
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref,  # inputs
    o_ref, lse_ref,  # outputs
    m_scr, l_scr, acc_scr,  # scratch
    *, scale: float, block_q: int, block_k: int, hq: int, nq: int,
    tiles: int, causal: bool, window=None, choice=None, block_causal=False,
):
    b, qi, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [bq, d], in the inputs' type
    # Segment ids arrive sublane/lane-broadcast (Mosaic needs >=2D tiles
    # with aligned minor dims): q ids [bq, 8] -> [bq, 1], k ids
    # [8, bk] -> [1, bk].
    seg_q = seg_q_ref[0][:, 0:1]

    def tile(ki, _):
        j = ki - c * tiles  # the tile's place in the resident chunk
        rows = _tile_rows(j, block_k)
        k = k_ref[0, rows, :]  # [bk, d]
        v = v_ref[0, rows, :]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] fp32
        mask = _tile_mask(
            seg_q, seg_k_ref[0, j][0:1, :], qi, ki, block_q, block_k, causal,
            window, picked=_picked_keys(choice, b, hq, ki, j),
            block_causal=block_causal,
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    jax.lax.fori_loop(
        *_live_in_chunk(k_lo_ref, k_hi_ref, (b // hq) * nq + qi, c, tiles),
        tile, None,
    )

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_scr[:] + jnp.log(safe_l), NEG_INF)


def _seg_layouts(seg: jax.Array, block: int) -> Tuple[jax.Array, jax.Array]:
    """[B, S] int32 -> (ids as a column [B, S, 8], ids along the lanes by
    block [B, S / block, 8, block]).

    Mosaic requires >=2D tiles whose minor dims are 8/128-aligned or span the
    array; broadcasting ids over 8 sublanes/lanes (the official TPU flash
    kernel's trick) satisfies that at 8x int32 cost.  The blocked form is
    what a kernel holds resident and indexes by trip (a dynamic index on a
    leading dim, where a dynamic lane offset would not lower).  Ids are
    per-BATCH (not per-head): the BlockSpec index maps divide the b*h grid
    index by the head count, so no H-fold copy is materialized.
    """
    b, s = seg.shape
    column = jnp.broadcast_to(seg[:, :, None], (b, s, 8))
    rows = jnp.broadcast_to(
        seg.reshape(b, s // block, 1, block), (b, s // block, 8, block)
    )
    return column, rows


def _kv_row(hq: int, hkv: int):
    """Grid index (batch-major b*hq) -> kv row in the UNEXPANDED [B*hkv]
    array: in-kernel GQA — q head h reads kv head h // (hq//hkv), so the
    7x repeat_kv materialization never happens."""
    n_rep = hq // hkv
    return lambda b: (b // hq) * hkv + (b % hq) // n_rep


def _q_major_specs(hq, hkv, nq, nk, d, block_q, block_k, itemsize,
                   choice=None):
    """The kernels that walk q blocks (forward, dq): K/V tiles a step holds
    resident and their bytes, and the block specs — what a step holds of
    the q side, and the resident K/V chunk with its ids; last, a block
    choice as `_call` takes it (None without one): every window of the q
    block's choice a step, and resident beside K/V the keys' one-hot, a
    trip a leading index."""
    token_bytes = 2 * d * itemsize + 8 * 4
    if choice is not None:
        token_bytes += CHOICE_BLOCKS * 2
    tiles = _resident_blocks(nk, block_k, token_bytes)
    kv_row = _kv_row(hq, hkv)
    chunk = _chunk_index(nk // tiles, tiles)

    def q_side(width):
        return pl.BlockSpec(
            (1, block_q, width), lambda b, qi, c, lo, hi, *_: (b, qi, 0)
        )

    seg_q = pl.BlockSpec(
        (1, block_q, 8), lambda b, qi, c, lo, hi, *_: (b // hq, qi, 0)
    )
    seg_kb = pl.BlockSpec(
        (1, tiles, 8, block_k),
        lambda b, qi, c, lo, hi, *_: (
            b // hq, chunk(lo, hi, (b // hq) * nq + qi, c), 0, 0
        ),
    )
    kv = pl.BlockSpec(
        (1, tiles * block_k, d),
        lambda b, qi, c, lo, hi, *_: (
            kv_row(b), chunk(lo, hi, (b // hq) * nq + qi, c), 0
        ),
    )
    if choice is not None:
        sel, n_win, win, hot = _choice_tables(
            choice, nq, block_q, hkv, block_k
        )
        sel = sel.transpose(0, 3, 1, 4, 2, 5).reshape(
            -1, nq, n_win, block_q, CHOICE_BLOCKS
        )
        choice = (
            win,
            (sel, hot.swapaxes(2, 3)),  # one-hot [B, nk, blocks, bk]
            [
                pl.BlockSpec(
                    (1, 1, n_win, block_q, CHOICE_BLOCKS),
                    lambda b, qi, c, *_: (kv_row(b), qi, 0, 0, 0),
                ),
                pl.BlockSpec((1, tiles, CHOICE_BLOCKS, block_k), seg_kb.index_map),
            ],
            nk,
        )
    return (
        tiles, tiles * block_k * token_bytes, q_side, seg_q, seg_kb, kv,
        choice,
    )


def _fwd(
    q, k, v, seg, sched, hq, scale, block_q, block_k, causal, window=None,
    choice=None, block_causal=False,
) -> Tuple[jax.Array, jax.Array]:
    """q: [B*hq, S, D]; k/v: [B*hkv, S, D] (unexpanded GQA); seg: [B, S]
    int32; sched: the tiles to visit; choice: a `BlockChoice` or None.
    Returns (o [B*hq,S,D], lse [B*hq,S,1])."""
    bh, s, d = q.shape
    hkv = k.shape[0] // seg.shape[0]
    nq = pl.cdiv(s, block_q)
    k_lo, k_hi, block_k, trip = _widen(
        sched.k_lo, sched.k_hi, pl.cdiv(s, block_k), block_k, d, False,
        "keys",
    )
    nk = pl.cdiv(s, block_k)
    tiles, resident, q_side, seg_q_spec, seg_kb_spec, kv_spec, choice = (
        _q_major_specs(
            hq, hkv, nq, nk, d, block_q, block_k, k.dtype.itemsize, choice
        )
    )
    seg_q, seg_kb = _seg_layouts(seg, block_k)
    return _call(
        "flash_fwd",
        trip,
        functools.partial(
            _fwd_kernel,
            scale=scale, block_q=block_q, block_k=block_k, hq=hq, nq=nq,
            tiles=tiles, causal=causal, window=window,
            block_causal=block_causal,
        ),
        (k_lo, k_hi),
        (seg_q, seg_kb, q, k, v),
        grid=(bh, nq, nk // tiles),
        in_specs=[seg_q_spec, seg_kb_spec, q_side(d), kv_spec, kv_spec],
        out_specs=[q_side(d), q_side(1)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        resident_bytes=resident,
        choice=choice,
    )


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    k_lo_ref, k_hi_ref,
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *, scale, block_q, block_k, hq, nq, tiles, causal, window=None,
    choice=None, block_causal=False,
):
    b, qi, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]  # [bq, 1]
    delta = delta_ref[0]  # [bq, 1]
    seg_q = seg_q_ref[0][:, 0:1]

    def tile(ki, _):
        j = ki - c * tiles
        rows = _tile_rows(j, block_k)
        k = k_ref[0, rows, :]
        v = v_ref[0, rows, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _tile_mask(
            seg_q, seg_k_ref[0, j][0:1, :], qi, ki, block_q, block_k, causal,
            window, picked=_picked_keys(choice, b, hq, ki, j),
            block_causal=block_causal,
        )
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    jax.lax.fori_loop(
        *_live_in_chunk(k_lo_ref, k_hi_ref, (b // hq) * nq + qi, c, tiles),
        tile, None,
    )

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_lo_ref, q_hi_ref,
    seg_q_ref, seg_k_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, block_q, block_k, hq, nk, tiles, causal, window=None,
    choice=None, block_causal=False,
):
    b, ki, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k = k_ref[0]
    v = v_ref[0]
    seg_k = seg_k_ref[0][:, 0:1]  # [bk, 1]
    sel_ref = hot = None
    if choice is not None:  # the k block's one-hot [bk, blocks], a step's own
        _, sel_ref, hot_ref, _ = choice
        hot = hot_ref[0, 0]

    def tile(qi, _):
        j = qi - c * tiles
        rows = _tile_rows(j, block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, j]  # [1, bq]
        delta = delta_ref[0, j]  # [1, bq]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bk, bq]: the scores' transpose, as every tile below
        mask = _tile_mask(
            seg_q_ref[0, j][0:1, :], seg_k, qi, ki, block_q, block_k, causal,
            window, k_major=True,
            picked=None if hot is None else _picked_tile(
                hot, sel_ref[0, 0, j]),
            block_causal=block_causal,
        )
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    jax.lax.fori_loop(
        *_live_in_chunk(q_lo_ref, q_hi_ref, (b // hq) * nk + ki, c, tiles),
        tile, None,
    )

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq(q, k, v, do, lse, delta, seg, sched, hq, scale, block_q, block_k,
        causal, window=None, choice=None, block_causal=False) -> jax.Array:
    """dq [B*hq, S, D] in q's type: one grid step a q block, the loop over
    its live keys a trip at a time (`_widen`)."""
    bh, s, d = q.shape
    hkv = k.shape[0] // seg.shape[0]
    nq = pl.cdiv(s, block_q)
    k_lo, k_hi, block_k, trip = _widen(
        sched.k_lo, sched.k_hi, pl.cdiv(s, block_k), block_k, d, True,
        "keys",
    )
    nk = pl.cdiv(s, block_k)
    tiles, resident, q_side, seg_q_spec, seg_kb_spec, kv_spec, choice = (
        _q_major_specs(
            hq, hkv, nq, nk, d, block_q, block_k, k.dtype.itemsize, choice
        )
    )
    seg_q, seg_kb = _seg_layouts(seg, block_k)
    return _call(
        "flash_dq",
        trip,
        functools.partial(
            _dq_kernel,
            scale=scale, block_q=block_q, block_k=block_k, hq=hq, nq=nq,
            tiles=tiles, causal=causal, window=window,
            block_causal=block_causal,
        ),
        (k_lo, k_hi),
        (seg_q, seg_kb, q, k, v, do, lse, delta),
        grid=(bh, nq, nk // tiles),
        in_specs=[
            seg_q_spec, seg_kb_spec, q_side(d), kv_spec, kv_spec,
            q_side(d), q_side(1), q_side(1),
        ],
        out_specs=q_side(d),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        resident_bytes=resident,
        choice=choice,
    )


def _dkv(q, k, v, do, lse, delta, seg, sched, hq, scale, block_q, block_k,
         causal, window=None, choice=None, block_causal=False,
         ) -> Tuple[jax.Array, jax.Array]:
    """dk, dv [B*hq, S, D] fp32, per Q-HEAD (the grid walks q heads; the
    caller sums the heads that share a kv head): one grid step a k block,
    K/V and the k ids by step, the q side resident, the loop over its live
    queries a trip at a time."""
    bh, s, d = q.shape
    hkv = k.shape[0] // seg.shape[0]
    nk = pl.cdiv(s, block_k)
    q_lo, q_hi, block_q, trip = _widen(
        sched.q_lo, sched.q_hi, pl.cdiv(s, block_q), block_q, d, True,
        "queries",
    )
    nq = pl.cdiv(s, block_q)
    # The kernel holds the scores' TRANSPOSE, [keys, queries]: the k ids a
    # column, and along the lanes, a trip to a row, the q ids, lse and
    # delta — 32 bytes a token each in VMEM where a column takes 512.
    seg_k, seg_qb = _seg_layouts(seg, block_q)
    lse, delta = (x.reshape(bh, nq, 1, block_q) for x in (lse, delta))
    token_bytes = 2 * d * q.dtype.itemsize + 3 * _ROW_BYTES
    if choice is not None:
        token_bytes += CHOICE_BLOCKS * 2
    tiles = _resident_blocks(nq, block_q, token_bytes)
    kv_row = _kv_row(hq, hkv)
    chunk = _chunk_index(nq // tiles, tiles)

    def k_side(rows, width=d):
        return pl.BlockSpec(
            (1, block_k, width), lambda b, ki, c, lo, hi, *_: (rows(b), ki, 0)
        )

    def q_resident(rows=lambda b: b):
        return pl.BlockSpec(
            (1, tiles * block_q, d),
            lambda b, ki, c, lo, hi, *_: (
                rows(b), chunk(lo, hi, (b // hq) * nk + ki, c), 0
            ),
        )

    def q_rows(sublanes, rows=lambda b: b):
        return pl.BlockSpec(
            (1, tiles, sublanes, block_q),
            lambda b, ki, c, lo, hi, *_: (
                rows(b), chunk(lo, hi, (b // hq) * nk + ki, c), 0, 0
            ),
        )

    if choice is not None:
        # The term transposed: the k block's one-hot [bk, blocks] a step,
        # and resident beside Q the queries' choice in the k block's
        # WINDOW (the index map's, from the prefetched table), [blocks, bq]
        # a trip.
        sel, n_win, win, hot = _choice_tables(
            choice, nq, block_q, hkv, block_k
        )
        sel = sel.transpose(0, 3, 4, 1, 5, 2).reshape(
            -1, n_win, nq, CHOICE_BLOCKS, block_q
        )
        choice = (
            win,
            (sel, hot),
            [
                pl.BlockSpec(
                    (1, 1, tiles, CHOICE_BLOCKS, block_q),
                    lambda b, ki, c, lo, hi, win: (
                        kv_row(b), win[(b // hq) * nk + ki],
                        chunk(lo, hi, (b // hq) * nk + ki, c), 0, 0,
                    ),
                ),
                pl.BlockSpec(
                    (1, 1, block_k, CHOICE_BLOCKS),
                    lambda b, ki, c, *_: (b // hq, ki, 0, 0),
                ),
            ],
            nk,
        )
    return _call(
        "flash_dkv",
        trip,
        functools.partial(
            _dkv_kernel,
            scale=scale, block_q=block_q, block_k=block_k, hq=hq, nk=nk,
            tiles=tiles, causal=causal, window=window,
            block_causal=block_causal,
        ),
        (q_lo, q_hi),
        (seg_qb, seg_k, q, k, v, do, lse, delta),
        grid=(bh, nk, nq // tiles),
        in_specs=[
            q_rows(8, lambda b: b // hq),
            k_side(lambda b: b // hq, 8),
            q_resident(),
            k_side(kv_row),
            k_side(kv_row),
            q_resident(),
            q_rows(1),
            q_rows(1),
        ],
        out_specs=[k_side(lambda b: b), k_side(lambda b: b)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_k, d), jnp.float32),
            _vmem((block_k, d), jnp.float32),
        ],
        resident_bytes=tiles * block_q * token_bytes,
        choice=choice,
    )


def _bwd(
    scale, block_q, block_k, causal, res, do, window=None, choice=None,
    block_causal=False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q, k, v, o, lse, seg, sched = res
    bh, s, d = q.shape
    b = seg.shape[0]
    hq = bh // b
    hkv = k.shape[0] // b
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, S, 1]
    args = (q, k, v, do, lse, delta, seg, sched, hq, scale, block_q, block_k,
            causal, window, choice, block_causal)
    dq = _dq(*args)
    dk_x, dv_x = _dkv(*args)

    def group_sum(g):
        return (
            g.reshape(b, hkv, hq // hkv, s, d)
            .sum(axis=2)
            .reshape(b * hkv, s, d)
        )

    return dq, group_sum(dk_x).astype(k.dtype), group_sum(dv_x).astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_bhsd(
    q, k, v, seg, sched, choice, scale, block_q, block_k, causal, window,
    block_causal=False,
):
    return _flash_fwd_rule(
        q, k, v, seg, sched, choice, scale, block_q, block_k, causal, window,
        block_causal,
    )[0]


def _flash_fwd_rule(
    q, k, v, seg, sched, choice, scale, block_q, block_k, causal, window,
    block_causal,
):
    hq = q.shape[0] // seg.shape[0]
    o, lse = _fwd(
        q, k, v, seg, sched, hq, scale, block_q, block_k, causal, window,
        choice, block_causal,
    )
    return o, ((q, k, v, o, lse, seg, sched), choice)


def _flash_bwd_rule(
    scale, block_q, block_k, causal, window, block_causal, res, do
):
    # Ids, the schedule and a choice (bools and indices) carry no gradient.
    res, choice = res
    return (
        *_bwd(scale, block_q, block_k, causal, res, do, window, choice,
              block_causal),
        None, None, None,
    )


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S] int32, 0 = pad
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    window: "int | None" = None,
    choice: "BlockChoice | None" = None,
    blocks=None,
) -> jax.Array:
    """Segment-aware causal flash attention over packed rows.  GQA is
    native: kv stays at n_kv heads and the kernel's BlockSpec index maps
    route q head h to kv head h // n_rep — no repeat_kv materialization.
    `window` (a Python int, with `causal`): a query sees the last `window`
    keys of its sequence, itself included — one more term in the tile mask
    and a band in the schedule (`live_schedule`); None traces the program
    it always did.  `choice`: a `BlockChoice`, one more term in the tile
    mask of all three kernels and nothing in the schedule; it carries no
    gradient, and None traces the program it always did.  `blocks`:
    (block ids, stream ids) [B, S] — the BLOCK-causal mask in the causal
    term's place (`ops/attention.make_packed_mask`): the ids ride the id
    operand (`block_codes`), the schedule is the causal one — a stream
    starts, and a block lies, on a multiple of the block's length in the
    row (`engines/packing.py`), which divides a tile, so a visible key
    after its query lies in the query's own tile."""
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if blocks is not None and (window is not None or choice is not None):
        raise ValueError("the block-causal mask takes no window or choice")
    b, s, hq, d = q.shape
    hkv = k.shape[2]

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"sequence length {s} must be a multiple of block sizes "
            f"({block_q}, {block_k})"
        )

    def to_bhsd(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    seg = segment_ids.astype(jnp.int32)
    # Under `blocks` the schedule stays the causal one of the sequences' ids.
    ids = seg if blocks is None else block_codes(seg, *blocks)
    o = _flash_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), ids,
        live_schedule(seg, block_q, block_k, causal, window),
        choice, d**-0.5, block_q, block_k, causal, window,
        blocks is not None,
    )
    return o.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,  # [B, S, n_q, d]
    k: jax.Array,  # [B, S, n_kv, d]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S]
    mesh,
    causal: bool = True,
    window: "int | None" = None,
) -> jax.Array:
    """The multi-chip wrapper: Pallas kernels are not GSPMD-partitionable,
    so `shard_map` pins the layout — batch over (data, fsdp), heads over
    `model`, sequence unsharded (ring attention owns the seq axis) — and
    each device runs the kernel on its local shard.  Attention is
    independent per (batch row, head), so no collectives are needed; GQA
    locality requires n_kv % model_axis == 0 (contiguous head sharding
    keeps each q-head group with its kv head)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from areal_tpu.base.topology import (
        DATA_AXIS,
        FSDP_AXIS,
        MODEL_AXIS,
        SEQ_AXIS,
    )

    if mesh.shape[SEQ_AXIS] != 1:
        raise ValueError("flash_attention_sharded: seq axis must be 1 (CP "
                         "uses ring attention)")
    m = mesh.shape[MODEL_AXIS]
    if k.shape[2] % m or q.shape[2] % m:
        raise ValueError(
            f"flash_attention_sharded: the model axis ({m}) must divide "
            f"both head counts ({q.shape[2]}q/{k.shape[2]}kv)"
        )
    batch = (DATA_AXIS, FSDP_AXIS)
    spec_qkv = P(batch, None, MODEL_AXIS, None)
    spec_seg = P(batch, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_seg),
        out_specs=spec_qkv,
        check_vma=False,  # pallas_call outputs carry no vma metadata
    )
    def inner(ql, kl, vl, segl):
        return flash_attention(ql, kl, vl, segl, causal=causal, window=window)

    return inner(q, k, v, segment_ids)

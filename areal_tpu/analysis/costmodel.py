"""Analytical per-MFC cost model: predict wall and memory for candidate
layouts from a roofline calibrated against the profile store.

The model is deliberately first-order — it exists to RANK candidate
plans (analysis/profile.py feeds it measured records; apps/advisor.py
enumerates candidates), not to forecast microseconds:

- per-MFC wall = dispatch overhead
               + FLOPs / (achieved FLOP/s per device x devices x scaling)
               + attributed transfer bytes / fabric bandwidth

  FLOPs come from the measured record (the worker already stamps the
  analytic ``base/monitor.py`` count on every span) or, for shapes
  never measured, from the monitor formulas directly
  (:func:`workload_flops`).  Achieved FLOP/s is calibrated per MFC from
  the store — a roofline anchored at the measured operating point, so
  same-layout predictions reproduce the measurement and candidate
  layouts move along analytic scaling curves.

- scaling: data/fsdp axes scale near-linearly (they split the batch);
  each doubling of the model axis pays ``model_axis_eff`` (collective
  overhead), each pipe stage pays ``pipe_axis_eff``.

- per-MFC memory = params/shards + optimizer/shards + KV-pool watermark
  scaled by the candidate's per-device batch share.

- step composition: per-MFC predictions compose through the DFG levels
  (profile store ``topo`` entries — the topology as actually scheduled):
  barrier = sum over levels of the level max.  Pipeline-overlapped
  steps (``overlap_window`` >= 2, ``pipeline_chunk_seqs``) split the
  batch into n chunks and run stages as a software pipeline:
  T = fill (one chunk through every stage) + (n-1) x bottleneck-stage
  chunk time; ``overlap_window`` == 1 serializes the chunks (the
  bit-exact-vs-barrier mode) and predicts the barrier sum.

- param_realloc plans cost their moved bytes over the fabric bandwidth;
  the plan is a regex-rule PartitionSpec tree (:func:`match_partition_
  rules`) so "which params move" follows the same rule grammar
  ``parallel/sharding.py`` places them with.

Stdlib-only (no jax): runs on a bare advisor box; ``base/monitor.py``'s
FLOP formulas are jax-free at module level.
"""

import dataclasses
import math
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from areal_tpu.analysis.profile import ProfileKey

# Mirrors base/topology.ParallelConfig's letter grammar ("d4f2m2p2s2");
# kept dependency-free — topology pulls in jax at module level.
_AXIS_LETTERS = {"d": "data", "f": "fsdp", "m": "model",
                 "p": "pipe", "s": "seq"}
_LAYOUT_TOKEN = re.compile(r"([dfmps])(\d+)")


def parse_layout(s: str) -> Dict[str, int]:
    """'d4f2m2' -> {'data': 4, 'fsdp': 2, 'model': 2, 'pipe': 1,
    'seq': 1}.  Empty/unknown strings parse as the single-device
    layout."""
    out = {v: 1 for v in _AXIS_LETTERS.values()}
    pos = 0
    s = (s or "").strip().lower()
    for m in _LAYOUT_TOKEN.finditer(s):
        if m.start() != pos:
            return {v: 1 for v in _AXIS_LETTERS.values()}
        pos = m.end()
        out[_AXIS_LETTERS[m.group(1)]] = int(m.group(2))
    if pos != len(s):
        return {v: 1 for v in _AXIS_LETTERS.values()}
    return out


def layout_str(axes: Dict[str, int]) -> str:
    parts = []
    for letter, field in _AXIS_LETTERS.items():
        v = int(axes.get(field, 1))
        if v != 1 or letter == "d":
            parts.append(f"{letter}{v}")
    return "".join(parts)


def layout_devices(s: str) -> int:
    axes = parse_layout(s)
    n = 1
    for v in axes.values():
        n *= v
    return n


def batch_shards(s: str) -> int:
    """Ways the global batch is split (BATCH_AXES = data x fsdp)."""
    axes = parse_layout(s)
    return axes["data"] * axes["fsdp"]


def param_shards(s: str) -> int:
    """Ways each parameter is split (fsdp x model x pipe)."""
    axes = parse_layout(s)
    return axes["fsdp"] * axes["model"] * axes["pipe"]


# ---------------------------------------------------------------------------
# FLOP formulas for never-measured shapes (base/monitor.py, jax-free)
# ---------------------------------------------------------------------------


def workload_flops(cfg, itype: str, tokens: int,
                   sum_sq_seqlens: float) -> float:
    """Analytic FLOPs for one MFC call on a model config — the same
    formulas the worker stamps on spans, for candidate batch shapes the
    store has never measured."""
    from areal_tpu.base import monitor

    if itype == "train_step":
        return float(monitor.flops_train(cfg, tokens, sum_sq_seqlens))
    if itype == "generate":
        # Approximate: treat the whole output as generated tokens over a
        # mean prompt (callers with real per-seq lens should use
        # monitor.flops_generate directly).
        n = max(int(math.sqrt(max(sum_sq_seqlens, 1.0))), 1)
        return float(monitor.flops_generate(cfg, [tokens // 2], [tokens // 2])) \
            if n else 0.0
    return float(monitor.flops_forward(cfg, tokens, sum_sq_seqlens))


# ---------------------------------------------------------------------------
# param_realloc plans: regex-rule PartitionSpec trees (SNIPPETS.md [3])
# ---------------------------------------------------------------------------

# A "spec" here is a tuple of axis names (or None) per tensor dim, the
# jax-free shadow of a PartitionSpec — enough to decide residency.
Spec = Tuple[Optional[str], ...]


def match_partition_rules(
    rules: Sequence[Tuple[str, Spec]],
    named_shapes: Dict[str, Tuple[int, ...]],
) -> Dict[str, Spec]:
    """First-match regex rules -> spec per named parameter (the
    fmengine ``match_partition_rules`` shape, jax-free).  Scalars always
    replicate; an unmatched name raises — a silent replicate default
    hides real sharding-table gaps."""
    out: Dict[str, Spec] = {}
    for name, shape in named_shapes.items():
        if len(shape) == 0 or all(d == 1 for d in shape):
            out[name] = ()
            continue
        for pat, spec in rules:
            if re.search(pat, name) is not None:
                out[name] = tuple(spec)
                break
        else:
            raise ValueError(f"no partition rule matches param {name!r}")
    return out


def realloc_plan_bytes(
    named_shapes: Dict[str, Tuple[int, ...]],
    src_rules: Sequence[Tuple[str, Spec]],
    dst_rules: Sequence[Tuple[str, Spec]],
    dtype_bytes: int = 4,
) -> int:
    """Bytes a param_realloc plan moves: every parameter whose src and
    dst specs differ reshards its full global size (parallel/realloc.py's
    reshard span counts exactly this as `bytes_resharded` + `bytes_put`)."""
    src = match_partition_rules(src_rules, named_shapes)
    dst = match_partition_rules(dst_rules, named_shapes)
    moved = 0
    for name, shape in named_shapes.items():
        if src[name] == dst[name]:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        moved += n * dtype_bytes
    return moved


# ---------------------------------------------------------------------------
# Roofline calibration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    """Achieved (not peak) rates, calibrated from measured records."""

    # mfc label -> achieved FLOP/s per device at the measured layout.
    eff_flops_per_dev: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    # mfc label -> seconds per SEQUENCE for records with no FLOP count
    # (reward/other host-side MFCs scale with how many sequences they
    # grade, not with how often they're called — a chunked schedule
    # calls them more often on smaller slices for the same total).
    fixed_s_per_seq: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    # mfc label -> mean measured wall for FLOP-less records with no seq
    # count either (last-resort constant).
    fixed_wall_s: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    xfer_bytes_per_s: float = 1e9
    overhead_s: float = 1e-3
    # Efficiency retained per DOUBLING of the axis degree.
    model_axis_eff: float = 0.85
    pipe_axis_eff: float = 0.90
    batch_axis_eff: float = 0.97

    def to_dict(self) -> Dict[str, Any]:
        return {
            "eff_flops_per_dev": {
                k: round(v, 3)
                for k, v in sorted(self.eff_flops_per_dev.items())
            },
            "fixed_s_per_seq": {
                k: round(v, 6)
                for k, v in sorted(self.fixed_s_per_seq.items())
            },
            "fixed_wall_s": {
                k: round(v, 6)
                for k, v in sorted(self.fixed_wall_s.items())
            },
            "xfer_bytes_per_s": round(self.xfer_bytes_per_s, 3),
            "overhead_s": round(self.overhead_s, 6),
            "model_axis_eff": self.model_axis_eff,
            "pipe_axis_eff": self.pipe_axis_eff,
            "batch_axis_eff": self.batch_axis_eff,
        }


def calibrate(
    records: Iterable[Tuple[ProfileKey, Dict[str, float]]],
    overhead_s: float = 1e-3,
) -> Roofline:
    """Anchor the roofline at the measured operating points: achieved
    FLOP/s per device per MFC, constant walls for FLOP-less MFCs.

    The rate is WORK-weighted — total FLOPs over total device-seconds
    of compute wall — not a mean of per-call rates.  Predicting wall
    means dividing work by the rate, so the right pooled rate is the
    harmonic (work-weighted) one: an arithmetic mean of per-call rates
    overweights fast calls, and a store mixing large calls with many
    small noisy chunks (streamed executors) then systematically
    under-predicts total wall."""
    rf = Roofline(overhead_s=overhead_s)
    flops_sum: Dict[str, float] = {}
    devwall_sum: Dict[str, float] = {}
    fixed_acc: Dict[str, List[float]] = {}
    seq_wall: Dict[str, float] = {}
    seq_n: Dict[str, float] = {}
    for key, m in records:
        wall = float(m.get("wall_s_mean", 0.0))
        if wall <= 0:
            continue
        n_dev = max(layout_devices(key.layout), 1)
        calls = int(m.get("calls", 1))
        tflops = m.get("tflops_mean")
        if tflops:
            flops_sum[key.mfc] = (
                flops_sum.get(key.mfc, 0.0)
                + float(tflops) * 1e12 * calls
            )
            devwall_sum[key.mfc] = (
                devwall_sum.get(key.mfc, 0.0)
                + max(wall - overhead_s, 1e-9) * n_dev * calls
            )
        else:
            fixed_acc.setdefault(key.mfc, []).extend([wall] * calls)
            seqs = float(m.get("seqs_mean") or 0.0)
            if seqs > 0:
                seq_wall[key.mfc] = seq_wall.get(key.mfc, 0.0) + (
                    max(wall - overhead_s, 0.0) * calls
                )
                seq_n[key.mfc] = seq_n.get(key.mfc, 0.0) + seqs * calls
    for mfc, fl in flops_sum.items():
        rf.eff_flops_per_dev[mfc] = fl / devwall_sum[mfc]
    for mfc, vals in fixed_acc.items():
        rf.fixed_wall_s[mfc] = sum(vals) / len(vals)
    for mfc, w in seq_wall.items():
        if seq_n.get(mfc, 0.0) > 0:
            rf.fixed_s_per_seq[mfc] = w / seq_n[mfc]
    return rf


def _axis_scaling(rf: Roofline, layout: str) -> float:
    """Multiplicative efficiency of a layout vs single-axis: each
    doubling of a non-batch axis pays its retention factor."""
    axes = parse_layout(layout)
    eff = 1.0
    for field, per_doubling in (
        ("model", rf.model_axis_eff),
        ("pipe", rf.pipe_axis_eff),
        ("seq", rf.model_axis_eff),
        ("data", rf.batch_axis_eff),
        ("fsdp", rf.batch_axis_eff),
    ):
        deg = max(axes[field], 1)
        eff *= per_doubling ** math.log2(deg) if deg > 1 else 1.0
    return eff


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MFCPrediction:
    mfc: str
    wall_s: float
    mem_bytes: float
    compute_bound: bool

    def to_dict(self) -> Dict[str, Any]:
        return {
            "mfc": self.mfc,
            "wall_s": round(self.wall_s, 6),
            "mem_bytes": round(self.mem_bytes, 3),
            "compute_bound": self.compute_bound,
        }


def predict_mfc(
    key: ProfileKey,
    metrics: Dict[str, float],
    rf: Roofline,
    layout: Optional[str] = None,
) -> MFCPrediction:
    """Predict one MFC's wall and per-device memory under ``layout``
    (default: the measured layout)."""
    layout = layout if layout is not None else key.layout
    n_dev = max(layout_devices(layout), 1)
    tflops = float(metrics.get("tflops_mean") or 0.0)
    xfer_bytes = float(metrics.get("xfer_bytes_mean") or 0.0)
    xfer_s = xfer_bytes / max(rf.xfer_bytes_per_s, 1.0)
    seqs = float(metrics.get("seqs_mean") or 0.0)
    if tflops and key.mfc in rf.eff_flops_per_dev:
        eff = rf.eff_flops_per_dev[key.mfc] * _axis_scaling(rf, layout) \
            / max(_axis_scaling(rf, key.layout), 1e-9)
        compute_s = tflops * 1e12 / max(eff * n_dev, 1.0)
        wall = rf.overhead_s + compute_s + xfer_s
        compute_bound = compute_s >= (xfer_s + rf.overhead_s)
    elif seqs > 0 and key.mfc in rf.fixed_s_per_seq:
        wall = (
            rf.overhead_s + rf.fixed_s_per_seq[key.mfc] * seqs + xfer_s
        )
        compute_bound = False
    else:
        wall = rf.fixed_wall_s.get(key.mfc, rf.overhead_s) + xfer_s
        compute_bound = False
    shards = max(param_shards(layout), 1)
    mem = (
        float(metrics.get("param_bytes") or 0.0) / shards
        + float(metrics.get("opt_bytes") or 0.0) / shards
    )
    pool = float(
        metrics.get("pool_peak_bytes") or metrics.get("pool_bytes") or 0.0
    )
    if pool:
        # KV pool holds the per-device batch share: scale the measured
        # watermark by the batch-shard ratio between layouts.
        ratio = max(batch_shards(key.layout), 1) / max(
            batch_shards(layout), 1
        )
        mem += pool * ratio
    return MFCPrediction(
        mfc=key.mfc, wall_s=wall, mem_bytes=mem,
        compute_bound=compute_bound,
    )


def compose_step(
    levels: Sequence[Sequence[str]],
    walls: Dict[str, float],
    extra_s: float = 0.0,
) -> float:
    """Barrier composition: each level waits for its slowest MFC.  MFCs
    absent from ``walls`` contribute nothing (a level of unknowns is
    free, not infinite)."""
    total = extra_s
    for level in levels:
        vals = [walls[m] for m in level if m in walls]
        if vals:
            total += max(vals)
    return total


def compose_step_pipelined(
    levels: Sequence[Sequence[str]],
    walls: Dict[str, float],
    n_chunks: int,
    overlap_window: int,
    extra_s: float = 0.0,
) -> float:
    """Pipeline-overlap composition over the same levels: the batch is
    split into ``n_chunks`` retired-rollout chunks; each level is one
    pipeline stage whose per-chunk time is its barrier wall / n_chunks.

    ``overlap_window`` == 1 keeps chunks strictly serial (the bit-exact
    executor mode): the prediction degrades to the barrier sum.  A
    window >= 2 admits the classic fill + steady-state bound:
    T = sum(stage chunk times) + (n-1) x max(stage chunk time), with
    the in-flight cap still throttling how much of the non-bottleneck
    time hides: fraction hidden scales with (window-1)/window.
    """
    stage_walls = []
    for level in levels:
        vals = [walls[m] for m in level if m in walls]
        if vals:
            stage_walls.append(max(vals))
    if not stage_walls:
        return extra_s
    n = max(int(n_chunks), 1)
    if overlap_window <= 1 or n == 1 or len(stage_walls) == 1:
        return extra_s + sum(stage_walls)
    t = [w / n for w in stage_walls]
    bottleneck = max(t)
    full = sum(t) + (n - 1) * bottleneck
    serial = n * sum(t)
    w_frac = (min(overlap_window, n) - 1) / min(overlap_window, n)
    return extra_s + serial - (serial - full) * w_frac


@dataclasses.dataclass
class CandidatePlan:
    """One enumerable placement/parallelism candidate."""

    name: str
    gen_layout: str
    train_layout: str
    colocated: bool = True
    overlap_window: int = 1
    pipeline_chunk_seqs: int = 0   # 0 = no chunking
    realloc_bytes: float = 0.0     # gen<-train weight plan, per step

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "gen_layout": self.gen_layout,
            "train_layout": self.train_layout,
            "colocated": self.colocated,
            "overlap_window": self.overlap_window,
            "pipeline_chunk_seqs": self.pipeline_chunk_seqs,
        }


@dataclasses.dataclass
class PlanPrediction:
    plan: CandidatePlan
    step_s: float
    mem_bytes: float
    per_mfc: List[MFCPrediction]
    feasible: bool = True

    def to_dict(self) -> Dict[str, Any]:
        d = self.plan.to_dict()
        d.update(
            predicted_step_s=round(self.step_s, 6),
            predicted_mem_gb=round(self.mem_bytes / 1e9, 6),
            feasible=self.feasible,
            per_mfc=[p.to_dict() for p in self.per_mfc],
        )
        return d


def _is_gen(mfc: str) -> bool:
    return mfc.endswith(":generate")


def predict_plan(
    plan: CandidatePlan,
    latest: Dict[ProfileKey, Dict[str, float]],
    levels: Sequence[Sequence[str]],
    rf: Roofline,
    batch_seqs: int = 0,
    mem_budget_bytes: float = 0.0,
) -> PlanPrediction:
    """Compose per-MFC predictions under a candidate plan into a step
    prediction.  Generate MFCs take the plan's gen layout, everything
    else the train layout; a split (non-colocated) plan adds the weight
    realloc bytes to the step; chunked plans pipeline through
    :func:`compose_step_pipelined`."""
    preds: List[MFCPrediction] = []
    walls: Dict[str, float] = {}
    mem_train = 0.0
    mem_gen = 0.0
    for key, metrics in latest.items():
        layout = plan.gen_layout if _is_gen(key.mfc) else plan.train_layout
        p = predict_mfc(key, metrics, rf, layout=layout)
        preds.append(p)
        # Several batch shapes of one mfc: keep the slowest (the step
        # pays the heaviest shape each iteration).
        walls[key.mfc] = max(walls.get(key.mfc, 0.0), p.wall_s)
        if _is_gen(key.mfc):
            mem_gen = max(mem_gen, p.mem_bytes)
        else:
            mem_train += p.mem_bytes
    extra = plan.realloc_bytes / max(rf.xfer_bytes_per_s, 1.0)
    if plan.pipeline_chunk_seqs > 0 and batch_seqs > 0:
        n_chunks = max(
            math.ceil(batch_seqs / plan.pipeline_chunk_seqs), 1
        )
        step = compose_step_pipelined(
            levels, walls, n_chunks, plan.overlap_window, extra_s=extra
        )
    else:
        step = compose_step(levels, walls, extra_s=extra)
    # Colocated: gen and train share devices, memory adds; split: each
    # set pays its own (report the max pressure).
    mem = mem_train + mem_gen if plan.colocated else max(mem_train, mem_gen)
    feasible = mem_budget_bytes <= 0 or mem <= mem_budget_bytes
    preds.sort(key=lambda p: -p.wall_s)
    return PlanPrediction(
        plan=plan, step_s=step, mem_bytes=mem, per_mfc=preds,
        feasible=feasible,
    )


def enumerate_layouts(n_devices: int) -> List[str]:
    """Every (data, fsdp, model) factorization of ``n_devices`` (pipe
    and seq stay 1 — the CPU-cluster search space; chips widen this
    later), canonical string form, deduplicated."""
    out: List[str] = []
    for d in range(1, n_devices + 1):
        if n_devices % d:
            continue
        rest = n_devices // d
        for f in range(1, rest + 1):
            if rest % f:
                continue
            m = rest // f
            out.append(
                layout_str({"data": d, "fsdp": f, "model": m})
            )
    return sorted(set(out), key=lambda s: (layout_devices(s), s))


def rank_plans(
    predictions: Iterable[PlanPrediction],
) -> List[PlanPrediction]:
    """Feasible plans first, fastest first; infeasible plans trail in
    predicted-time order (still informative: what a bigger budget
    buys)."""
    return sorted(
        predictions, key=lambda p: (not p.feasible, p.step_s, p.plan.name)
    )

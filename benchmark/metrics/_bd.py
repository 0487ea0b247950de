"""What the readers of generation by diffusion over blocks share
(sdar_moe: `cfg.block_length`, on the static decode program): whether the
run's model is one, the generator's block counters, and device seconds per
forward under a scope of the block loop.  Every reader returns None for
another model and for a program that keeps no such scope or counter (the
parent of PR 68)."""
from benchmark.metrics import _moe
from benchmark.metrics._program import scope_seconds, step_median

# The scopes of the loop's FORWARDS (B tokens a row through every layer),
# and beside them the draw and the reveal.
FORWARDS = ("gen/bd_denoise", "gen/bd_commit", "gen/bd_first_block_logp")
SCOPES = FORWARDS + ("gen/bd_unmask",)


def is_bd(run):
    return bool(getattr(run.model_cfg, "block_length", 0))


def counter(run, name):
    """The median step's `bd/<name>` of the generator's pool stats."""
    if not is_bd(run):
        return None
    return step_median(run, "pool", lambda p: float(p[f"bd/{name}"]))


def forwards(run, kind):
    """Forwards of kind `denoise` | `commit` | `first_block` a generate
    call made, from the last step's counters; None without them."""
    pool = run.steps[-1]["pool"] if run.steps else {}
    return pool.get(f"bd/{kind}_forwards")


def all_forwards(run):
    """Every forward of the block loop a generate call made."""
    pool = run.steps[-1]["pool"] if run.steps else {}
    return pool.get("bd/forwards")


def loop_seconds(run):
    """Device seconds per traced step under the block loop's scopes."""
    if not is_bd(run):
        return None
    parts = [scope_seconds(run, s) for s in SCOPES]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)


def contexts(run):
    """Each row's mean cache length over the last step's block loop."""
    step = run.steps[-1]
    return [p + (l - p) / 2.0
            for l, p in zip(step["seq_lens"], step["prompt_lens"])]


def _ops(run):
    """(short name, scope, seconds) of the traced operations, or ()."""
    ops = (run.trace or {}).get("op_seconds_scoped") or {}
    for name, seconds in ops.items():
        short, _, tail = name.partition(" @")
        yield short, tail.rpartition(":")[0], seconds


def _ragged_seconds(run):
    """Device seconds per traced step of XLA's ragged-dot kernels of the
    block loop's forwards.  They carry no scope (`_moe.py`); they are told
    by their rows — rows x B tokens x experts a token, the same in every
    forward of the loop.  None where the trace names none, or another
    program of the run steps as many rows."""
    cfg = run.model_cfg
    rows = (len(run.steps[-1]["seq_lens"]) * cfg.block_length
            * cfg.n_experts_per_tok)
    total, seen = 0.0, False
    for short, scope, seconds in _ops(run):
        dims = _moe._dims(short)
        if len(dims) != 2 or dims[0] != rows:
            continue
        if short.startswith("ragged-dot-") and not short.startswith(
                "ragged-dot-metadata"):
            total, seen = total + seconds, True
        elif scope.endswith("layer/mlp/experts") and not scope.startswith(
                "gen/bd_"):
            return None
    return total / run.trace["traced_steps"] if seen else None


def mlp_seconds(run, *parts):
    """Device seconds per traced step in the MoE MLPs of the loop's
    forwards: the scope `layer/mlp` (or its named `parts` alone) under
    each of `FORWARDS`, and — for the whole — the ragged-dot kernels."""
    if not is_bd(run) or not getattr(run.model_cfg, "n_experts", 0):
        return None
    needles = [f"layer/mlp/{p}" for p in parts] or ["layer/mlp"]
    found = [scope_seconds(run, fwd, n) for fwd in FORWARDS for n in needles]
    if not parts:
        found.append(_ragged_seconds(run))
    if all(f is None for f in found):
        return None
    return sum(f or 0.0 for f in found)


def cache_copy_seconds(run):
    """Device seconds per traced step of the slices that copy ONE layer's
    K or V out of the stacked cache in front of a forward's attention: the
    operations directly under a scope of `FORWARDS` whose result is [1,
    rows, slots, key heads, head width].  None where the trace holds no
    operation under those scopes."""
    if not is_bd(run) or not run.steps:
        return None
    cfg, rows = run.model_cfg, len(run.steps[-1]["seq_lens"])
    total, seen = 0.0, False
    for short, scope, seconds in _ops(run):
        if scope not in FORWARDS:
            continue
        seen = True
        dims = _moe._dims(short)
        if len(dims) == 5 and dims[:2] == (1, rows) and dims[3:] == (
                cfg.n_kv_heads, cfg.head_dim):
            total += seconds
    return total / run.trace["traced_steps"] if seen else None

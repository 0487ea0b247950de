"""What the readers of the MoE block's device time share.

Everything the MoE MLP does runs under the scope `layer/mlp` and one of
its four parts (`router`, `dispatch`, `experts`, `combine`) — EXCEPT the
expert matmuls themselves.  XLA:TPU rewrites each `jax.lax.ragged_dot`
into a Mosaic kernel of its own (`%ragged-dot-none.N = ...
custom-call(...), custom_call_target="tpu_custom_call"`, fed by one
`%ragged-dot-metadata` kernel per group of three) and gives the new
instruction the op_name `ragged-dot-none`: the program's path is dropped,
so in a trace these kernels lie under NO scope and in no phase (seen in
the deviceless v5e compile and in the chip's trace, PR 26).  They are
found here by name, and told apart by program from the trace alone: the
activation multiply between them IS scoped (`<program>/.../layer/mlp/
experts`, result `bf16[rows, F]`), and a ragged kernel's two-dimensional
result has the same `rows` (tokens x experts per token) as the program it
runs in; a three-dimensional result (`[E, in, out]`, the weight gradient)
belongs to the train step.  The metadata kernels (microseconds, one name
in every program) are left out.
"""
import re

from benchmark.metrics._program import scope_seconds

_RESULT = re.compile(r" \w+\[([\d,]+)\]$")  # `... bf16[64,1024]`, no tuple
TRAIN, DECODE, PREFILL = "train/grad", "gen/decode_step", "gen/prefill"


def _dims(short):
    """Result dimensions from an operation's short name, () for a tuple."""
    m = _RESULT.search(short)
    return tuple(int(d) for d in m.group(1).split(",")) if m else ()


def _program_rows(ops):
    """{program scope: set of `rows`} from the scoped operations under
    `layer/mlp/experts` whose result is two-dimensional."""
    rows = {}
    for name in ops:
        short, _, tail = name.partition(" @")
        scope = tail.rpartition(":")[0]
        if not scope.endswith("layer/mlp/experts"):
            continue
        dims = _dims(short)
        if len(dims) != 2:
            continue
        for program in (TRAIN, DECODE, PREFILL):
            if f"/{program}/" in f"/{scope}/":
                rows.setdefault(program, set()).add(dims[0])
    return rows


def ragged_seconds(run, program):
    """Device self seconds PER TRACED STEP, mean over chips, of XLA's
    ragged-dot kernels that ran in `program` (`TRAIN`, `DECODE`,
    `PREFILL`).  None where the run was not traced, the trace names no
    such kernel, or two programs share a row count (nothing to tell them
    apart by)."""
    ops = (run.trace or {}).get("op_seconds_scoped")
    if not ops:
        return None
    rows = _program_rows(ops)
    mine = rows.get(program, set())
    if any(mine & other for p, other in rows.items() if p != program):
        return None
    total, seen = 0.0, False
    for name, seconds in ops.items():
        if not name.startswith("ragged-dot-") or name.startswith(
                "ragged-dot-metadata"):
            continue
        seen = True
        dims = _dims(name)
        if (len(dims) == 2 and dims[0] in mine) or (
                len(dims) == 3 and program == TRAIN):
            total += seconds
    return total / run.trace["traced_steps"] if seen else None


def mlp_seconds(run, program, phase=None):
    """`layer/mlp` of one program per traced step: the scoped operations
    (in one phase or in all) and, where `phase` is None, the ragged-dot
    kernels, which have no phase.  None for a program without either."""
    scoped = scope_seconds(run, program, "layer/mlp", phase=phase)
    ragged = None if phase else ragged_seconds(run, program)
    if scoped is None and ragged is None:
        return None
    return (scoped or 0.0) + (ragged or 0.0)

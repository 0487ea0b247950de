"""What the PROGRAM's own names add to a trace's reduction.

`benchmark/trace.py` reduces a trace with what the benchmark itself wrote
into it (`bench:<label>` spans) and names a device operation by its HLO
line.  Since PR 23 the program names things too, and this module reads
those names out of the same `.xplane.pb`:

  - every `tracer.span` of the program is a `jax.profiler.TraceAnnotation`
    named `areal:<name>` on the `/host:CPU` plane, one line per thread,
    on the device planes' clock (`areal:step` is a `StepTraceAnnotation`);
    scalar span arguments arrive as the event's stats (`step`);
  - every part of the model, the engines' programs and each Pallas kernel
    runs under a `jax.named_scope`, which reaches the trace as the
    operation's `op_name` path, e.g.
    `jit(grad_fn)/train/grad/transpose(jvp())/while/body/closed_call/
    checkpoint/rematted_computation/layer/attn/flash_fwd/flash_fwd/
    pallas_call:`.

Where the path is, as seen in traces of a TPU v5e with jax 0.9.0
(`benchmark/tests/data/`): NOT in what `jax.profiler.ProfileData` exposes.
An `XLA Ops` event's `.stats` there are `device_offset_ps`,
`device_duration_ps` and `Time Scale Multiplier`, and the HLO line that is
the event's name carries no `metadata={...}`.  The path is the `tf_op`
stat of the operation's entry in the device plane's `event_metadata`
(beside `hlo_category`, `flops`, `bytes_accessed`, `source`), and no
`xplane_pb2` module is importable in this image (`xprof` and
`tensorboard_plugin_profile` ship without their protos).  So `op_paths`
below reads exactly those fields from the protobuf wire format with the
standard library — `XSpace.planes` -> `XPlane.name`, `.event_metadata`,
`.stat_metadata` -> `XEventMetadata.name`, `.stats` -> `XStat.str_value`
or `.ref_value` — and the result is joined to `ProfileData`'s events by
name (the HLO line).  A Mosaic kernel's instruction is also NAMED after
its scope (`%flash_dkv.10 = ... custom-call(...)`), so kernel names reach
`trace.py`'s `op_seconds` without any of this.

Forward, recomputed forward and backward of one scope are told apart by
what JAX itself puts in the path: `rematted_computation` marks the
recomputation inside a `jax.checkpoint`, `transpose(jvp(...))` the
backward pass, anything else is forward.

`reduce` returns NEW keys only (and `breakdown`, with the longer names);
it calls `trace.reduce` for none of them and changes none of its keys.
`benchmark/run.py` `reduce_trace` merges the two into `Run.trace`, where
the readers under `metrics/` find them (`metrics/_program.py`
`scope_seconds`, `scope_share`).  By hand, on a trace directory:

    python3 -m benchmark.program_trace <trace dir or .xplane.pb> [chips]
"""

import json
import os
import re
import sys

from benchmark import trace

PROGRAM_PREFIX = "areal:"
PHASES = ("fwd", "recompute", "bwd")
# Path elements that JAX or XLA put there, not the program: programs,
# transforms, control flow.  What is left of a path is the scope.
_WRAPPER = re.compile(r"^(jit|pjit|jvp|transpose|vmap|pmap|xla_call)\(")
_STRUCTURE = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint", "remat", "remat2",
    "rematted_computation", "scan", "shard_map", "pallas_call", "core_call",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin",
))


# --------------------------------------------------------------------------
# The protobuf wire format, for the fields named above and nothing else
# --------------------------------------------------------------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i: i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield tag >> 3, value


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def op_paths(path):
    """{device plane name: {HLO line: op_name path}} of an `.xplane.pb`:
    the `tf_op` stat of every operation that has one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, event_meta, stat_names = "", [], {}
        for f2, value in _fields(plane):
            if f2 == 2:  # XPlane.name
                name = bytes(value).decode()
            elif f2 == 4:  # XPlane.event_metadata: map<int64, XEventMetadata>
                event_meta.append(_map_entry(value)[1])
            elif f2 == 5:  # XPlane.stat_metadata: map<int64, XStatMetadata>
                key, meta = _map_entry(value)
                stat_names[key] = bytes(dict(_fields(meta)).get(2, b""))
        if not trace.DEVICE_PLANE.match(name):
            continue
        paths = {}
        for meta in event_meta:
            line, tf_op = None, None
            for f3, value in _fields(meta):
                if f3 == 2:  # XEventMetadata.name
                    line = bytes(value).decode(errors="replace")
                elif f3 == 5:  # XEventMetadata.stats: XStat
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != b"tf_op":
                        continue
                    if 5 in stat:  # XStat.str_value
                        tf_op = bytes(stat[5])
                    elif 7 in stat:  # XStat.ref_value -> a stat_metadata name
                        tf_op = stat_names.get(stat[7])
            if line is not None and tf_op:
                paths[line] = tf_op.decode(errors="replace")
        out[name] = paths
    return out


# --------------------------------------------------------------------------
# A path -> (scope, phase)
# --------------------------------------------------------------------------


def _split(path):
    """Elements of an op_name path; a `/` inside brackets does not split
    (`transpose(jvp(train/grad))` is one element)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def scope_of(path):
    """`jit(f)/train/grad/transpose(jvp())/while/body/checkpoint/layer/mlp/
    dot_general:` -> ("train/grad/layer/mlp", "bwd").  The scope is what
    the program named, outermost first.  A transform keeps the scopes it
    wrapped inside its brackets (`jvp(embed)`, `transpose(jvp(train/
    grad))`): they are taken out; a run of names that repeats what is
    already there (the wrapped copy of the outer scope, a kernel's scope
    and its own name) counts once.  The last element is the primitive
    and is dropped."""
    parts = _split(path.rsplit(":", 1)[0])
    phase = "fwd"
    if "rematted_computation" in parts:
        phase = "recompute"
    elif any(p.startswith("transpose(") for p in parts):
        phase = "bwd"
    scope = []
    for p in parts[:-1]:
        while True:
            m = _WRAPPER.match(p)
            if not m or not p.endswith(")"):
                break
            p = "" if m.group(1) in ("jit", "pjit") else p[m.end():-1]
        run = [e for e in p.split("/")  # `bsd,dv->bsv` is an einsum's own
               if e and e not in _STRUCTURE and "->" not in e]
        if run and scope[-len(run):] != run:
            scope += run
    return "/".join(scope), phase


# --------------------------------------------------------------------------
# The reduction
# --------------------------------------------------------------------------


def _window(host_spans, devices):
    marks = [ev for ev in host_spans if ev[2] == trace.WINDOW_SPAN]
    if len(marks) >= 2:
        return min(m[0] for m in marks), max(m[1] for m in marks)
    every = [ev for evs in devices.values() for ev in evs] + [
        ev for ev in host_spans if ev[2] != trace.WINDOW_SPAN
    ]
    return min(e[0] for e in every), max(e[1] for e in every)


def _innermost(mid, spans):
    """For each midpoint the index of the shortest span covering it, -1
    where none does (the rule of `trace.idle_by_phase`)."""
    import numpy as np

    owner = np.full(len(mid), -1, np.int64)
    order = sorted(range(len(spans)), key=lambda i: spans[i][0] - spans[i][1])
    for i in order:  # longest first: shorter ones overwrite
        s, e, _ = spans[i]
        owner[(mid >= s) & (mid < e)] = i
    return owner


def idle_by_program_span(gaps, bench_spans, program_spans):
    """{`<bench label>/<program span>`: idle seconds}: each device gap to
    the innermost benchmark span AND the innermost `areal:` span covering
    its midpoint.  The part before the `/` is `trace.idle_by_phase`'s
    label, so a bench label's total is what it was; a gap under no
    program span keeps the bare label."""
    import numpy as np

    if not gaps:
        return {}
    g = np.asarray(gaps, np.float64)
    mid, dur = g.mean(axis=1), (g[:, 1] - g[:, 0]) / 1e9
    bench = _innermost(mid, bench_spans)
    prog = _innermost(mid, program_spans)
    pair = (bench + 1) * (len(program_spans) + 1) + prog + 1
    out = {}
    for code in np.unique(pair):
        b, p = divmod(int(code), len(program_spans) + 1)
        label = (bench_spans[b - 1][2][len(trace.SPAN_PREFIX):] if b
                 else "between requests (master)")
        if p:
            label += "/" + program_spans[p - 1][2][len(PROGRAM_PREFIX):]
        out[label] = out.get(label, 0.0) + float(dur[pair == code].sum())
    return out


def busy_inside(gaps, spans, w0, w1):
    """{bench label: device busy seconds inside its spans} on one chip: a
    span's length minus the idle gaps' overlap with it."""
    import numpy as np

    g = np.asarray(gaps, np.float64).reshape(-1, 2)
    out = {}
    for s0, s1, name in spans:
        s0, s1 = max(s0, w0), min(s1, w1)
        idle = np.clip(
            np.minimum(g[:, 1], s1) - np.maximum(g[:, 0], s0), 0, None
        ).sum()
        label = name[len(trace.SPAN_PREFIX):]
        out[label] = out.get(label, 0.0) + (max(s1 - s0, 0) - idle) / 1e9
    return out


def span_totals(lines, w0, w1):
    """{name: {"n", "total_s", "self_s"}} of the `areal:` events of the
    host threads (`lines`: one list of (start, end, name) per thread)
    inside the window; self = duration minus the `areal:` children on the
    same thread."""
    out = {}
    for events in lines:
        _, self_s, _, _ = trace.union_and_self(events, w0, w1)
        for s, e, name in events:
            if e <= w0 or s >= w1:
                continue
            rec = out.setdefault(name[len(PROGRAM_PREFIX):],
                                 {"n": 0, "total_s": 0.0, "self_s": 0.0})
            rec["n"] += 1
            rec["total_s"] += (min(e, w1) - max(s, w0)) / 1e9
        for name, sec in self_s.items():
            out[name[len(PROGRAM_PREFIX):]]["self_s"] += sec
    return out


def reduce(profile, paths, chips):
    """The new keys, from a `ProfileData` and `op_paths()` of its file."""
    devices, host_lines, bench = {}, [], []
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if trace.OPS_LINE in lines:
                plane_paths = paths.get(plane.name, {})

                def rename(line, plane_paths=plane_paths):
                    scope, phase = scope_of(plane_paths.get(line, ""))
                    short = trace.short_op_name(line)
                    return f"{short} @{scope}:{phase}" if scope else short

                devices[int(m.group(1))] = trace._line_events(
                    lines[trace.OPS_LINE], rename
                )
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                events = trace._line_events(line)
                bench += [ev for ev in events
                          if ev[2].startswith(trace.SPAN_PREFIX)]
                mine = [ev for ev in events
                        if ev[2].startswith(PROGRAM_PREFIX)]
                if mine:
                    host_lines.append(mine)
    devices = {i: ev for i, ev in sorted(devices.items())[:chips] if ev}
    if not devices:
        raise ValueError("the trace holds no device operations")
    w0, w1 = _window(bench, devices)
    bench = [ev for ev in bench if ev[2] != trace.WINDOW_SPAN]
    program = [ev for events in host_lines for ev in events]
    ops, idle, busy_in = {}, {}, {}
    for events in devices.values():
        _, self_s, gaps, _ = trace.union_and_self(events, w0, w1)
        for name, s in self_s.items():
            ops[name] = ops.get(name, 0.0) + s / len(devices)
        for label, s in idle_by_program_span(gaps, bench, program).items():
            idle[label] = idle.get(label, 0.0) + s / len(devices)
        for label, s in busy_inside(gaps, bench, w0, w1).items():
            busy_in[label] = busy_in.get(label, 0.0) + s / len(devices)
    scopes, kernels = {}, {}
    for name, s in ops.items():
        short, _, tail = name.partition(" @")
        if not tail:
            continue
        scope, _, phase = tail.rpartition(":")
        by_phase = scopes.setdefault(scope, dict.fromkeys(PHASES, 0.0))
        by_phase[phase] += s
        if "tpu_custom_call" in short:  # a Mosaic kernel: its own name
            kernel = scope.rsplit("/", 1)[-1]
            kernels[kernel] = kernels.get(kernel, 0.0) + s
    return {
        "program_spans": span_totals(host_lines, w0, w1),
        "busy_by_bench_span": busy_in,
        "idle_by_program_span": idle,
        "scope_seconds": scopes,
        "kernel_seconds": kernels,
        "op_seconds_scoped": ops,
        "breakdown": {"device_ops": trace._top(ops),
                      "idle_gaps": trace._top(idle)},
    }


def scope_total(reduced, *needles, phase=None):
    """Mean-over-chips self seconds of the operations whose scope holds
    every needle as an element run (`"train/grad"`, `"head_logprob"`), in
    one phase or in all."""
    total = 0.0
    for scope, by_phase in reduced["scope_seconds"].items():
        padded = f"/{scope}/"
        if all(f"/{n}/" in padded for n in needles):
            total += (by_phase[phase] if phase else sum(by_phase.values()))
    return total


def reduce_file(path, chips):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace.find_xplane(path)
    return reduce(ProfileData.from_file(path), op_paths(path), chips)


if __name__ == "__main__":
    out = reduce_file(sys.argv[1], int(sys.argv[2]) if sys.argv[2:] else 1)
    del out["op_seconds_scoped"]
    print(json.dumps(out, indent=1))

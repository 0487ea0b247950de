"""The reduction from the profiler's trace (`.xplane.pb`) to numbers.

Reads the file with `jax.profiler.ProfileData` and nothing else.  What it
relies on, as seen in a trace of this repo on a TPU v5e with jax 0.9.0
(`benchmark/tests/data/`):

  - one plane per chip, named `/device:TPU:<n>`; its line `XLA Ops` holds
    one event per executed HLO operation (nested: a `while` encloses the
    operations of its body), its line `XLA Modules` one per program run;
  - the plane `/host:CPU` holds the host threads; the benchmark's own
    spans appear there as events named `bench:<label>`
    (`jax.profiler.TraceAnnotation`, written by `benchmark/run.py` around
    each worker request), on the same clock as the device planes;
  - `start_ns` / `duration_ns` of every event are on one clock, but the
    device's and the host's are aligned only to about a millisecond (in
    the recorded trace a program starts 0.95 ms before the host call that
    launched it), which is nothing against a window of seconds;
  - an operation's name is its whole HLO line; Pallas (Mosaic) kernels
    are `custom-call`s with `custom_call_target="tpu_custom_call"` and
    carry no kernel name, collectives are named by their opcode.

Busy time of a chip is the UNION of its operation intervals inside the
window; idle is the window minus that.  An operation's own ("self") time
is its duration minus the part its nested operations cover, so the
per-name totals add up to the busy time and a `while` does not swallow
its body.  The outermost `while` of a program is kept whole as well: the
static decode program is one `lax.while_loop` over the new tokens, so its
longest outermost loop inside a generate span is the decode loop, without
prefill and without the host.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def find_xplane(trace_dir):
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _line_events(line, rename=None):
    names = {}  # the trace repeats a few thousand distinct names

    def short(name):
        if name not in names:
            names[name] = rename(name) if rename else name
        return names[name]

    return [(e.start_ns, e.start_ns + e.duration_ns, short(e.name))
            for e in line.events]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"[\s)]([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op_name(text):
    """The trace names an operation by its whole HLO line, e.g.
    `%fusion.356 = bf16[8,8960]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[28,...`.
    Keep the instruction's name, its opcode (and a custom call's target)
    and its result shape without layouts:
    `fusion.356 fusion bf16[8,8960]`."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    rest = _LAYOUT.sub("", rest)
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    shape = rest[: m.start()].strip() if m else ""
    target = _TARGET.search(text)
    if target:
        opcode += ":" + target.group(1)
    return f"{name.lstrip('%')} {opcode} {shape[:60]}".strip()


def union_and_self(events, w0, w1):
    """events: (start, end, name), any order, possibly nested.  Returns
    (busy seconds inside [w0, w1], {name: self seconds}, gaps, tops) where
    gaps are the (start, end) intervals inside the window with no event
    and tops the events that no other event encloses."""
    # At equal starts the longer event first: a `while` starts on the same
    # tick as the first operation of its body and encloses it.
    events = sorted(
        ((max(s, w0), min(e, w1), n) for s, e, n in events
         if e > w0 and s < w1),
        key=lambda ev: (ev[0], -ev[1]),
    )
    self_ns = {}
    stack = []  # (end, name, [covered-by-children ns], start)
    busy = 0.0
    gaps, tops = [], []
    cursor = w0  # end of the union so far

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, covered, start = stack.pop()
            self_ns[name] = self_ns.get(name, 0.0) + (end - start) - covered[0]
            if stack:
                stack[-1][2][0] += end - start

    for s, e, n in events:
        close(s)
        if stack:
            e = min(e, stack[-1][0])  # a child never outlives its parent
        else:
            tops.append((s, e, n))
            if s > cursor:
                gaps.append((cursor, s))
        if e > cursor:
            busy += e - max(s, cursor)
            cursor = e
        stack.append((e, n, [0.0], s))
    close(float("inf"))
    if w1 > cursor:
        gaps.append((cursor, w1))
    return busy / 1e9, {n: v / 1e9 for n, v in self_ns.items()}, gaps, tops


def reduce(profile, chips):
    devices = {}
    host_spans = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[int(m.group(1))] = _line_events(
                    lines[OPS_LINE], short_op_name
                )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [
                    ev for ev in _line_events(line)
                    if ev[2].startswith(SPAN_PREFIX)
                ]
    devices = {i: ev for i, ev in sorted(devices.items())[:chips] if ev}
    if not devices:
        raise ValueError("the trace holds no device operations")
    marks = [ev for ev in host_spans if ev[2] == WINDOW_SPAN]
    spans = [ev for ev in host_spans if ev[2] != WINDOW_SPAN]
    if len(marks) >= 2:  # written at the window's two ends
        w0, w1 = min(m[0] for m in marks), max(m[1] for m in marks)
    else:
        every = [ev for evs in devices.values() for ev in evs] + spans
        w0, w1 = min(e[0] for e in every), max(e[1] for e in every)
    busy, ops, idle, loops = [], {}, {}, {}
    n_events = 0
    for i, events in devices.items():
        n_events += len(events)
        b, self_s, gaps, tops = union_and_self(events, w0, w1)
        busy.append(b)
        for span, s in longest_loops(tops, spans).items():
            loops.setdefault(span, []).append(s)
        for name, s in self_s.items():
            ops[name] = ops.get(name, 0.0) + s / len(devices)
        for label, s in idle_by_phase(gaps, spans).items():
            idle[label] = idle.get(label, 0.0) + s / len(devices)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "busy_by_device": busy,
        "n_events": n_events,
        "op_seconds": ops,
        "idle_seconds": idle,
        "loop_seconds": loops_by_label(loops),
        "breakdown": {"device_ops": _top(ops), "idle_gaps": _top(idle)},
    }


def _top(seconds_by_name, n=10):
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, s] for name, s in ranked[:n]]


def idle_by_phase(gaps, spans):
    """Idle seconds by what the host was doing: each gap goes to the
    benchmark span that covers its midpoint — the shortest one if several
    do (requests run on threads) — else to the master, between requests."""
    import numpy as np

    if not gaps:
        return {}
    g = np.asarray(gaps, np.float64)
    mid, dur = g.mean(axis=1), (g[:, 1] - g[:, 0]) / 1e9
    labels = ["between requests (master)"]
    owner = np.zeros(len(g), np.int64)
    for s, e, name in sorted(spans, key=lambda ev: ev[0] - ev[1]):
        labels.append(name[len(SPAN_PREFIX):])
        owner[(mid >= s) & (mid < e)] = len(labels) - 1  # shorter overwrite
    out = {}
    for i in np.unique(owner):
        out[labels[i]] = out.get(labels[i], 0.0) + float(dur[owner == i].sum())
    return out


def longest_loops(tops, spans):
    """{span: seconds} on one chip: for each benchmark span (start, end,
    name) the longest outermost `while` operation whose midpoint falls
    inside it, whole, its body included; spans without one are left out."""
    whiles = [(s, e) for s, e, n in tops if n.split()[1:2] == ["while"]]
    out = {}
    for span in spans:
        inside = [e - s for s, e in whiles if span[0] <= (s + e) / 2 < span[1]]
        if inside:
            out[span] = max(inside) / 1e9
    return out


def loops_by_label(loops):
    """{span: [seconds on each chip that has one]} -> {label: [mean over
    those chips, ...]} with a label's spans in time order."""
    out = {}
    for span in sorted(loops):
        out.setdefault(span[2][len(SPAN_PREFIX):], []).append(
            sum(loops[span]) / len(loops[span])
        )
    return out


def op_seconds_matching(trace, patterns):
    """Mean-over-chips self seconds of operations whose name contains any
    of the patterns."""
    return sum(
        s for name, s in trace["op_seconds"].items()
        if any(p in name for p in patterns)
    )

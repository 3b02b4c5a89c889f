"""From a profiler trace (.xplane.pb) to numbers: the one reduction
every PR's traced run goes through.

Reads the file with `jax.profiler.ProfileData` and nothing else. A
device is a plane named `/device:TPU:<n>`; what ran on it is the line
`XLA Ops` (one event per executed HLO op, Pallas kernels included as
custom calls) and `XLA Modules` (one event per executed program). The
host is the plane `/host:CPU`; the benchmark's own thread is the line
that holds the window annotation (`bench.window`), and every
`jax.profiler.TraceAnnotation` of the benchmark and of the program's
telemetry spans lands on it.

    busy    union of the XLA-op intervals inside the window, per device,
            averaged over the devices that ran anything
    idle    window - busy; each gap goes to the innermost host event
            that covers its midpoint
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
PROBE = "bench.probe"
_OP_NAME = re.compile(r"^%?([^\s=(]+)")


def op_name(text: str) -> str:
    """`%fusion.42 = f32[...] fusion(...)` -> `fusion.42`."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text


def _line_arrays(line):
    starts, durs, names = [], [], []
    for e in line.events:
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
        names.append(e.name)
    return (np.asarray(starts, np.float64), np.asarray(durs, np.float64),
            names)


def _union(starts, ends):
    """Merged intervals of (starts, ends), as two sorted arrays."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], run_end[last]


def _nest(starts, durs):
    """Events of one line nest. Returns (order, ends, parent): the
    order that sorts them outer-before-inner, their end times in that
    order, and each one's enclosing event (index in that order, -1 at
    top level)."""
    order = np.lexsort((-durs, starts))
    s, e = starts[order], (starts + durs)[order]
    parent = np.full(s.size, -1, np.int64)
    stack = []
    for i in range(s.size):
        while stack and e[stack[-1]] <= s[i]:
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    return order, e, parent


def _self_time(starts, durs):
    """Each event's duration less that of the events directly inside it
    (a `while` or `conditional` op holds the ops of its body)."""
    order, _e, parent = _nest(starts, durs)
    d = durs[order]
    has = parent >= 0
    inner = np.bincount(parent[has], weights=d[has], minlength=d.size)
    out = np.empty_like(durs)
    out[order] = d - inner
    return out


def _innermost(starts, durs, names, points):
    """For each point, the name of the shortest event of one host line
    that covers it, or None."""
    order, e, parent = _nest(starts, durs)
    s = starts[order]
    out = []
    at = np.searchsorted(s, points, side="right") - 1
    for p, i in zip(points, at):
        while i >= 0 and e[i] < p:
            i = parent[i]
        out.append(names[order[i]] if i >= 0 else None)
    return out


def reduce(path: str, op_span: str, window: str = WINDOW,
           probe: str = PROBE) -> dict:
    """The numbers of one trace. Times in seconds.

    The traced window is the operations themselves: the host spans
    named `op_span` inside the `window` annotation. What the harness
    does between two operations (reading a result back for the check)
    is no part of it.

    window_s (sum of the operations' spans), busy_s (mean over
    devices), devices, n_ops (all devices), op_time (device seconds by
    op name, summed over devices, each op's own time: one that holds
    others counts less what it holds), idle_gaps (host names by idle
    time under them, longest first), probe (calls and device seconds
    per call of the programs run under the `probe` annotation), or {}
    where the trace holds no window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_lines, host_lines = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                device_lines.append((_line_arrays(lines["XLA Ops"]),
                                     _line_arrays(lines["XLA Modules"])
                                     if "XLA Modules" in lines else None))
        elif plane.name == "/host:CPU":
            host_lines = [_line_arrays(ln) for ln in plane.lines]
    main = next((ln for ln in host_lines if window in ln[2]), None)
    if main is None:
        return {}
    ms, md, mn = main
    i = mn.index(window)
    w0, w1 = ms[i], ms[i] + md[i]
    is_op = np.asarray([n == op_span for n in mn]) & (ms >= w0) & (ms < w1)
    if not is_op.any():
        return {}
    order = np.argsort(ms[is_op])
    o0 = ms[is_op][order]
    o1 = o0 + md[is_op][order]
    out = {"window_s": float((o1 - o0).sum()) * 1e-9,
           "traced_ops": int(o0.size)}

    def in_ops(t):
        k = np.searchsorted(o0, t, side="right") - 1
        return (k >= 0) & (t < o1[np.maximum(k, 0)])

    busy, n_ops, op_time = [], 0, defaultdict(float)
    gap_s, gap_e = None, None
    short = {}
    for (s, d, names), _mods in device_lines:
        inside = in_ops(s)
        if not inside.any():
            continue
        us, ue = _union(s[inside], (s + d)[inside])
        # clip each merged interval to the operation it started in
        ue = np.minimum(ue, o1[np.searchsorted(o0, us, side="right") - 1])
        busy.append(float((ue - us).sum()) * 1e-9)
        n_ops += int(inside.sum())
        own = _self_time(s[inside], d[inside])
        for j, t in zip(np.flatnonzero(inside), own):
            text = names[j]
            nm = short.get(text)
            if nm is None:
                nm = short[text] = op_name(text)
            op_time[nm] += t * 1e-9
        if gap_s is None:       # gaps are attributed on the first device
            edges_s = np.concatenate((o0, ue))
            edges_e = np.concatenate((us, o1))
            gs, ge = np.sort(edges_s), np.sort(edges_e)
            gap_s, gap_e = gs, ge
    if not busy:
        return out
    out.update(busy_s=float(np.mean(busy)), devices=len(busy),
               n_ops=n_ops, op_time=dict(op_time))

    keep = (gap_e - gap_s) > 0
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    under = defaultdict(float)
    mids = 0.5 * (gap_s + gap_e)
    # the window annotation itself covers everything: leave it out so a
    # gap under no other event reads as such
    not_window = np.asarray([n != window for n in mn])
    hits = _innermost(ms[not_window], md[not_window],
                      [n for n in mn if n != window], mids)
    for (g0, g1), hit in zip(zip(gap_s, gap_e), hits):
        under[hit or "(no host event)"] += (g1 - g0) * 1e-9
    out["idle_gaps"] = [[nm, t] for nm, t in sorted(
        under.items(), key=lambda kv: -kv[1])]

    if probe in mn:
        i = mn.index(probe)
        p0, p1 = ms[i], ms[i] + md[i]
        calls, total = 0, 0.0
        for _ops, mods in device_lines:
            if mods is None:
                continue
            s, d, _ = mods
            inside = (s >= p0) & (s < p1)
            calls += int(inside.sum())
            total += float(d[inside].sum()) * 1e-9
        if calls:
            out["probe"] = {"calls": calls,
                            "device_s_per_call": total / calls}
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's `breakdown`: the device ops that took most
    time and the longest idle gaps by what the host was doing."""
    ops = sorted(reduced.get("op_time", {}).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, t] for n, t in ops[:top]],
            "idle_gaps": reduced.get("idle_gaps", [])[:top]}

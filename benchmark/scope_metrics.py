"""Device time by the program's own stages: which level of the cycle,
which stage of the Krylov shell, kernel or glue.

What it reads. Two things, joined by HLO instruction name:

- `obs.trace["op_time"]`, the traced window's device seconds by
  instruction (`pad.580`, `_dia_smooth_call.80`), each op's own time,
  as `trace_reduce.reduce` made it;
- `amgx_tpu.telemetry.programs.scopes()`, the program's own table
  {instruction: scope}. The program traces every stage of a solve under
  a `jax.named_scope` (`amg.L<k>.presmooth`, `krylov.<NAME>.iter`,
  `refine.defect`, ...); the compiler keeps the scope in each
  instruction's `op_name` (a fusion carries its root's), and the program
  reads it back from the text of the executable a solve runs. A trace
  carries instruction names and no `op_name`, so the join is by name.

Every instruction of the window falls in exactly one of three parts:
`amg.*` (the cycle), `krylov.*` / `refine.*` (the Krylov shell and the
f64 defect-correction loop), and unscoped: an instruction the table
does not know, or knows without a scope. The eager programs a solve
runs beside the solve program (converting and zero-filling its
arguments) are unscoped, which is right: that is their device time, and
`device.unscoped_busy_share` is the instrument's own coverage. An eager
program's instruction that happens to be named like one of the solve
program's is put down to that one's scope; eager programs are a few
short ops.

The shares are over `busy_s`, as `kernels.pallas_busy_share` is. The
two `cycle.*` metrics cut the `amg.*` part two ways: by level
(`amg.L0` and `amg.L0.*`: the fine level) and by kernel or glue (glue
is what runs under `amg.*` and is not one of the Pallas kernels, whose
instructions are named `_dia_*_call*` / `_swell_*_call*`).

Where the program has no `telemetry.programs` (the parent of the PR
that brought it), or registered no solve program (a control's entry),
every reduction here returns None and the harness leaves the metric
out of the line.

The `scope` lines. The first reduction of a run that finds a table
prints, before the result line, one line per part of the cycle,

    scope amg.L<k> kernels=<s> glue=<s> ops=<n>

(`amg.tail.L<k>` and `amg.coarse` likewise, then `krylov`, the shell
and the f64 loop together, and `unscoped`): device seconds of the
part's Pallas kernels and of everything else traced under it, and the
number of distinct instructions of it that ran in the window. That is the table a
`perf_opt` issue quotes for "which level issues `pad.580`".
"""
from __future__ import annotations

import fnmatch
import re
from collections import defaultdict
from typing import Optional

KERNELS = ("_dia_*_call*", "_swell_*_call*")
SHELL = ("krylov.*", "refine.*")
_LEVEL = re.compile(r"^amg\.(L\d+|tail\.L\d+|coarse)")


def _any(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def program_scopes() -> Optional[dict]:
    """The program's {instruction: scope}, or None where it has none to
    give."""
    try:
        from amgx_tpu.telemetry import programs
    except ImportError:
        return None
    return programs.scopes() or None    # none held, or they disagree


def split(op_time: dict, scopes: dict) -> dict:
    """{"by_scope": {scope or None: seconds}, "kernels": {scope:
    seconds of Pallas kernels}, "ops": {scope: instructions}} of one
    window's op times."""
    by_scope, kernels = defaultdict(float), defaultdict(float)
    ops = defaultdict(int)
    for name, seconds in op_time.items():
        scope = scopes.get(name)
        by_scope[scope] += seconds
        ops[scope] += 1
        if _any(name, KERNELS):
            kernels[scope] += seconds
    return {"by_scope": dict(by_scope), "kernels": dict(kernels),
            "ops": dict(ops)}


def level_lines(parts: dict):
    """The `scope ...` lines of one split: cycle levels first, then
    the shell and what has no scope, all in one form."""
    rows = defaultdict(lambda: [0.0, 0.0, 0])
    for scope, seconds in parts["by_scope"].items():
        m = _LEVEL.match(scope or "")
        if m:
            label = "amg." + m.group(1)
        elif scope is None:
            label = "unscoped"
        elif _any(scope, SHELL):
            label = "krylov"
        else:                       # amg.* outside any level
            label = "amg"
        kernel = parts["kernels"].get(scope, 0.0)
        row = rows[label]
        row[0] += kernel
        row[1] += seconds - kernel
        row[2] += parts["ops"][scope]

    def order(label):
        m = re.search(r"L(\d+)$", label)
        return (label == "unscoped", label == "krylov",
                label.startswith("amg.tail"), label == "amg.coarse",
                int(m.group(1)) if m else -1)

    return [f"scope {label} kernels={k:.6f} glue={g:.6f} ops={n}"
            for label, (k, g, n) in sorted(rows.items(),
                                           key=lambda kv: order(kv[0]))]


def _parts(obs) -> Optional[dict]:
    """The window's split, made and printed once a run."""
    done = getattr(obs, "_scope_parts", None)
    if done is not None:
        return done or None
    op_time = obs.trace.get("op_time")
    scopes = program_scopes() if op_time else None
    parts = split(op_time, scopes) if scopes else {}
    obs._scope_parts = parts
    if parts:
        print("\n".join(level_lines(parts)), flush=True)
    return parts or None


def _share(obs, seconds: float) -> float:
    return 100.0 * seconds / obs.trace["devices"] / obs.trace["busy_s"]


def scope_share(obs, scopes, kernels: bool = True) -> Optional[float]:
    """Device time of the instructions whose scope matches one of the
    patterns, as a share of busy; with `kernels` false, less that of
    the Pallas kernels among them."""
    parts = _parts(obs)
    if parts is None or not obs.trace.get("busy_s"):
        return None
    hit = 0.0
    for scope, seconds in parts["by_scope"].items():
        if scope is not None and _any(scope, scopes):
            hit += seconds
            if not kernels:
                hit -= parts["kernels"].get(scope, 0.0)
    return _share(obs, hit)


def unscoped_share(obs) -> Optional[float]:
    """Device time of the instructions the program's table gives no
    scope, as a share of busy."""
    parts = _parts(obs)
    if parts is None or not obs.trace.get("busy_s"):
        return None
    return _share(obs, parts["by_scope"].get(None, 0.0))

"""Programs: what JAX compiled for this process, and which stage of the
solve each instruction of a solve program belongs to.

Two instruments, both passive until someone reads them.

COMPILE EVENTS. One listener on `jax.monitoring`, installed when this
module is imported, puts JAX's own compile events down to counters and
spans (telemetry/metrics.py, telemetry/spans.py):

    /jax/core/compile/jaxpr_trace_duration          compile.trace_s    span compile.trace
    /jax/core/compile/jaxpr_to_mlir_module_duration compile.lower_s    span compile.lower
    /jax/core/compile/backend_compile_duration      compile.backend_s  span compile.backend
                                                    compile.programs   (one per backend event)
    /jax/compilation_cache/cache_hits               compile.cache_hits
    /jax/compilation_cache/cache_misses             compile.cache_misses

Every span carries the event's `fun_name` in its args, so a retrace
names the program that was traced again. Traces nest (a jitted helper
traced inside `solve_fn` reports its own event and is inside the outer
one's duration): the span keeps each event's whole duration, the
counter adds each event's OWN time, so `compile.trace_s` never counts a
second twice and stays under the wall of the call that compiled.

SCOPE TABLES. The cycle and the Krylov shell put `jax.named_scope`s
round their stages (`amg.L<k>.presmooth`, `krylov.<NAME>.iter`,
`refine.defect`, ...: amg/cycles.py, solvers/base.py,
solvers/refinement.py). A scope is metadata: it reaches the optimized
HLO as the `op_name` of each instruction's `metadata={...}`, and a
fusion carries its root's. A profiler trace names device ops by HLO
instruction name (`pad.580`, `_dia_smooth_call.80`) and carries no
`op_name`, so the join is made here: `Solver.solve` registers the
executable it runs (`register`), and on request `op_names()` reads that
executable's own text and returns {instruction name: op_name},
`scopes()` {instruction name: innermost amg.* / krylov.* / refine.*
component of it}, each None where two registered programs disagree on
a name (names are unique within one program only).
`benchmark/scope_metrics.py` joins that with a trace's per-op device
times.

What the registry keeps is the runtime executable, never the solver,
its `Compiled` or an array, and at most `KEEP` of them (a program
registered again under the same label and signature takes its
predecessor's place: a time loop that retraces every step keeps one).
Nothing is dumped or parsed until a table is asked for; the parsed
table then takes the executable's place. The tables outlive the solver
(`AMGX_solver_destroy` drops the solver, not its table).
"""
from __future__ import annotations

import collections
import re
import threading
import time
from typing import Dict, Optional

import jax

from . import metrics as _tm
from . import spans as _spans

# ---------------------------------------------------------------------------
# compile events
# ---------------------------------------------------------------------------

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
# per thread: the trace events seen so far that no later event has
# claimed as its children, oldest first, as (start, duration)
_traces = threading.local()
_MAX_OPEN_TRACES = 1 << 16


def _own_trace_time(start: float, dur: float) -> float:
    """A trace event's duration less that of the events nested in it.
    Events of one thread end in order and nest or are disjoint, so the
    children of this one are the tail of the list that started after
    it did."""
    seen = getattr(_traces, "open", None)
    if seen is None:
        seen = _traces.open = collections.deque(maxlen=_MAX_OPEN_TRACES)
    own = dur
    while seen and seen[-1][0] >= start:
        own -= seen.pop()[1]
    seen.append((start, dur))
    return max(own, 0.0)


def _on_duration(event: str, duration: float, **kwargs):
    stage = _STAGES.get(event)
    if stage is None:
        return
    start = time.perf_counter() - duration
    _spans.record_span(f"compile.{stage}", start, duration,
                       args={"fun_name": str(kwargs.get("fun_name", "?"))})
    if stage == "trace":
        duration = _own_trace_time(start, duration)
    elif stage == "backend":
        _tm.inc("compile.programs")
    _tm.add(f"compile.{stage}_s", duration)


def _on_event(event: str, **kwargs):
    counter = _CACHE_EVENTS.get(event)
    if counter is not None:
        _tm.inc(counter)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

# ---------------------------------------------------------------------------
# scope tables
# ---------------------------------------------------------------------------

KEEP = 8
SCOPE_FAMILIES = ("amg.", "krylov.", "refine.")

_lock = threading.Lock()
# (label, signature) -> {"exe": runtime executable or None,
#                        "names": {instruction: op_name} or None}
_programs: "collections.OrderedDict" = collections.OrderedDict()

# `%name = ...` or `ROOT name = ...` with a metadata block that holds
# an op_name; the chip's text writes names bare, the CPU's with `%`
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*\bmetadata=\{[^}]*?'
    r'\bop_name="([^"]*)"', re.MULTILINE)


def register(label: str, signature, compiled) -> None:
    """Keep what it takes to name the stages of `compiled`'s
    instructions later: its runtime executable. `label` says whose
    program it is (`REFINEMENT.solve`), `signature` what it was
    compiled for; the pair's earlier program is replaced."""
    try:
        exe = compiled.runtime_executable()
    except Exception:       # a backend with no executable to show
        return
    key = (label, signature)
    with _lock:
        _programs.pop(key, None)
        _programs[key] = {"exe": exe, "names": None}
        while len(_programs) > KEEP:
            _programs.popitem(last=False)


def _registered():
    """The (label, signature) pairs held, oldest first (tests)."""
    with _lock:
        return list(_programs)


def _reset():
    """Forget every registered program (tests)."""
    with _lock:
        _programs.clear()


def parse_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of one HLO module's text."""
    return {m.group(1): m.group(2)
            for m in _INSTRUCTION.finditer(hlo_text)}


def scope_of(op_name: str) -> Optional[str]:
    """The innermost `amg.*` / `krylov.*` / `refine.*` component of an
    op_name path (`jit(solve_fn)/while/body/krylov.FGMRES.iter/
    amg.L0/amg.L0.presmooth/jit(_pad)/pad` -> `amg.L0.presmooth`), or
    None where it has none."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_FAMILIES):
            return part
    return None


def _names_of(entry: dict) -> Dict[str, str]:
    if entry["names"] is None:
        names: Dict[str, str] = {}
        for module in entry["exe"].hlo_modules():
            names.update(parse_op_names(module.to_string()))
        # the table takes the executable's place: the program's device
        # memory is not held for the sake of a dictionary
        entry["names"], entry["exe"] = names, None
    return entry["names"]


def _joined(of_op_name) -> Optional[Dict[str, object]]:
    """{instruction name: of_op_name(its op_name)} over the registered
    programs, or None where two of them disagree on a name."""
    with _lock:
        entries = list(_programs.values())
    out: Dict[str, object] = {}
    for entry in entries:
        for name, op_name in _names_of(entry).items():
            value = of_op_name(op_name)
            if out.setdefault(name, value) != value:
                return None
    return out


def op_names() -> Optional[Dict[str, str]]:
    """{instruction name: op_name} over the registered programs; empty
    where nothing registered. An instruction name is unique within one
    program only (`fusion.33` is in most) and a trace says no more
    than the name, so where two registered programs give one name two
    op_names there is no table to join by: None."""
    return _joined(str)


def scopes() -> Optional[Dict[str, Optional[str]]]:
    """{instruction name: scope} for every instruction `op_names`
    knows; the scope is None for an instruction of a registered
    program that sits in no named stage. None where two registered
    programs put one name in two scopes (two solvers, two right-hand
    side shapes in one process): filing one program's device time
    under the other's level would still add to 100%."""
    return _joined(scope_of)

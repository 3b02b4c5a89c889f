"""Hierarchical host spans with Perfetto export.

The reference's AMGX_timer tree (src/amgx_timer.cu) keeps parent/child
timing relationships; the port's original `profiling.py` flattened them
into a name->total dict. This module restores the tree: every
`span(name)` records a (name, start, duration, depth, parent, thread)
event into a bounded process-wide buffer, alongside the flat
(calls, total) accumulator the existing `profiling.timers()` /
`timers_total()` API keeps reading — the accounted-fraction contract
(`timers_total("amg.") / wall`, PR 3) is unchanged because the amg.*
setup regions remain DISJOINT LEAF spans by construction (the span
REGISTRY below is statically linted for that by tools/check_spans.py).

Spans measure HOST wall clock. Under async dispatch that means "time
until the region's Python body returned", not device occupancy — the
honest default for orchestration spans. Set `telemetry_sync=1` (config)
or AMGX_TPU_TELEMETRY_SYNC=1 (env) to fence device work at every span
boundary so host spans bound device occupancy; this perturbs pipelining
(the overlapped level shipping, XLA async dispatch), so it is a
debugging mode, not a production default.

`export_chrome_trace(path)` writes the recorded spans as Chrome
trace-event JSON ("X" complete events, microseconds), loadable by
Perfetto / chrome://tracing — the host-side timeline that sits next to
the device timeline `jax.profiler` captures.

REQUEST TRACING: spans (and instant `mark()` events) accept an `args`
dict; an args entry `trace=<id>` (or `traces=[ids]` for batched
stages touching several requests) tags the event with a request trace
id (`new_trace_id()`; serving mints one per ServiceTicket). The
export turns each trace id's tagged events into a Perfetto FLOW — a
connected s→t→…→f arrow chain through the tagged slices — so one
request's submit→queue→build→admit→chunk-cycles→checkpoint→finalize
path reads as a single arrow chain in the trace viewer, across
threads and (because the serving journal persists trace ids) across
service incarnations when a crash-recovered resume re-tags the
original id. `record_span()` records a span retroactively with
explicit timing (queue waits measured between submit and admission;
per-shard synthetic tracks use its `tid` override).
"""
from __future__ import annotations

import contextlib
import fnmatch
import hashlib
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import metrics as _metrics

# ---------------------------------------------------------------------------
# span-name registry
# ---------------------------------------------------------------------------

# Every span/trace_region name used in the package must match one of
# these fnmatch patterns (tools/check_spans.py enforces it statically).
# Patterns under ACCOUNTED_PREFIX are additionally checked to be
# pairwise non-nesting: the setup_accounted_fraction >= 0.9 contract
# sums them, so no amg.* span may ever double-count a child.
DECLARED_SPANS: Tuple[str, ...] = (
    # amg.* accounted setup leaves (disjoint by contract)
    "amg.l0_layout",
    "amg.host_pull",
    # REFINEMENT's reduced-precision copy of the operator, which the
    # inner chain and its hierarchy are then built against
    "amg.operator_cast",
    # the one fetch of the GEO levels' deferred wrap flags: where the
    # main thread waits for the level build's device work
    "amg.wrap_check",
    "amg.value_resetup",
    "amg.L*.selector",
    "amg.L*.strength",
    "amg.L*.cfsplit",
    "amg.L*.interp",
    # the cut of P to interp_truncation_factor / interp_max_elements
    # where it is a pass of its own (not the native D2 sweep, which
    # fuses it): a leaf of interp, opened only where the keys truncate
    "amg.L*.truncate",
    "amg.L*.layoutP",
    "amg.L*.transposeR",
    # classical device-parallel RS/HMIS first pass: runs INSIDE the
    # amg.L*.cfsplit leaf on the main thread, so it is declared
    # OUTSIDE the amg.* accounted prefix (summing both would
    # double-count the selector wall)
    "selector.device_sweep",
    # the value-only resetup's host stages (amg/value_resetup.py): run
    # INSIDE the amg.value_resetup leaf, so they too are declared
    # outside the accounted prefix
    "value_resetup.plan",
    "value_resetup.dispatch",
    "value_resetup.sync",
    "value_resetup.splice",
    "amg.L*.rap",
    # plan-split RAP (ops/spgemm.py): structure-phase plan build/lookup
    # and the fused value phase — disjoint siblings of amg.L*.rap (the
    # eager route's span), never nested inside it
    "amg.L*.rap_plan",
    "amg.L*.rap_values",
    "amg.L*.mf_detect",
    # the one fetch of a level's constancy flag (ops/stencil.py): where
    # the build's host thread waits for the level's device work; runs
    # INSIDE the amg.L*.mf_detect leaf, so it is declared outside the
    # accounted prefix
    "mf_detect.sync",
    "amg.L*.galerkin",
    "amg.L*.layout",
    "amg.L*.smoother_setup",
    # a colored smoother's coloring, made ahead of its setup by the
    # hierarchy (a sibling of smoother_setup, not inside it)
    "amg.L*.coloring",
    "amg.coarse_solver_setup",
    # a host-built level's hand-over to the ship worker, on the build
    # thread (hierarchy._prefetch_level): slim views of its operators
    # and the smoother's solve-data tree; a sibling of smoother_setup
    # and layout
    "amg.L*.prefetch",
    "amg.ship_resolve",
    "amg.device_sync",
    # the static signature of the finished hierarchy (amg/signature.py):
    # host work at the end of AMG.setup / AMG.resetup, after every
    # other leaf has closed
    "amg.static_signature",
    # the setup route a (re)setup took (amg/hierarchy.py): an instant
    # event beside the flight recorder's of the same name, so that a
    # reader of this buffer (resetup_rows) knows each re-setup's road
    "resetup.route",
    # an assembly of a set-up solver's solve-data tree for a caller
    # of solve_data() (solve_data.py): it may run under a caller's
    # amg.device_sync, so it is named outside the accounted prefix
    "solve_data.build",
    # overlapped ship worker (reports on its own thread; NOT summed
    # into the amg.* accounted fraction)
    "ship.cast_put",
    "ship.resolve_stragglers",
    # serving subsystem (amgx_tpu/serving/): the scheduler's cycle
    # phases + the AOT store round-trips
    "serving.step",
    "serving.admit",
    "serving.finalize",
    "serving.bucket_build",
    "serving.aot_export",
    "serving.aot_load",
    # serving fault tolerance: checkpoint/journal writes, restart
    # replay, hierarchy-structure persistence, bucket quarantine
    "serving.checkpoint",
    "serving.recover",
    "serving.quarantine",
    "serving.hstore_save",
    "serving.hstore_load",
    # request-path tracing (serving_tracing knob): per-ticket
    # lifecycle stages tagged with the ticket's trace id — submit
    # bookkeeping, shed decisions (instant), the retroactive queue
    # wait, the build the candidate ticket triggered, journal-replay
    # resume, and the terminal completion (instant; the flow chain's
    # last anchor)
    "serving.submit",
    "serving.shed",
    "serving.queue",
    "serving.build",
    "serving.resume",
    "serving.complete",
    # fleet router (serving/fleet.py): the per-request routing
    # decision — an instant event on the ticket's flow chain carrying
    # the serving replica id and route class (warm|cold|spill), the
    # cross-replica postmortem's attribution anchor
    "fleet.route",
    # fleet health (serving/health.py): every breaker/liveness
    # transition (SUSPECT, WEDGED, DEAD, OPEN/HALF_OPEN/CLOSED, DOWN,
    # DRAINING, RESTORED, PROBE) as an instant event — the Perfetto
    # view of an incident timeline
    "fleet.health.transition",
    # fleet failover (serving/fleet.py): one instant event per DOWN
    # path with its whole outcome (survivors, tickets requeued,
    # fingerprints rehomed, journal adopter + replay count, wall)
    "fleet.failover",
    # online config autotuner (serving/autotune.py): each shadow
    # solve as a real span (the idle-capacity cost is visible on the
    # timeline next to production work), each promote/demote/retire
    # verdict as an instant event — both tagged with the search's
    # trace id so the whole watch->shadow->promote chain reconstructs
    "autotune.shadow",
    "autotune.decision",
    # distributed comms/shard telemetry: one synthetic track per
    # shard in the Perfetto export (record_span with a per-shard tid)
    "shard.solve",
    # Solver.resetup's hand-over from the operator of the call before
    # to the new one (solvers/base.py): where the old one's arrays and
    # their host mirrors are released
    "solver.release_operator",
    # host stages of the outermost Solver.solve, disjoint children of
    # <NAME>.solve (solvers/base.py); each also adds its seconds to
    # the counter solve.stage_s.<stage>
    "solve.prepare",
    "solve.run",
    "solve.readback",
    "solve.report",
    # CsrMatrix.with_values (matrix.py): the host pass of the new
    # coefficients into the value layouts (for DIA through the kept
    # refill map) and the device_puts of what it made (counters
    # matrix.refill_host_s, matrix.upload_s, matrix.upload_bytes)
    "matrix.refill_host",
    "matrix.upload",
    # JAX's compile events, recorded retroactively with fun_name in
    # args (telemetry/programs.py)
    "compile.trace",
    "compile.lower",
    "compile.backend",
    # solver-tree entry points (dynamic solver names: CG.solve, ...).
    # NO catch-all patterns belong here: a `<anything>.*` entry would
    # let any typo'd two-segment name pass the static registry check
    # (telemetry's own engine spans live in the checker-exempt
    # spans.py and need no declaration)
    "*.setup",
    "*.resetup",
    "*.solve",
)

ACCOUNTED_PREFIX = "amg."


def is_declared(name: str) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in DECLARED_SPANS)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_tls = threading.local()
_records: List[dict] = []
_MAX_RECORDS = 100_000      # oldest half dropped past this
_dropped_to = -1.0          # start of the newest record dropped so far
_flat: Dict[str, Tuple[int, float]] = {}
_t0 = time.perf_counter()   # trace epoch (ts offsets in the export)

def env_sync() -> bool:
    """The AMGX_TPU_TELEMETRY_SYNC environment toggle (read at call
    time). The root-construction latch ORs this in, so the env var
    keeps fencing on even when configs leave telemetry_sync=0."""
    return os.environ.get("AMGX_TPU_TELEMETRY_SYNC", "0") not in (
        "", "0", "false", "False")


_sync = env_sync()


def set_sync(on: bool):
    """Enable/disable device fencing at span boundaries (the
    telemetry_sync knob)."""
    global _sync
    _sync = bool(on)


def sync_enabled() -> bool:
    return _sync


def _fence():
    """Best-effort device fence so a host span bounds device occupancy.
    Backends without a synchronization surface degrade to a no-op (the
    span then measures dispatch, as documented)."""
    try:
        import jax
        for d in jax.local_devices():
            try:
                d.synchronize_all_activity()
            except Exception:
                pass
    except Exception:
        pass


def _stack() -> list:
    """This thread's open spans, outermost first, a frame each (see
    span())."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


# ---------------------------------------------------------------------------
# the re-setup's account
# ---------------------------------------------------------------------------

# The outermost `<NAME>.resetup` span of a thread (Solver._setup_impl
# opens it with account=True) keeps books while it is open. Every span
# that closes inside it on that thread adds its SELF time (its wall
# less its children's) under its name: to the leaves, or, where the
# span is the re-setup's own or a solver's re-setup directly inside
# one (REFINEMENT.resetup > FGMRES.resetup > AMG.resetup: the chain
# that only hands the call down), to what no leaf covers. As the
# re-setup's span closes, its wall is the leaves' seconds, of which
# some are waits for the device, plus what is unnamed; each goes to a
# counter, and the two tables go into the span's record, where
# resetup_rows() finds them step by step.

# the leaves in which the host blocks on the device
WAIT_SPANS: Tuple[str, ...] = (
    "amg.wrap_check",
    "amg.host_pull",
    "value_resetup.sync",
    "mf_detect.sync",
)
SELECTOR_SPANS = "amg.L*.selector"


def _close_account(books: dict, wall: float) -> dict:
    leaves, under = books["leaves"], books["under"]
    wait = sum(s for n, s in leaves.items() if n in WAIT_SPANS)
    selector = sum(s for n, s in leaves.items()
                   if fnmatch.fnmatchcase(n, SELECTOR_SPANS))
    unnamed = sum(under.values())
    _metrics.add("resetup.call_s", wall)
    _metrics.add("resetup.device_wait_s", wait)
    _metrics.add("resetup.unnamed_s", unnamed)
    _metrics.add("amg.resetup.selector_s", selector)
    return {"leaves": leaves, "under": under, "wait": wait,
            "unnamed": unnamed}


@contextlib.contextmanager
def span(name: str, annotate: bool = True,
         args: Optional[Dict[str, Any]] = None,
         counter: Optional[str] = None,
         account: bool = False):
    """Record one hierarchical span (and accumulate the flat timer).
    With annotate=True the region is also a jax.profiler
    TraceAnnotation, so it shows up in captured device profiles — the
    nvtxRange analog `profiling.trace_region` has always been.
    `args` attaches extra key/values to the exported event (read when
    the span closes, so the body may fill the dict); a
    `trace`/`traces` entry additionally enrolls the span in that
    request's Perfetto flow chain (module docs). `counter` names a
    declared seconds counter (telemetry/metrics.py) that the span's
    host wall is added to, so a scrape reads the stage without the
    span buffer. `account` marks a re-setup's span: the outermost such
    span of a thread keeps the re-setup's account (above)."""
    if _sync:
        _fence()
    stack = _stack()
    parent = stack[-1][0] if stack else None
    books = getattr(_tls, "account", None)
    keeps = account and books is None
    if keeps:
        books = _tls.account = {"leaves": {}, "under": {}}
    # name, seconds of the children closed so far, hands the call down
    frame = [name, 0.0, keeps or (account and stack[-1][2])]
    stack.append(frame)
    t_start = time.perf_counter()
    ctx = contextlib.nullcontext()
    if annotate:
        try:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        except Exception:
            pass
    try:
        with ctx:
            yield
    finally:
        if _sync:
            _fence()
        t_end = time.perf_counter()
        stack.pop()
        dt = t_end - t_start
        if stack:
            stack[-1][1] += dt
        rec = {"name": name, "ts": t_start - _t0, "dur": dt,
               "self": dt - frame[1],
               "depth": len(stack), "parent": parent,
               "tid": threading.get_ident()}
        if args:
            rec["args"] = dict(args)
        if books is not None:
            table = books["under" if frame[2] else "leaves"]
            table[name] = table.get(name, 0.0) + dt - frame[1]
            if keeps:
                _tls.account = None
                rec["account"] = _close_account(books, dt)
        _commit(rec, name, dt)
        if counter is not None:
            _metrics.add(counter, dt)


def _commit(rec: dict, name: str, dt: float):
    global _dropped_to
    with _lock:
        _records.append(rec)
        if len(_records) > _MAX_RECORDS:
            _dropped_to = max(
                r["ts"] for r in _records[: _MAX_RECORDS // 2])
            del _records[: _MAX_RECORDS // 2]
        calls, tot = _flat.get(name, (0, 0.0))
        _flat[name] = (calls + 1, tot + dt)


def mark(name: str, args: Optional[Dict[str, Any]] = None):
    """Record one INSTANT event (zero-duration; exported as a Chrome
    'i' event) — lifecycle points like a shed decision or a request's
    terminal completion, where a span would be noise. Shares the span
    registry (check_spans lints mark names too) and the flow-chain
    tagging via args."""
    stack = _stack()
    rec = {"name": name, "ts": time.perf_counter() - _t0, "dur": 0.0,
           "depth": len(stack),
           "parent": stack[-1][0] if stack else None,
           "tid": threading.get_ident(), "ph": "i"}
    if args:
        rec["args"] = dict(args)
    _commit(rec, name, 0.0)


def record_span(name: str, t_start: float, dur: float,
                args: Optional[Dict[str, Any]] = None,
                tid: Optional[int] = None):
    """Record a span RETROACTIVELY with explicit timing: `t_start` in
    time.perf_counter() units, `dur` in seconds. Used for intervals
    only known after the fact (a ticket's queue wait, measured when it
    is admitted) and — via the `tid` override — for synthetic tracks
    (one Perfetto track per shard: the per-shard tallies of a
    distributed solve). Flat-timer accounting matches span()."""
    rec = {"name": name, "ts": t_start - _t0, "dur": float(dur),
           "depth": 0, "parent": None,
           "tid": int(tid) if tid is not None else threading.get_ident()}
    if args:
        rec["args"] = dict(args)
    _commit(rec, name, float(dur))


# ---------------------------------------------------------------------------
# request trace ids
# ---------------------------------------------------------------------------

_trace_seq = itertools.count(1)


def new_trace_id() -> str:
    """Mint a process-unique request trace id (pid + monotone counter
    + a coarse time suffix so ids stay distinct across process
    restarts — the successor of a crashed service mints fresh ids for
    new work while journal-replayed requests keep their ORIGINAL id,
    which is what links their spans across incarnations)."""
    return (f"{os.getpid():x}-{next(_trace_seq):x}-"
            f"{int(time.time() * 1e3) & 0xFFFFFF:x}")


def records() -> List[dict]:
    """Copy of the recorded span events (oldest first)."""
    with _lock:
        return [dict(r) for r in _records]


def flat_timers() -> Dict[str, Tuple[int, float]]:
    """The flat (calls, total_seconds) view per span name — the
    accumulator `profiling.timers()` has always returned."""
    with _lock:
        return dict(_flat)


def timers_total(prefix: str) -> float:
    """Total wall seconds under span names starting with `prefix`. The
    amg.* setup regions are maintained as DISJOINT leaf spans (enforced
    by the registry above + tools/check_spans.py) precisely so
    `timers_total("amg.") / wall` is an honest accounted fraction."""
    with _lock:
        return sum(tot for name, (_c, tot) in _flat.items()
                   if name.startswith(prefix))


def reset():
    """Drop recorded spans and flat accumulations (open spans on any
    thread keep recording into the fresh buffers when they close)."""
    global _dropped_to
    with _lock:
        _records.clear()
        _flat.clear()
        _dropped_to = -1.0


def clock() -> float:
    """Now, on the clock of the records' `ts`."""
    return time.perf_counter() - _t0


def resetup_rows(since: float = 0.0) -> Tuple[List[dict], bool]:
    """The re-setups' accounts step by step: one row per outermost
    re-setup still in the buffer that started at or after `since`
    (`clock()` units), oldest first: {"start", "wall", "route",
    "leaves": {name: seconds}, "wait", "unnamed", "under": {name:
    seconds}}: wall = the leaves + unnamed, `under` divides unnamed by
    the span whose self time it is, and `route` is what the
    hierarchy's `resetup.route` event inside it said (None where no
    hierarchy was re-set-up). A pure reader of records().
    The second value is True where the buffer has dropped records that
    started at or after `since`: rows of the window asked for may then
    be missing from the list."""
    with _lock:
        recs = [r for r in _records if r["ts"] >= since
                and ("account" in r or r["name"] == "resetup.route")]
        wrapped = _dropped_to >= since
    rows = []
    for r in recs:
        if "account" not in r:
            continue
        end = r["ts"] + r["dur"]
        routes = [m["args"]["route"] for m in recs
                  if m["name"] == "resetup.route" and m["tid"] == r["tid"]
                  and r["ts"] <= m["ts"] <= end]
        acct = r["account"]
        rows.append({"start": r["ts"], "wall": r["dur"],
                     "route": routes[-1] if routes else None,
                     "leaves": dict(acct["leaves"]), "wait": acct["wait"],
                     "unnamed": acct["unnamed"],
                     "under": dict(acct["under"])})
    rows.sort(key=lambda row: row["start"])
    return rows, wrapped


# ---------------------------------------------------------------------------
# Perfetto / chrome://tracing export
# ---------------------------------------------------------------------------


def _flow_id(trace: str) -> int:
    """Stable positive int flow id for a request trace id (Chrome
    flow events bind on (cat, name, id); the id must survive export
    across processes, so it is a digest, not an enumeration)."""
    return int.from_bytes(
        hashlib.blake2b(str(trace).encode(), digest_size=6).digest(),
        "big")


def trace_track(trace: str, base: int = 2_000_000) -> int:
    """Synthetic per-request track id for RETROACTIVE request-lane
    spans (the serving.queue wait): recorded on the admitting
    scheduler thread's real tid they would partially overlap its open
    cycle slices, which the Chrome trace format forbids (same-track
    slices must nest). One derived track per trace id keeps every
    request's lane self-consistent; a digest collision between two
    concurrent requests costs only a cosmetic overlap on a synthetic
    lane, never a corrupt scheduler track."""
    return base + _flow_id(str(trace)) % 1_000_000


def chrome_trace_events() -> List[dict]:
    """The recorded spans as Chrome trace-event events — 'X' complete
    slices (instant marks as 'i') with ts/dur in microseconds from the
    trace epoch, one track per host thread. Nesting is positional
    (Perfetto stacks overlapping events on a track), so parent linkage
    needs no explicit ids.

    Events whose args carry a request trace id (`trace=<id>` /
    `traces=[ids]`) additionally yield Perfetto FLOW events: per trace
    id, the tagged events sorted by start time become one s→t→…→f
    chain, each flow anchor emitted at its slice's start on the same
    pid/tid so it binds to that slice — the single connected arrow
    chain per request the serving layer's tracing promises. Flow
    anchors only bind to SLICES, so a trace-tagged instant mark (a
    shed decision, the terminal serving.complete) exports as a
    1-microsecond 'X' slice instead of an unbindable 'i' event —
    untagged marks stay true instants."""
    evs = []
    flows: Dict[str, List[Tuple[float, int, int]]] = {}
    for r in records():
        args = {"depth": r["depth"], "parent": r["parent"]}
        extra = r.get("args") or {}
        args.update(extra)
        ph = r.get("ph", "X")
        tr = extra.get("trace")
        tagged = ([tr] if tr else []) + [
            t for t in (extra.get("traces") or ()) if t]
        if ph == "i" and tagged:
            ph = "X"                 # bindable micro-slice (see docs)
        ev = {
            "name": r["name"],
            "cat": (ACCOUNTED_PREFIX.rstrip(".")
                    if r["name"].startswith(ACCOUNTED_PREFIX)
                    else r["name"].split(".", 1)[0]),
            "ph": ph,
            "ts": round(r["ts"] * 1e6, 3),
            "dur": max(round(r["dur"] * 1e6, 3),
                       1.0 if tagged else 0.0),
            "pid": os.getpid(),
            "tid": r["tid"],
            "args": args,
        }
        if ph == "i":
            ev["s"] = "t"            # thread-scoped instant
            del ev["dur"]
        evs.append(ev)
        for t in tagged:
            flows.setdefault(str(t), []).append(
                (ev["ts"], ev["pid"], ev["tid"]))
    for trace, anchors in flows.items():
        if len(anchors) < 2:
            continue                 # nothing to connect
        anchors.sort()
        fid = _flow_id(trace)
        last = len(anchors) - 1
        for i, (ts, pid, tid) in enumerate(anchors):
            fe = {
                "name": "request",
                "cat": "trace.flow",
                "ph": "s" if i == 0 else ("f" if i == last else "t"),
                "id": fid,
                "ts": ts,
                "pid": pid,
                "tid": tid,
                "args": {"trace": trace},
            }
            if fe["ph"] == "f":
                fe["bp"] = "e"       # bind to the ENCLOSING slice
            evs.append(fe)
    return evs


def export_chrome_trace(path: str) -> int:
    """Write the recorded spans as a Perfetto-loadable trace-event JSON
    file; returns the number of events written."""
    evs = chrome_trace_events()
    payload = {
        "traceEvents": evs,
        "displayTimeUnit": "ms",
        "otherData": {"source": "amgx_tpu.telemetry.spans"},
    }
    with span("telemetry.export", annotate=False):
        with open(path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
    return len(evs)

"""Unified telemetry subsystem.

One place every layer reports into (the reference exposes the same
information through nvtx ranges, the AMGX_timer tree, and the verbose
solve tables; ours is structured and machine-readable):

- `telemetry.metrics` — process-wide counter/gauge/histogram registry
  (cache hit/miss, setup-routing, batcher occupancy, fallback events,
  jit retraces, memory watermarks, per-tenant serving-latency
  distributions); dump with `metrics.snapshot()` / the C API's
  `AMGX_read_metrics`, or scrape the whole registry as an OpenMetrics
  text exposition (`metrics.to_openmetrics()` /
  `AMGX_read_metrics_openmetrics`).
- `telemetry.diagnostics` — opt-in convergence diagnostics
  (`diagnostics=1`): an in-trace probe cycle records per-level
  residual norms at the cycle stages, and host-side derivation turns
  them into reduction factors, smoother effectiveness, an asymptotic
  convergence-factor estimate and a bottleneck-level attribution on
  `SolveReport.diagnostics`.
- `telemetry.spans` — hierarchical host spans behind
  `profiling.trace_region`, exported as Chrome/Perfetto trace-event
  JSON (`spans.export_chrome_trace`); `telemetry_sync=1` fences device
  work at span boundaries so host spans bound device occupancy.
- `telemetry.programs` — JAX's compile events as counters and spans
  (`compile.trace_s` / `.lower_s` / `.backend_s`, `compile.programs`,
  each span with the program's `fun_name`), and the scope tables of
  the solve programs: {HLO instruction: `amg.L<k>.<stage>` /
  `krylov.<NAME>.<stage>` / `refine.<stage>`}, read on request from
  the executable a solve runs, which is what joins a profiler trace's
  device ops to the cycle's levels and stages.
- `telemetry.flightrec` — crash-surviving flight recorder: a bounded
  append-and-rotate structured event log of state transitions (bucket
  builds/quarantines/requeues, shed decisions with their feasibility
  estimate, fallback-chain hops, resetup routing, chaos injections),
  each stamped with the request trace id; on a BREAKDOWN the serving
  layer dumps the last-N events through output.py, and
  `tools/flightrec.py` pretty-prints + journal-correlates a log for
  postmortems.
- `telemetry.report` — `SolveReport`: in-trace solve metrics (riding
  the monitor's packed stats array at zero added device->host syncs)
  plus static per-level kernel-activity metadata, attached to
  `SolveResult.report` / `BatchedSolveResult.reports` / distributed
  results and reachable from the C API (`AMGX_solver_get_report`);
  validated against `report_schema.json`.

The `telemetry` config knob (default 1) gates report construction and
memory-watermark sampling per solver; counters and spans are always on
(dict updates — the in-trace solve program is NEVER touched either
way, so `telemetry=0` and `telemetry=1` compile identical XLA).
"""
from __future__ import annotations

from . import diagnostics, flightrec, metrics, programs, spans  # noqa: F401
from .report import SolveReport, build_report, validate_report  # noqa: F401

"""Tracing / profiling subsystem.

TPU-native analog of the reference's nvtx ranges + profiler hooks
(src/amgx_timer.cu, include/profile.h nvtxRange, AMGX_pin_memory-era
instrumentation): named trace regions that show up in a captured device
profile, plus wall-clock accumulation for setup/solve stage breakdowns
(the reference's AMGX_timer tree).

Since the telemetry subsystem landed, the recording engine lives in
`telemetry/spans.py`: every region is a node in a parent/child span
tree (exportable as Chrome/Perfetto trace-event JSON via
`telemetry.spans.export_chrome_trace`), and this module is the stable
thin API over it:

- `trace_region(name)`: context manager annotating device work with
  `jax.profiler.TraceAnnotation` (visible in TensorBoard/Perfetto
  traces), recording a hierarchical span, and accumulating host
  wall-clock per name.
- `timers()` / `reset_timers()`: the accumulated (calls, seconds) per
  region, printed by AMGX_print_timers via the output callback.

Regions are cheap no-ops for device latency (annotation only); the
wall-clock numbers measure host-observed span, which for async
dispatch means "time until the region's Python body returned", not
device occupancy — use `jax.profiler` for real device timelines, or set
`telemetry_sync=1` to fence device work at span boundaries (debugging
mode; it defeats the overlapped shipping/dispatch pipelining).
"""
from __future__ import annotations

from typing import Dict, Tuple

from .telemetry import spans as _spans

# the recording engine: hierarchical span + flat accumulator + optional
# device fencing (telemetry/spans.py)
trace_region = _spans.span


def timers() -> Dict[str, Tuple[int, float]]:
    return _spans.flat_timers()


def reset_timers():
    _spans.reset()


def timers_total(prefix: str) -> float:
    """Total wall seconds accumulated under regions starting with
    `prefix`. The amg.* setup regions are maintained as DISJOINT leaf
    spans (no nesting; the overlapped ship worker reports under ship.*;
    tools/check_spans.py lints the registry) precisely so
    `timers_total("amg.") / wall` is an honest accounted fraction of a
    setup's main-thread wall time."""
    return _spans.timers_total(prefix)


def format_timers() -> str:
    """AMGX_timer-style report (src/amgx_timer.cu print tree role),
    printed through the output callback by capi.AMGX_print_timers:
    regions sorted by total time, aligned columns, calls / mean /
    share-of-recorded columns."""
    rows = sorted(timers().items(), key=lambda kv: -kv[1][1])
    if not rows:
        return "no trace regions recorded\n"
    grand = sum(tot for _, (_c, tot) in rows) or 1e-30
    w = max(len("region"), max(len(k) for k, _ in rows))
    header = (f"{'region':<{w}}  {'calls':>6}  {'total_s':>9}  "
              f"{'mean_ms':>9}  {'share':>6}")
    out = [header, "-" * len(header)]
    for name, (calls, tot) in rows:
        out.append(f"{name:<{w}}  {calls:6d}  {tot:9.3f}  "
                   f"{tot / calls * 1e3:9.3f}  {tot / grand:6.1%}")
    return "\n".join(out) + "\n"

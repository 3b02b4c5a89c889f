"""The reduction of the step account's one metric that is neither a
counter nor a span alone (named by its reader's `"module"` key, as
`layer_metrics.py` provides).

A re-setup's wall, as its caller sees it, is the program's call
`Solver.resetup(A)`, which returns once the host has dispatched the
rebuild, and then the caller's wait until the device has worked that
backlog off (`block_until_ready(solve_data())`). The benchmark's span
`bench.resetup` is the two added; the program's counter
`resetup.call_s` is the first.
"""
from __future__ import annotations

from typing import Optional

from .layer_metrics import Observed, delta_per_op, span_median


def span_less_counter_per_op(obs: Observed, span,
                             counters) -> Optional[float]:
    """Median seconds of the benchmark's span less the counters' growth
    per operation: what of the span the counters do not cover."""
    wall = span_median(obs, span)
    inside = delta_per_op(obs, counters)
    if wall is None or inside is None:
        return None
    return wall - inside

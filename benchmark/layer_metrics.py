"""Per-layer metrics: one small reader file each, found by name.

`layer_metrics/<metric name>.json` says where the metric's number comes
from and how it is reduced:

    {"reduction": "delta_per_op", "counters": ["solver.retrace.*"]}

`reduction` names a function of this module (or of the module the file
gives under `"module"`, so a later PR can bring a reduction of its own
without editing this file). A reduction takes the run's observations
and the reader's own parameters, and returns a number, or None where it
finds nothing to read: the harness then leaves the metric out of the
line.

The observations (`Observed`) are what the harness gathered round the
window: the growth of the program's counters over it, the program's span
timers over `Solver.setup`, the benchmark's own spans, the results of
the operations, the reduced trace and the probe.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import importlib
import json
import os
import statistics
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Observed:
    ops: int = 0                      # operations of the window
    counter_growth: dict = dataclasses.field(default_factory=dict)
    setup_timers: dict = dataclasses.field(default_factory=dict)
    spans: dict = dataclasses.field(default_factory=dict)
    iterations: list = dataclasses.field(default_factory=list)
    trace: dict = dataclasses.field(default_factory=dict)
    probe: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)


def _matching(table: dict, patterns):
    return [v for k, v in table.items()
            if any(fnmatch.fnmatchcase(k, p) for p in patterns)]


def delta_per_op(obs: Observed, counters) -> Optional[float]:
    """Growth of the program's counters over the window, per operation."""
    hit = _matching(obs.counter_growth, counters)
    if not obs.ops or not hit:
        return None
    return sum(hit) / obs.ops


def timer_sum(obs: Observed, timers) -> Optional[float]:
    """Seconds the program's own span timers of these names took during
    `Solver.setup` (read from the program's flat timers, not the trace:
    a profiler running through set-up slows the host code it times)."""
    hit = _matching(obs.setup_timers, timers)
    return float(sum(hit)) if hit else None


def span_median(obs: Observed, span) -> Optional[float]:
    """Median seconds of the benchmark's own span of this name."""
    walls = obs.spans.get(span)
    return float(statistics.median(walls)) if walls else None


def iterations_median(obs: Observed) -> Optional[float]:
    return (float(statistics.median(obs.iterations))
            if obs.iterations else None)


def device_ops_per_op(obs: Observed) -> Optional[float]:
    """XLA-op events on the device in the traced window, per operation."""
    if not obs.ops or not obs.trace.get("n_ops"):
        return None
    return obs.trace["n_ops"] / obs.ops / obs.trace["devices"]


def share_of_busy(obs: Observed, ops) -> Optional[float]:
    """Device time of the ops whose name matches, as a share of the
    device's busy time."""
    busy = obs.trace.get("busy_s")
    if not busy:
        return None
    hit = sum(_matching(obs.trace["op_time"], ops))
    return 100.0 * hit / obs.trace["devices"] / busy


def idle_share(obs: Observed) -> Optional[float]:
    busy, window = obs.trace.get("busy_s"), obs.trace.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def probe_hbm_share(obs: Observed) -> Optional[float]:
    """Bytes the probed call streams (from its arrays' shapes) over its
    device time, as a share of the chip's HBM peak."""
    p = obs.probe
    if not p or not p.get("device_s_per_call"):
        return None
    rate = p["bytes"] / p["device_s_per_call"]
    return 100.0 * rate / obs.peaks["hbm_bytes_per_s"]


def load(name: str) -> dict:
    path = os.path.join(HERE, "layer_metrics", name + ".json")
    with open(path) as f:
        return json.load(f)


def read(name: str, obs: Observed) -> Optional[float]:
    """The metric's value from the observations, or None."""
    spec = dict(load(name))
    module = importlib.import_module(
        spec.pop("module", "benchmark.layer_metrics"))
    fn = getattr(module, spec.pop("reduction"))
    for note in ("reads", "moves"):
        spec.pop(note, None)
    return fn(obs, **spec)

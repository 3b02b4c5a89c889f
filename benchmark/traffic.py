"""The traffic generator: a seeded, closed-loop caller of one entry.

A traffic mix is a data file `traffic/<name>.json`; its `kind` picks one
of the two loops below, everything else is a parameter. Both loops time
each call with the host clock round work that ends in
`block_until_ready`, start whole operations while the window is open,
and between operations (outside every span) keep what the check needs.
"""
from __future__ import annotations

import math
import time
from collections import defaultdict
from contextlib import contextmanager

import numpy as np


class Spans:
    """The benchmark's own spans: host wall per name, and the same
    region as a profiler annotation so a traced run can place it."""

    def __init__(self):
        self.walls = defaultdict(list)

    @contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.walls[name].append(time.perf_counter() - t0)


class Sample:
    """The operations the check reads back: `size` of the first
    `expected` operations of the window, drawn from the seed before the
    window opens, so that a window pays for `size` read-backs and no
    more. An operation past `expected` is not in the sample."""

    def __init__(self, rng, size: int, expected: int):
        expected = max(int(expected), 1)
        self.ops = set(rng.choice(expected, size=min(size, expected),
                                  replace=False).tolist())
        self.kept = []

    def wants(self, k: int) -> bool:
        return k in self.ops

    def keep(self, record):
        self.kept.append(record)

    def records(self):
        return self.kept


class Inputs:
    """What a run feeds the system, all from the seed.

    The right-hand sides are the traffic file's own set (standard
    normals from its `rhs_seed`), which every seed shares: how long a
    solve takes depends on its right-hand side (the inner Krylov count
    moves by one), so a set drawn anew for each seed made the seed
    change the work. The run's seed orders the set and scales each
    vector by a power of two and a sign, which changes no iteration
    count; it draws the coefficient factors of the steps and the sample
    the check reads."""

    def __init__(self, seed: int, spec: dict, rows: int):
        count = int(spec["rhs"])
        rng = np.random.default_rng([seed, 1])
        order = rng.permutation(count)
        scale = np.ldexp(rng.choice([-1.0, 1.0], count),
                         rng.integers(-3, 4, count))
        self.rhs = [scale[k] * np.random.default_rng(
            [int(spec["rhs_seed"]), int(i)]).standard_normal(rows)
            for k, i in enumerate(order)]
        lo, hi = spec.get("factor_range", (1.0, 1.0))
        self.factors = lo + (hi - lo) * np.random.default_rng(
            [seed, 2]).random(1 << 12)
        self.sample_rng = np.random.default_rng([seed, 3])

    def factor(self, step: int) -> float:
        return float(self.factors[step % self.factors.shape[0]])


class Window:
    """When the loop stops: after `seconds` of an untraced run, or once
    a traced run has both its seconds and its operations. `op_s` is
    what one operation took in warm-up, from which the sample reckons
    how many the window will hold."""

    def __init__(self, seconds: float, min_ops: int = 0, op_s: float = 0.0):
        self.seconds, self.min_ops = seconds, min_ops
        # a quarter more than the warm-up time promises, and one: where
        # that is no more than the sample's size, every operation is read
        self.expected = max(
            min_ops, math.ceil(1.25 * seconds / op_s) + 1 if op_s else 0)
        self.t0 = time.perf_counter()

    def open(self, ops_done: int) -> bool:
        return (time.perf_counter() - self.t0 < self.seconds
                or ops_done < self.min_ops)


def _note(entry, log, sample, op, rhs_i, factor):
    s = entry.last()
    rec = {"op": op, "rhs": rhs_i, "factor": factor,
           "iterations": s.iterations, "ok": s.ok}
    if sample.wants(len(log)):
        sample.keep(dict(rec, x=np.asarray(s.x)))
    log.append(rec)


def solve_stream(entry, spec, inputs, spans, window, base_vals=None,
                 first_op=0):
    """solve(b_i) back to back, round robin over the right-hand sides.
    Returns (log of every operation, sample kept for the check)."""
    log = []
    sample = Sample(inputs.sample_rng, int(spec["checked_ops"]),
                    window.expected)
    op = first_op
    while window.open(len(log)):
        i = op % len(inputs.rhs)
        with spans.span("bench.solve"):
            entry.solve(i)
        _note(entry, log, sample, op, i, 1.0)
        op += 1
    return log, sample


def time_step(entry, spec, inputs, spans, window, base_vals=None,
              first_op=0):
    """New coefficients on the same pattern -> resetup -> solve."""
    log = []
    sample = Sample(inputs.sample_rng, int(spec["checked_ops"]),
                    window.expected)
    op = first_op
    while window.open(len(log)):
        i = op % len(inputs.rhs)
        f = inputs.factor(op)
        vals = base_vals * f           # before the step's clock starts
        with spans.span("bench.step"):
            with spans.span("bench.replace"):
                entry.replace(vals)
            with spans.span("bench.resetup"):
                entry.resetup()
            with spans.span("bench.solve"):
                entry.solve(i)
        del vals
        _note(entry, log, sample, op, i, f)
        op += 1
    return log, sample


# kind -> (the loop, the span that is one operation of it)
KINDS = {"solve_stream": (solve_stream, "bench.solve"),
         "time_step": (time_step, "bench.step")}

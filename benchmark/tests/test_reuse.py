"""The structure-reuse cell and its plain reference.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout; the harness is driven as `test_correct.py`
drives it (its fixtures, by import), at 16^3:

- `flagship-reuse-p7-256.time-step` comes out correct, its control
  (float32 without the float64 shell) does not;
- every step of it, the warm one too, takes the value-only route
  (`amg.resetup.value` +1 a step) and none rebuilds the hierarchy
  (`amg.setup.full` stands at the one setup);
- `reference_reuse`, on a 4^3 grid with hand-written aggregates, gives
  the dense P^T A P written out here.
"""
from __future__ import annotations

import numpy as np

from benchmark import control, reference_reuse
from benchmark.operator_host import poisson_csr
from benchmark.tests.test_correct import drive, small  # noqa: F401

CELL = "flagship-reuse-p7-256.time-step"
COUNTERS = ("amg.resetup.value", "amg.resetup.structure",
            "amg.resetup.value_declined", "amg.setup.full")


def _counters():
    from amgx_tpu.telemetry import metrics
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in COUNTERS}


def test_cell_is_correct_and_every_step_takes_the_value_route(small):
    before = _counters()
    result, lines = drive(CELL)
    grew = {k: v - before[k] for k, v in _counters().items()}
    assert result["correct"] and result["failed"] == 0, lines
    steps = result["attempted"] + 1             # and the warm step
    assert result["attempted"] >= 1
    assert grew == {"amg.resetup.value": steps, "amg.resetup.structure": 0,
                    "amg.resetup.value_declined": 0, "amg.setup.full": 1}
    assert {"setup_s", "step_s"} <= set(result["metrics"])


def test_control_is_not_correct(small):
    result, lines = drive(CELL, make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines


def test_reference_equals_a_dense_product_written_out():
    grid = (4, 4, 4)
    ro, ci, vals = poisson_csr("7pt", grid)
    n = 64
    rng = np.random.default_rng(5)
    vals = vals * (1.0 + rng.random(vals.shape[0]))      # not symmetric
    # hand-written aggregates: the eight 2x2x2 blocks of the grid, then
    # an uneven split of those eight into three
    first = np.empty(n, dtype=np.int64)
    for z in range(4):
        for y in range(4):
            for x in range(4):
                first[(z * 4 + y) * 4 + x] = \
                    ((z // 2) * 2 + y // 2) * 2 + x // 2
    second = np.array([0, 0, 1, 1, 1, 2, 2, 2])
    assert np.array_equal(
        first, reference_reuse.paired_aggregates(grid, (0, 1, 2))[0])
    ref = reference_reuse.rebuild(ro, ci, vals, [(first, 8), (second, 3)],
                                  slab_rows=16)
    dense = np.zeros((n, n))
    for i in range(n):
        dense[i, ci[ro[i]:ro[i + 1]]] = vals[ro[i]:ro[i + 1]]
    want = [dense]
    for agg, nc in ((first, 8), (second, 3)):
        P = np.zeros((want[-1].shape[0], nc))
        P[np.arange(P.shape[0]), agg] = 1.0
        want.append(P.T @ want[-1] @ P)
    for got, w in zip(ref["operators"], want):
        assert np.allclose(got.toarray(), w, rtol=1e-14, atol=1e-14)
    assert np.allclose(ref["coarsest"], want[-1], rtol=1e-14, atol=1e-14)
    assert np.allclose(ref["bounds"],
                       [np.abs(w).sum(axis=1).max() for w in want],
                       rtol=1e-14, atol=0.0)
    # the slabs change nothing
    whole = reference_reuse.rebuild(ro, ci, vals, [(first, 8), (second, 3)])
    assert reference_reuse.largest_difference(
        whole["operators"][1], ref["operators"][1]) <= 1e-15

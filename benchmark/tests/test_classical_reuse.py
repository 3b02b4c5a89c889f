"""The classical structure-reuse cell, its control and its plain reference.

Run with `JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q` from
the root of a checkout; the harness is driven as `test_correct.py`
drives it (its fixtures, by import), at 16^3:

- `classical-reuse-p7-128.time-step` comes out correct; its control (the
  plain CG in bfloat16, taking each step's new values), which its
  configuration names by module, does not, and `python3 -m
  benchmark.control` says so by its exit code;
- every step of it, the warm one too, takes the structure route
  (`amg.resetup.structure` +1 a step, every level reused) and none
  coarsens again (`amg.setup.full` stands at the one setup) or traces
  the solve again;
- `reference_classical_reuse`, on a 4^3 grid with a hand-written
  interpolation, gives the dense `P^T A P` written out here, and its
  PCG converges on it.
"""
from __future__ import annotations

import numpy as np

from benchmark import control
from benchmark import reference_classical_reuse as reference
from benchmark.operator_host import poisson_csr
from benchmark.tests.test_correct import drive, small  # noqa: F401

CELL = "classical-reuse-p7-128.time-step"
COUNTERS = ("amg.resetup.value", "amg.resetup.structure",
            "amg.resetup.reused_levels", "amg.setup.full",
            "solver.retrace.solve", "matrix.swell_layout_dropped")


def _counters():
    from amgx_tpu.telemetry import metrics
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in COUNTERS}


def test_cell_is_correct_and_every_step_takes_the_structure_route(small):
    before = _counters()
    result, lines = drive(CELL)
    grew = {k: v - before[k] for k, v in _counters().items()}
    assert result["correct"] and result["failed"] == 0, lines
    steps = result["attempted"] + 1             # and the warm step
    assert result["attempted"] >= 1
    levels = grew.pop("amg.resetup.reused_levels") / steps
    assert levels == int(levels) and levels >= 2
    assert grew == {"amg.resetup.value": 0, "amg.resetup.structure": steps,
                    "amg.setup.full": 1, "solver.retrace.solve": 1,
                    "matrix.swell_layout_dropped": 0}
    assert {"setup_s", "step_s"} == set(result["metrics"])


def test_control_is_not_correct(small):
    result, lines = drive(CELL, make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines
    assert any(ln.endswith(" FAILED") for ln in lines)


def test_control_in_float32_meets_the_limit(small):
    """What fails the control is its precision, not its method."""
    def f32(config):
        ctl = config["control"]
        return reference.ReferenceCGSteps(
            dict(ctl["solver"], dtype="float32"), config["operator"])
    result, lines = drive(CELL, make_entry=f32)
    assert result["correct"], lines


def test_control_command_exits_0_when_not_correct(small, monkeypatch):
    import jax
    from benchmark import run
    calls, real = [], run.run

    def harness(workload, seed, seconds, trace, make_entry=None):
        calls.append((workload, seed, seconds, trace))
        return real(workload, seed, seconds, trace, make_entry=make_entry,
                    devs=jax.devices(), out=lambda line: None)

    monkeypatch.setattr(run, "run", harness)
    argv = ["--workload", CELL, "--seed", "5", "--seconds", "0.5"]
    assert control.control_class(run.find_cell(CELL)[1]) \
        is reference.ReferenceCGSteps
    assert control.main(argv) == 0
    assert calls == [(CELL, 5, 0.5, False)]
    # and 1 when what ran in the control's place is correct
    monkeypatch.setattr(control, "control_entry", None)     # the program
    assert control.main(argv) == 1


def test_reference_equals_a_dense_product_written_out():
    grid = (4, 4, 4)
    ro, ci, vals = poisson_csr("7pt", grid)
    n = 64
    rng = np.random.default_rng(5)
    # D A D: symmetric, definite, no longer a constant stencil
    d = 1.0 + rng.random(n)
    rows = np.repeat(np.arange(n), np.diff(ro))
    vals = vals * d[rows] * d[ci]
    # a hand-written interpolation: the even cells of the grid are
    # coarse (weight 1 onto themselves), every other cell takes seeded
    # positive weights from the coarse cells its row reaches, or from
    # coarse cell 0 where it reaches none; then 8 coarse cells onto 3
    dense = np.zeros((n, n))
    dense[rows, ci] = vals
    cell = np.arange(n)
    x, y, z = cell % 4, (cell // 4) % 4, cell // 16
    coarse = np.flatnonzero((x % 2 == 0) & (y % 2 == 0) & (z % 2 == 0))
    number = {int(c): k for k, c in enumerate(coarse)}
    P0 = np.zeros((n, coarse.size))
    for i in range(n):
        if i in number:
            P0[i, number[i]] = 1.0
            continue
        reach = [number[int(j)] for j in ci[ro[i]:ro[i + 1]]
                 if int(j) in number] or [0]
        P0[i, reach] = rng.random(len(reach)) + 0.1
    P1 = np.zeros((coarse.size, 3))
    P1[np.arange(8), [0, 0, 1, 1, 1, 2, 2, 2]] = rng.random(8) + 0.5
    want = [dense]
    for P in (P0, P1):
        want.append(P.T @ want[-1] @ P)

    def csr(P):
        nz = P != 0
        return (np.concatenate([[0], np.cumsum(nz.sum(axis=1))]),
                np.nonzero(nz)[1], P[nz], P.shape[1])

    ref = reference.rebuild(ro, ci, vals, [csr(P0), csr(P1)])
    for got, w in zip(ref["operators"], want):
        assert np.allclose(got.toarray(), w, rtol=1e-13, atol=1e-13)
    assert np.allclose(ref["coarsest"], want[-1], rtol=1e-13, atol=1e-13)
    for got, w in zip(ref["diagonals"], want):
        assert np.allclose(got, np.diag(w), rtol=1e-13, atol=0.0)
    # an entry of level 1 sums at most (entries of a P column) x 7 x
    # (entries of a P column) products, and at least the one of a
    # coarse cell's own diagonal
    assert ref["terms"][0] == 0 and ref["terms"][1] >= 7
    assert reference.largest_difference(ref["operators"][1],
                                        ref["operators"][1]) == 0.0
    # its PCG solves the system it was handed
    b = rng.standard_normal(n)
    xs, iters = reference.solve(ref, b, tolerance=1e-10)
    assert 1 <= iters < 40
    assert np.linalg.norm(b - dense @ xs) <= 1e-9 * np.linalg.norm(b)

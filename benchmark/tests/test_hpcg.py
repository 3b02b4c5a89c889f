"""The `hpcg-p27-192` cell's `correct`, shown to fail.

As test_correct.py does for the other cells, through the harness itself
(`benchmark.run.run`) at 16^3 on whatever JAX has:

- the program under the configuration's options comes out correct (the
  float64 twin of the control: the same cycle inside the f64 shell);
- the configuration's control (the same options without the shell, on
  a float32 operator with float32 vectors, PCG asked for 1e-8) comes
  out NOT correct;
- the plain reference (benchmark/reference_hpcg.py) meets the limit in
  float64 on the cell's own right-hand side, so the limit is one a
  straightforward float64 implementation of the same equations meets.
"""
from __future__ import annotations

import numpy as np

from benchmark import control, reference, reference_hpcg, run, traffic
from benchmark.operator_host import poisson_csr
from benchmark.tests.test_correct import drive, small  # noqa: F401

CELL = "hpcg-p27-192.solve-stream"


def test_program_is_correct(small):
    result, lines = drive(CELL)
    assert result["correct"] and result["failed"] == 0, lines
    assert result["attempted"] >= 1
    assert {"setup_s", "solve_s"} <= set(result["metrics"])


def test_float32_control_fails(small):
    result, lines = drive(CELL, make_entry=control.control_entry)
    assert not result["correct"] and result["failed"] >= 1, lines
    assert any(ln.endswith(" FAILED") for ln in lines)


def test_plain_reference_meets_the_limit(small):
    _cell, config, spec, _bench = run.find_cell(CELL)
    grid = config["operator"]["grid"]
    host_op = poisson_csr("27pt", grid, np.float64)
    b = traffic.Inputs(2147483700, spec, int(np.prod(grid))).rhs[0]
    # 16^3 halves twice before the hierarchy's smallest level
    mg = reference_hpcg.Multigrid(tuple(grid[::-1]), 3)
    x, steps, residuals = mg.refine(b, 1e-8, 1e-5)
    limit = config["guarantees"]["true_relative_residual"]
    assert len(steps) == 2 and residuals[-1] <= limit
    # by the harness's own yardstick, not the reference's
    M = reference.host_matrix(*host_op)
    assert reference.true_relres(M, x, b) <= limit

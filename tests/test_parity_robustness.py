"""Iteration-regression + robustness harness (VERDICT round-1 item 10).

Regression table: four shipped configs run on fixed fixtures and must
reproduce the recorded iteration counts exactly. The recorded counts
are THIS FRAMEWORK'S (captured when the faithful reference preset
files were adopted) — a self-regression table, NOT verified AmgX
output: without GPU hardware the reference's counts for these fixtures
cannot be produced, and its repo publishes none for them (the only
cross-checked number is the 12-row README sample). What the table
guards is drift: a change to any selector, smoother, or convergence
component that alters convergence behavior trips these.

Robustness: NaN rhs, zero diagonal, and zero-row inputs must not hang
or crash — mirroring src/tests/smoother_nan_random.cu and the
zero_in_diagonal tests of the reference.
"""
import dataclasses
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.matrix import CsrMatrix
from amgx_tpu.solvers import make_solver

amgx.initialize()

_CONFIG_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")

# parity table: (config file, fixture, recorded iteration count).
# Regenerate deliberately (and update here) when algorithm changes are
# intended; see docstring.
_PARITY = [
    # counts regenerated when configs/ switched to the verbatim
    # reference presets (MULTICOLOR_DILU smoother, aggressive levels,
    # reference tolerances)
    ("FGMRES_AGGREGATION.json", ("7pt", (16, 16, 16)), 7),
    # 13 until PR 47: under NOSOLVER the hierarchy no longer stops at
    # dense_lu_num_rows (the reference reads that key only where the
    # coarse solver is the dense LU) but runs down to min_coarse_rows
    ("AMG_CLASSICAL_PMIS.json", ("7pt", (16, 16, 16)), 12),
    ("PCG_CLASSICAL_V_JACOBI.json", ("7pt", (16, 16, 16)), 14),
    ("PBICGSTAB_AGGREGATION_W_JACOBI.json", ("7pt", (16, 16, 16)), 6),
]


def _run(config_name, fixture):
    stencil, dims = fixture
    # without the gallery's grid_shape: the counts were recorded under
    # JPL's colors, and with a grid MIN_MAX serves the parity coloring
    # (2 colors here, under which FGMRES_AGGREGATION's DILU takes 8)
    A = dataclasses.replace(gallery.poisson(stencil, *dims),
                            grid_shape=None).init()
    cfg = Config.from_file(os.path.join(_CONFIG_DIR, config_name))
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    b = jnp.ones(A.num_rows)
    return A, b, slv.solve(b)


@pytest.mark.parametrize("config_name,fixture,expected_iters", _PARITY)
def test_iteration_parity(config_name, fixture, expected_iters):
    A, b, res = _run(config_name, fixture)
    assert bool(res.converged), f"{config_name} did not converge"
    assert int(res.iterations) == expected_iters, (
        f"{config_name}: {int(res.iterations)} iterations, parity table "
        f"records {expected_iters} — update the table only if the "
        "algorithm change is intended")
    r = np.asarray(b) - np.asarray(amgx.ops.spmv(A, res.x))
    assert np.linalg.norm(r) / np.linalg.norm(np.asarray(b)) < 1e-5


# ---------------------------------------------------------------------
# EXTERNAL parity anchors: the two runs the reference README publishes
# verbatim (reference README.md "Running examples": examples/matrix.mtx
# with src/configs/FGMRES_AGGREGATION.json) — the only AmgX iteration
# counts published anywhere in its repo. Unlike the self-regression
# table above, these rows are cross-checked against REAL AmgX output.
# ---------------------------------------------------------------------

def _readme_system():
    from amgx_tpu.io import read_system
    A, b, _x = read_system("/root/reference/examples/matrix.mtx")
    if b is None:
        b = np.ones(A.num_rows)
    return A.init(), np.asarray(b)


@pytest.mark.skipif(
    not os.path.exists("/root/reference/examples/matrix.mtx"),
    reason="reference checkout not present")
def test_external_anchor_readme_single_device():
    """Published single-GPU run: 'Total Iterations: 1' (Final Residual
    1.6e-14). Must reproduce exactly."""
    A, b = _readme_system()
    cfg = Config.from_file(os.path.join(_CONFIG_DIR,
                                        "FGMRES_AGGREGATION.json"))
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    res = slv.solve(jnp.asarray(b))
    assert bool(res.converged)
    assert int(res.iterations) == 1      # published AmgX count
    r = np.asarray(b) - np.asarray(amgx.ops.spmv(A, res.x))
    assert np.linalg.norm(r) < 1e-10


@pytest.mark.skipif(
    not os.path.exists("/root/reference/examples/matrix.mtx"),
    reason="reference checkout not present")
def test_external_anchor_readme_two_rank_distributed():
    """Published 2-rank MPI run of the SAME system and config: 'Total
    Iterations: 9' — AmgX's rank-local aggregation degrades the tiny
    hierarchy. Our distributed path preserves the single-device
    decisions (consolidation at this size), so it must converge at
    least as fast as the published 9 — and in fact matches the
    single-GPU count of 1 (documented design difference: semantic-id
    decisions make the sharded hierarchy partition-independent)."""
    from amgx_tpu.distributed import DistributedSolver, default_mesh
    A, b = _readme_system()
    cfg = Config.from_file(os.path.join(_CONFIG_DIR,
                                        "FGMRES_AGGREGATION.json"))
    d = DistributedSolver(cfg, default_mesh(2))
    d.setup(A)
    res = d.solve(b)
    assert bool(res.converged)
    assert int(res.iterations) <= 9      # published AmgX 2-rank count
    assert int(res.iterations) == 1      # our partition-independence
    r = np.asarray(b) - np.asarray(A.to_dense()) @ np.asarray(res.x)
    assert np.linalg.norm(r) < 1e-10


# ---------------------------------------------------------------------
# robustness (smoother_nan_random.cu / zero_in_diagonal analogs)
# ---------------------------------------------------------------------

def _simple_solver(extra=""):
    cfg = Config.from_string(
        "config_version=2, solver=PCG, preconditioner=BLOCK_JACOBI, "
        "max_iters=30, tolerance=1e-8, monitor_residual=1" +
        (", " + extra if extra else ""))
    return make_solver("PCG", cfg, "default")


def test_nan_rhs_does_not_hang():
    """NaN in the rhs must terminate (diverged/not-converged), not hang
    or return converged."""
    A = gallery.poisson("5pt", 12, 12).init()
    b = np.ones(144)
    b[7] = np.nan
    res = _simple_solver().setup(A).solve(jnp.asarray(b))
    assert not bool(res.converged)


def test_nan_matrix_smoothers():
    """Smoothers fed NaN coefficients must not crash (they may return
    NaN — the solver monitor then reports divergence)."""
    A = gallery.poisson("5pt", 8, 8)
    vals = np.asarray(A.values).copy()
    vals[3] = np.nan
    An = A.with_values(jnp.asarray(vals))
    An = An if An.initialized else An.init()
    for name in ["BLOCK_JACOBI", "JACOBI_L1", "GS"]:
        s = make_solver(name, Config.from_string(
            f"solver={name}, max_iters=2"), "default").setup(An)
        out = s.solve(jnp.ones(64))
        assert out.x.shape == (64,)     # no crash, shape preserved


def test_zero_in_diagonal():
    """A zero diagonal entry must not produce inf/NaN in Jacobi-family
    smoothers (guarded inverse), matching the reference's
    zero-in-diagonal robustness tests."""
    A = gallery.poisson("5pt", 8, 8)
    vals = np.asarray(A.values).copy()
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    # zero out row 5's diagonal
    for p in range(ro[5], ro[6]):
        if ci[p] == 5:
            vals[p] = 0.0
    Az = A.with_values(jnp.asarray(vals))
    Az = Az if Az.initialized else Az.init()
    for name in ["BLOCK_JACOBI", "JACOBI_L1"]:
        s = make_solver(name, Config.from_string(
            f"solver={name}, max_iters=4"), "default").setup(Az)
        out = s.solve(jnp.ones(64))
        assert np.all(np.isfinite(np.asarray(out.x)))


def test_zero_row():
    """A fully zero row (no connections at all) must not crash setup or
    produce non-finite smoother output."""
    n = 36
    A5 = gallery.poisson("5pt", 6, 6)
    rows, cols, vals = [np.asarray(v) for v in A5.init().coo()]
    keep = rows != 17
    Az = CsrMatrix.from_coo(rows[keep], cols[keep], vals[keep],
                            n, n).init()
    s = _simple_solver().setup(Az)
    out = s.solve(jnp.ones(n))
    assert out.x.shape == (n,)


def test_singular_system_reports_nonconvergence():
    """An all-zero matrix cannot converge on a nonzero rhs; the solver
    must terminate with converged=False (capi_graceful_failure role)."""
    n = 16
    Az = CsrMatrix.from_coo(np.arange(n), np.arange(n), np.zeros(n),
                            n, n).init()
    res = _simple_solver().setup(Az).solve(jnp.ones(n))
    assert not bool(res.converged)

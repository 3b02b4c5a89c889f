"""The `hpcg-p27-192` configuration at small sizes: the program against
the plain reference, the parity coloring, and the sweep's two forms.

CPU, float64, seeded right-hand sides. The reference
(benchmark/reference_hpcg.py) is numpy written from HPCG's equations
and shares no code with the program; the program runs the
configuration's own option string (the control's: PCG round the cycle
without the f32-in-f64 shell, so that both sides compute in float64).
"""
import dataclasses
import itertools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import amgx_tpu as amgx
from amgx_tpu.config import Config
from amgx_tpu.matrix import CsrMatrix
from amgx_tpu.ops import coloring, parity_sweep
from amgx_tpu.ops.coloring import color_matrix
from amgx_tpu.solvers.base import make_solver
from amgx_tpu.telemetry import metrics, spans

from benchmark import reference_hpcg as ref
from benchmark.operator_host import poisson_csr

amgx.initialize()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "hpcg-p27-192.json")) as f:
    CONFIG = json.load(f)
PCG_OPTIONS = CONFIG["control"]["solver"]["options"]


def _operator(stencil, shape, grid=True):
    """The benchmark's own operator (x fastest), as the entry hands it
    to the program: with the grid annotation, or without."""
    ro, ci, v = poisson_csr(stencil, shape, np.float64)
    n = int(np.prod(shape))
    A = CsrMatrix.from_scipy_like(ro, ci, v, n, n)
    if grid:
        A = dataclasses.replace(A, grid_shape=tuple(shape))
    return A.init(), sp.csr_matrix((v, ci, ro), shape=(n, n))


def _rhs(n, i=0):
    return np.random.default_rng([29, i]).standard_normal(n)


def _hierarchy(slv):
    s = slv
    while not hasattr(s, "amg"):
        s = s.preconditioner
    return s.amg


def _valid(M, colors):
    coo = M.tocoo()
    offd = coo.row != coo.col
    c = np.asarray(colors)
    return not np.any(c[coo.row[offd]] == c[coo.col[offd]])


# -- the program against the reference --------------------------------

# The coarse solver is a sweep and no dense LU, so dense_lu_num_rows
# stops nothing: at 16^3 a fourth level of 2^3 = 8 rows is built where
# max_levels allows it.
@pytest.mark.parametrize("n,max_levels,depth", [
    (16, 3, 3), (16, 4, 4), (32, 3, 3), (32, 4, 4)])
def test_program_matches_reference(n, max_levels, depth):
    """Same PCG iteration count, residual history and answer as the
    reference. Tolerances: both sides are the same arithmetic in
    float64 in another order of summation, which moves a residual of
    15 iterations by 1e-13 relative here; 1e-6 on the history and 1e-8
    on x leave room for a longer run and are still far under what a
    skipped color, sweep or level does (a level less moves the count by
    a quarter, a one-sided sweep by a third)."""
    A, _M = _operator("27pt", (n, n, n))
    options = PCG_OPTIONS.replace("amg:max_levels=4",
                                  f"amg:max_levels={max_levels}")
    slv = amgx.create_solver(Config.from_string(
        options + ", store_res_history=1"))
    slv.setup(A)
    amg = _hierarchy(slv)
    assert amg.num_levels == depth
    assert [lv.smoother.num_colors for lv in amg.levels] == \
        [8] * (depth - 1)
    b = _rhs(n ** 3)
    res = slv.solve(jnp.asarray(b))
    x, iters, history = ref.Multigrid((n, n, n), depth).pcg(b, 1e-8)
    assert str(res.status) == "success"
    assert abs(int(res.iterations) - iters) <= 1
    got = np.asarray(res.res_history).reshape(-1)[:int(res.iterations) + 1]
    m = min(len(got), len(history))
    np.testing.assert_allclose(got[:m] / got[0], history[:m], rtol=1e-6)
    assert np.linalg.norm(np.asarray(res.x) - x) <= 1e-8 * np.linalg.norm(x)


def test_configuration_converges_like_the_reference_loop():
    """The whole option string (f64 defect correction round f32 PCG)
    against the reference's loop in float64: as many steps, a true
    residual under the limit on both sides, and PCG counts within one
    of each other in the sum (float32 drifts from float64 by an
    iteration at most over a dozen)."""
    n = 16
    A, M = _operator("27pt", (n, n, n))
    slv = amgx.create_solver(Config.from_string(
        CONFIG["solver"]["options"]))
    slv.setup(A)
    b = _rhs(n ** 3, 1)
    before = metrics.snapshot().get("smoother.color_steps", 0)
    res = slv.solve(jnp.asarray(b))
    depth = _hierarchy(slv).num_levels
    x, steps, residuals = ref.Multigrid((n, n, n), depth).refine(
        b, 1e-8, 1e-5)
    assert str(res.status) == "success"
    assert int(res.iterations) == len(steps)
    inner = int(round(res.extra_stats["inner_iters"]))
    assert abs(inner - sum(steps)) <= 1
    limit = CONFIG["guarantees"]["true_relative_residual"]
    assert residuals[-1] <= limit
    assert np.linalg.norm(b - M @ np.asarray(res.x)) \
        <= limit * np.linalg.norm(b)
    # the counter: iterations that ran the cycle x the steps of a
    # cycle (levels x 2 sweeps x 2 passes x 8 colors, + the coarsest)
    grown = metrics.snapshot()["smoother.color_steps"] - before
    assert grown == inner * (2 * 16 * (depth - 1) + 16)


def test_float32_answer_fails_the_limit():
    """The control's precision cannot meet 1e-8: PCG in float32 on a
    float32 operator stalls above it."""
    n = 16
    A, M = _operator("27pt", (n, n, n))
    slv = amgx.create_solver(Config.from_string(PCG_OPTIONS))
    slv.setup(A.astype(jnp.float32))
    b = _rhs(n ** 3, 2).astype(np.float32)
    res = slv.solve(jnp.asarray(b))
    x = np.asarray(res.x, np.float64)
    rr = np.linalg.norm(b - M @ x) / np.linalg.norm(b)
    assert rr > CONFIG["guarantees"]["true_relative_residual"]


# -- the reference against scipy --------------------------------------

def test_reference_galerkin_stencil_is_ptap():
    """The Galerkin stencil of the reference, laid out as a matrix with
    Dirichlet truncation, is P^T A P of the benchmark's own 27-point
    matrix for 2x2x2 aggregates, entry for entry; its centre is
    8 * 26 - 56."""
    n, m = 8, 4
    _A, M = _operator("27pt", (n, n, n))
    i = np.arange(n ** 3)
    agg = ((i // (n * n)) // 2 * m + (i // n % n) // 2) * m + (i % n) // 2
    P = sp.csr_matrix((np.ones(n ** 3), (i, agg)), shape=(n ** 3, m ** 3))
    want = (P.T @ M @ P).toarray()
    c = ref.galerkin(ref.stencil27())
    assert c[1, 1, 1] == 8 * 26 - 56 == 152
    got = np.empty((m ** 3, m ** 3))
    for j in range(m ** 3):
        e = np.zeros(m ** 3)
        e[j] = 1.0
        got[:, j] = ref.apply(c, e.reshape(m, m, m)).reshape(-1)
    np.testing.assert_array_equal(got, want)
    # and the transfers are that P
    r = _rhs(n ** 3, 3)
    np.testing.assert_allclose(ref.restrict(r.reshape(n, n, n)).reshape(-1),
                               P.T @ r, rtol=1e-12)
    xc = _rhs(m ** 3, 4)
    np.testing.assert_array_equal(
        ref.prolong(xc.reshape(m, m, m)).reshape(-1), P @ xc)


# -- parity coloring ---------------------------------------------------

@pytest.mark.parametrize("stencil,colors", [("27pt", 8), ("7pt", 2)])
def test_parity_coloring_on_every_geo_level(stencil, colors):
    """A proper coloring (no two coupled rows share a color) with 8
    colors for 27 points and 2 for 7, on the fine level and on every
    GEO level under it (a Galerkin product over 2x2x2 aggregates keeps
    the stencil's kind)."""
    n = 16
    A, _M = _operator(stencil, (n, n, n))
    slv = amgx.create_solver(Config.from_string(
        "solver=AMG, algorithm=AGGREGATION, selector=GEO,"
        " smoother=MULTICOLOR_GS, max_levels=3, max_iters=1,"
        " coarse_solver=MULTICOLOR_GS, coarsest_sweeps=1"))
    slv.setup(A)
    amg = slv.amg
    mats = [lv.A for lv in amg.levels] + [amg.coarsest_A]
    assert [m.num_rows for m in mats] == [16 ** 3, 8 ** 3, 4 ** 3]
    for Ak in mats:
        col = color_matrix(Ak, Config.from_string(""), "default")
        rows, cols, vals = (np.asarray(a) for a in Ak.coo())
        Mk = sp.csr_matrix((vals, (rows, cols)),
                           shape=(Ak.num_rows, Ak.num_rows))
        Mk.eliminate_zeros()
        assert _valid(Mk, col.row_colors)
        assert int(np.asarray(col.row_colors).max()) + 1 \
            == col.num_colors == colors


@pytest.mark.parametrize("shape", [(8, 8, 8), (5, 6, 7), (1, 6, 5),
                                   (3, 4, 3)])
@pytest.mark.parametrize("stencil", ["27pt", "7pt"])
def test_parity_coloring_is_proper_on_any_grid(stencil, shape):
    A, M = _operator(stencil, shape)
    col = color_matrix(A, Config.from_string(""), "default")
    assert _valid(M, col.row_colors)
    assert col.num_colors <= (8 if stencil == "27pt" else 2)
    # from the CSR pattern alone (no diagonals to read): the same colors
    bare = dataclasses.replace(A, dia_offsets=None, dia_vals=None)
    again = color_matrix(bare, Config.from_string(""), "default")
    np.testing.assert_array_equal(np.asarray(col.row_colors),
                                  np.asarray(again.row_colors))


@pytest.mark.parametrize("stencil", ["27pt", "7pt"])
def test_matrix_without_grid_is_colored_as_before(stencil):
    """No grid_shape: Jones-Plassmann-Luby, color for color."""
    A, M = _operator(stencil, (8, 8, 8), grid=False)
    col = color_matrix(A, Config.from_string(""), "default")
    jpl = coloring._jpl_min_max(A)
    assert col.grid is None and col.num_colors == jpl.num_colors > 8 - 6 * (
        stencil == "7pt")
    np.testing.assert_array_equal(np.asarray(col.row_colors),
                                  np.asarray(jpl.row_colors))
    assert _valid(M, col.row_colors)


@pytest.mark.parametrize("stencil,grid", [("27pt", True), ("7pt", True),
                                          ("27pt", False)])
def test_parity_coloring_by_name(stencil, grid):
    """GRID_PARITY is the default's choice on a grid operator, color for
    color, and an error where the default would turn to JPL."""
    from amgx_tpu.errors import BadParametersError
    A, _M = _operator(stencil, (6, 5, 4), grid=grid)
    named = Config.from_string("matrix_coloring_scheme=GRID_PARITY")
    if not grid:
        with pytest.raises(BadParametersError, match="GRID_PARITY"):
            color_matrix(A, named, "default")
        return
    col = color_matrix(A, named, "default")
    default = color_matrix(A, Config.from_string(""), "default")
    assert col.grid == default.grid == (6, 5, 4)
    assert col.num_colors == default.num_colors
    np.testing.assert_array_equal(np.asarray(col.row_colors),
                                  np.asarray(default.row_colors))


def test_a_reach_of_two_is_not_colored_by_parity():
    """A grid operator that couples points two apart along an axis:
    the parity classes are no coloring of it, and JPL colors it."""
    n = 6
    A, M = _operator("7pt", (n, n, n))
    M = (M + sp.diags([0.5, 0.5], [2, -2], shape=M.shape)).tocsr()
    M.sort_indices()
    B = CsrMatrix.from_scipy_like(M.indptr, M.indices, M.data, n ** 3,
                                  n ** 3)
    B = dataclasses.replace(B, grid_shape=(n, n, n)).init()
    col = color_matrix(B, Config.from_string(""), "default")
    assert col.grid is None and _valid(M, col.row_colors)


# -- the sweep ----------------------------------------------------------

def _dense_gauss_seidel(M, colors, b, x, omega, symmetric):
    """Gauss-Seidel on the dense matrix, row by row in the order of the
    colors (rows of one color in index order), and back."""
    D = M.toarray()
    order = np.argsort(np.asarray(colors), kind="stable")
    x = x.copy()
    for rows in (order, order[::-1]) if symmetric else (order,):
        for i in rows:
            x[i] += omega * (b[i] - D[i] @ x) / D[i, i]
    return x


@pytest.mark.parametrize("stencil", ["27pt", "7pt"])
@pytest.mark.parametrize("symmetric", [0, 1])
def test_sweep_under_parity_colors_is_gauss_seidel(stencil, symmetric):
    n = 8
    A, M = _operator(stencil, (n, n, n))
    s = make_solver("MULTICOLOR_GS", Config.from_string(
        f"symmetric_GS={symmetric}, relaxation_factor=0.9"), "default")
    s.setup(A)
    assert s._parity is not None
    b, x = _rhs(n ** 3, 5), _rhs(n ** 3, 6)
    got = s.smooth(s.solve_data(), jnp.asarray(b), jnp.asarray(x), 1)
    want = _dense_gauss_seidel(M, s.row_colors, b, x, 0.9, symmetric)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-13)
    # and the reference's sweep is the same sweep (omega 1, symmetric)
    if stencil == "27pt" and symmetric:
        mine = ref.symgs(ref.stencil27(), b.reshape(n, n, n),
                         x.reshape(n, n, n)).reshape(-1)
        np.testing.assert_allclose(
            mine, _dense_gauss_seidel(M, s.row_colors, b, x, 1.0, True),
            rtol=0, atol=1e-13)


@pytest.mark.parametrize("shape", [(8, 8, 8), (5, 6, 7), (1, 6, 5),
                                   (4, 1, 1)])
@pytest.mark.parametrize("stencil", ["27pt", "7pt"])
def test_sub_lattice_form_equals_masked_form(stencil, shape):
    """The color step on its own rows against the masked step over all
    rows, same colors, same order, variable coefficients: 1e-12."""
    A, M = _operator(stencil, shape)
    n = A.num_rows
    # a symmetric positive scaling makes every coefficient its own
    d = 1.0 + np.random.default_rng(3).random(n)
    S = (sp.diags(d) @ M @ sp.diags(d)).tocsr()
    S.sort_indices()
    A = dataclasses.replace(
        CsrMatrix.from_scipy_like(S.indptr, S.indices, S.data, n, n),
        grid_shape=tuple(shape)).init()
    s = make_solver("MULTICOLOR_GS", Config.from_string(
        "symmetric_GS=1, relaxation_factor=0.9"), "default")
    s.setup(A)
    data = s.solve_data()
    assert "parity" in data
    masked = {k: v for k, v in data.items() if k != "parity"}
    b, x = jnp.asarray(_rhs(n, 7)), jnp.asarray(_rhs(n, 8))
    np.testing.assert_allclose(np.asarray(s.smooth(data, b, x, 2)),
                               np.asarray(s.smooth(masked, b, x, 2)),
                               rtol=0, atol=1e-12)


def test_cut_and_join_are_inverse():
    plan = parity_sweep.ParityPlan(
        (5, 6, 7), ((0, 0, 0),), tuple(itertools.product((0, 1), repeat=2)),
        False)
    v = jnp.asarray(_rhs(5 * 6 * 7, 9)).reshape(7, 6, 5)
    rows = parity_sweep._cut(v, plan)
    for (pz, py), a in zip(plan.rows, rows):
        want = np.asarray(v)[pz::2, py::2]
        got = np.asarray(a)[:want.shape[0], :want.shape[1]]
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(parity_sweep._join(rows, plan)), np.asarray(v))


def test_coloring_has_a_span_of_its_own():
    """amg.L<k>.coloring for every level's smoother and for the swept
    coarsest level, beside smoother_setup and not inside it. 32^3: at
    16^3 the fourth level is 2^3, whose GEO wrap check fails and has
    the levels built (and colored) a second time."""
    A, _M = _operator("27pt", (32, 32, 32))
    spans.reset()
    slv = amgx.create_solver(Config.from_string(PCG_OPTIONS))
    slv.setup(A)
    timers = spans.flat_timers()
    depth = _hierarchy(slv).num_levels
    for k in range(depth):
        assert timers[f"amg.L{k}.coloring"][0] == 1
    assert spans.is_declared("amg.L0.coloring")

"""Multicolor smoothers + coloring validity.

Mirrors the reference tests src/tests/valid_coloring.cu,
ilu_dilu_equivalence.cu, and the scalar/block smoother poisson
convergence tests (src/tests/).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import amgx_tpu as amgx
from amgx_tpu.config import Config
from amgx_tpu.ops.coloring import color_matrix
from amgx_tpu.solvers.base import make_solver

amgx.initialize()


def _poisson(n=8):
    return amgx.gallery.poisson("5pt", n, n).init()


def _valid(A, colors):
    rows, cols, _ = A.coo()
    rows, cols = np.asarray(rows), np.asarray(cols)
    c = np.asarray(colors)
    offd = rows != cols
    return not np.any(c[rows[offd]] == c[cols[offd]])


@pytest.mark.parametrize("scheme", ["MIN_MAX", "MULTI_HASH",
                                    "SERIAL_GREEDY_BFS"])
def test_valid_coloring(scheme):
    """No edge joins two same-colored vertices (valid_coloring.cu)."""
    A = _poisson(12)
    cfg = Config.from_string(f"matrix_coloring_scheme={scheme}")
    col = color_matrix(A, cfg, "default")
    assert _valid(A, col.row_colors)
    assert col.num_colors >= 2


def test_greedy_recolor_shrinks_color_count():
    """GREEDY_RECOLOR (greedy_recolor.cu role): valid coloring with a
    STRICTLY smaller-or-equal color count than plain MIN_MAX — fewer
    colors means shallower DILU/GS sweep chains. The gallery's grid
    matrices without their grid_shape: with it MIN_MAX serves the parity
    coloring (tests/test_hpcg.py), and this is about the JPL base."""
    def no_grid(A):
        return dataclasses.replace(A, grid_shape=None)

    for A in (no_grid(_poisson(16)),
              no_grid(amgx.gallery.poisson("9pt", 12, 12).init()),
              no_grid(amgx.gallery.poisson("27pt", 7, 7, 7).init()),
              amgx.gallery.random_matrix(300, max_nnz_per_row=9, seed=3,
                                         symmetric=True,
                                         diag_dominant=True).init()):
        base = color_matrix(A, Config.from_string(
            "matrix_coloring_scheme=MIN_MAX"), "default")
        rec = color_matrix(A, Config.from_string(
            "matrix_coloring_scheme=GREEDY_RECOLOR"), "default")
        assert _valid(A, rec.row_colors)
        assert rec.num_colors <= base.num_colors
        assert int(np.asarray(rec.row_colors).max()) + 1 == rec.num_colors
    # the 27pt stencil must actually shrink (MIN_MAX overshoots there)
    A = no_grid(amgx.gallery.poisson("27pt", 8, 8, 8).init())
    base = color_matrix(A, Config.from_string(
        "matrix_coloring_scheme=MIN_MAX"), "default")
    rec = color_matrix(A, Config.from_string(
        "matrix_coloring_scheme=GREEDY_RECOLOR"), "default")
    assert rec.num_colors < base.num_colors


def test_greedy_recolor_dilu_converges():
    A = _poisson(12)
    n = A.num_rows
    cfg = Config.from_string(
        "solver=PCG, max_iters=80, monitor_residual=1, tolerance=1e-10,"
        " preconditioner(sm)=MULTICOLOR_DILU,"
        " sm:matrix_coloring_scheme=GREEDY_RECOLOR")
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    b = np.ones(n)
    r = slv.solve(b)
    assert bool(r.converged)
    resid = np.asarray(A.to_dense()) @ np.asarray(r.x) - b
    assert np.linalg.norm(resid) < 1e-8


def test_valid_coloring_distance2():
    A = _poisson(8)
    cfg = Config.from_string("matrix_coloring_scheme=MIN_MAX,"
                             "coloring_level=2")
    col = color_matrix(A, cfg, "default")
    # distance-2 valid: no two rows sharing a neighbor share a color
    import scipy.sparse as sp
    rows, cols, vals = map(np.asarray, A.coo())
    S = sp.csr_matrix((np.ones_like(vals), (rows, cols)), shape=A.shape)
    S2 = (S @ S).tocoo()
    c = np.asarray(col.row_colors)
    offd = S2.row != S2.col
    assert not np.any(c[S2.row[offd]] == c[S2.col[offd]])


@pytest.mark.parametrize("name", ["MULTICOLOR_GS", "MULTICOLOR_DILU",
                                  "MULTICOLOR_ILU", "FIXCOLOR_GS", "GS"])
def test_smoother_converges_poisson(name):
    """Standalone smoother iteration converges on SPD Poisson (the
    scalar smoother poisson tests of src/tests/)."""
    A = _poisson(10)
    n = A.num_rows
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(n)
    b = jnp.asarray(np.asarray(amgx.ops.spmv(A, jnp.asarray(x_true))))
    cfg = Config.from_string(
        f"solver={name}, max_iters=500, monitor_residual=1, tolerance=1e-8,"
        " relaxation_factor=0.9" + (", symmetric_GS=1" if "GS" in name else ""))
    slv = make_solver(name, cfg, "default")
    slv.setup(A)
    res = slv.solve(b)
    assert res.converged, (name, res.res_norm)
    np.testing.assert_allclose(np.asarray(res.x), x_true, atol=1e-5)


def test_dilu_beats_jacobi_as_amg_smoother():
    """AMG with MULTICOLOR_DILU needs fewer FGMRES iterations than
    BLOCK_JACOBI (the reason the reference defaults to DILU)."""
    A = amgx.gallery.poisson("7pt", 16, 16, 16).init()
    b = jnp.ones(A.num_rows)
    iters = {}
    for sm in ["BLOCK_JACOBI", "MULTICOLOR_DILU"]:
        cfg = Config.from_string(
            "solver=FGMRES, max_iters=60, monitor_residual=1,"
            " tolerance=1e-8, gmres_n_restart=30,"
            " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
            " amg:selector=SIZE_2,"
            f" amg:smoother={sm}, amg:max_iters=1, amg:cycle=V,"
            " amg:max_levels=10, amg:relaxation_factor=0.9")
        slv = amgx.create_solver(cfg)
        slv.setup(A)
        res = slv.solve(b)
        assert res.converged
        iters[sm] = res.iterations
    assert iters["MULTICOLOR_DILU"] < iters["BLOCK_JACOBI"], iters


def test_ilu_dilu_equivalence_tridiag():
    """For a (properly colored) tridiagonal matrix ILU(0) and DILU give
    the same preconditioner action (ilu_dilu_equivalence.cu analog:
    both reduce to the same E on matrices with no fill)."""
    n = 32
    main = 2.0 * np.ones(n)
    off = -1.0 * np.ones(n - 1)
    rows = np.concatenate([np.arange(n), np.arange(n - 1), np.arange(1, n)])
    cols = np.concatenate([np.arange(n), np.arange(1, n), np.arange(n - 1)])
    vals = np.concatenate([main, off, off])
    A = amgx.CsrMatrix.from_coo(rows, cols, vals, n, n).init()
    b = jnp.asarray(np.random.default_rng(1).standard_normal(n))
    outs = {}
    for name in ["MULTICOLOR_DILU", "MULTICOLOR_ILU"]:
        cfg = Config.from_string(
            f"solver={name}, max_iters=1, relaxation_factor=1.0")
        slv = make_solver(name, cfg, "default")
        slv.setup(A)
        outs[name] = np.asarray(slv.smooth(slv.solve_data(), b,
                                           jnp.zeros(n), 1))
    np.testing.assert_allclose(outs["MULTICOLOR_DILU"],
                               outs["MULTICOLOR_ILU"], rtol=1e-10)


def test_ilu_exact_factors_small():
    """The color-sweep fixed point reproduces exact ILU(0) factors on a
    small matrix (checked against a dense reference factorization)."""
    rng = np.random.default_rng(3)
    A = _poisson(5)
    n = A.num_rows
    cfg = Config.from_string("solver=MULTICOLOR_ILU, max_iters=1")
    slv = make_solver("MULTICOLOR_ILU", cfg, "default")
    slv.setup(A)
    # dense IKJ ILU(0) on the color-permuted matrix; the solver stores
    # the factors back in ORIGINAL ordering (distribution-aware form),
    # so map the reference the same way
    perm = np.asarray(np.argsort(np.asarray(slv.row_colors),
                                 kind="stable"))
    Ad = np.asarray(A.to_dense())[np.ix_(perm, perm)]
    pattern = Ad != 0
    M = Ad.copy()
    for i in range(n):
        for k in range(i):
            if pattern[i, k] and M[k, k] != 0:
                M[i, k] = M[i, k] / M[k, k]
                for j in range(k + 1, n):
                    if pattern[i, j]:
                        M[i, j] -= M[i, k] * M[k, j]
    L_ref_o = np.zeros((n, n))
    U_ref_o = np.zeros((n, n))
    L_ref_o[np.ix_(perm, perm)] = np.tril(M, -1)
    U_ref_o[np.ix_(perm, perm)] = np.triu(M)
    L_got = np.asarray(slv._Lp.to_dense())
    U_got = np.asarray(slv._Up.to_dense())
    np.testing.assert_allclose(L_got, L_ref_o, atol=1e-12)
    np.testing.assert_allclose(U_got, U_ref_o, atol=1e-12)


def test_block_dilu_converges():
    """DILU on a block matrix (block Poisson) converges."""
    A = amgx.gallery.poisson("5pt", 8, 8).init()
    # expand to 2x2 blocks: A (x) I2 + small coupling
    rows, cols, vals = map(np.asarray, A.coo())
    n = A.num_rows
    bvals = np.einsum("n,xy->nxy", vals, np.eye(2))
    bvals[:, 0, 1] = 0.05 * vals
    Ab = amgx.CsrMatrix.from_coo(rows, cols, jnp.asarray(bvals), n, n,
                                 block_dims=(2, 2)).init()
    nb = 2 * n
    rng = np.random.default_rng(5)
    x_true = rng.standard_normal(nb)
    b = jnp.asarray(np.asarray(amgx.ops.spmv(Ab, jnp.asarray(x_true))))
    cfg = Config.from_string(
        "solver=MULTICOLOR_DILU, max_iters=300, monitor_residual=1,"
        " tolerance=1e-8, relaxation_factor=0.9")
    slv = make_solver("MULTICOLOR_DILU", cfg, "default")
    slv.setup(Ab)
    res = slv.solve(b)
    assert res.converged
    np.testing.assert_allclose(np.asarray(res.x), x_true, atol=1e-5)


@pytest.mark.parametrize("name", ["GS", "MULTICOLOR_ILU",
                                  "MULTICOLOR_DILU", "MULTICOLOR_GS"])
def test_smoothers_with_external_diag(name):
    """DIAG-property matrices (externally stored diagonal) must give the
    same smoother fixed point as in-CSR storage."""
    A = _poisson(8)
    rows, cols, vals = map(np.asarray, A.coo())
    offd = rows != cols
    d = np.asarray(A.diagonal())
    Ax = amgx.CsrMatrix.from_coo(rows[offd], cols[offd],
                                 jnp.asarray(vals[offd]),
                                 A.num_rows, A.num_cols,
                                 diag=jnp.asarray(d)).init()
    rng = np.random.default_rng(2)
    x_true = rng.standard_normal(A.num_rows)
    b = jnp.asarray(np.asarray(amgx.ops.spmv(A, jnp.asarray(x_true))))
    cfg = Config.from_string(
        f"solver={name}, max_iters=500, monitor_residual=1,"
        " tolerance=1e-8, relaxation_factor=0.9")
    slv = make_solver(name, cfg, "default")
    slv.setup(Ax)
    res = slv.solve(b)
    assert res.converged, (name, res.res_norm)
    np.testing.assert_allclose(np.asarray(res.x), x_true, atol=1e-5)


def test_cf_jacobi_under_classical_amg():
    """CF_JACOBI as the smoother of a classical AMG-preconditioned
    solve (cf_jacobi gets its CF map from the level)."""
    A = amgx.gallery.poisson("5pt", 24, 24).init()
    b = jnp.ones(A.num_rows)
    cfg = Config.from_string(
        "solver=PCG, max_iters=60, monitor_residual=1, tolerance=1e-8,"
        " preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
        " amg:smoother=CF_JACOBI, amg:max_iters=1, amg:cycle=V,"
        " amg:relaxation_factor=0.9")
    slv = amgx.create_solver(cfg)
    slv.setup(A)
    res = slv.solve(b)
    assert res.converged
    r = np.asarray(amgx.ops.residual(A, res.x, b))
    assert np.linalg.norm(r) < 1e-6 * np.linalg.norm(np.asarray(b))

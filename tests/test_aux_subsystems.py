"""Auxiliary subsystems: tracing/profiling, permute/sort/analysis
kernels, determinism checker, complex->real ERF conversion (SURVEY §5 /
§2.1 items 10, 14, 15, 60, 61)."""
import numpy as np
import pytest
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery, profiling
from amgx_tpu.config import Config
from amgx_tpu.determinism import (DeterminismChecker, DeterminismError,
                                  fingerprint)
from amgx_tpu.matrix import CsrMatrix
from amgx_tpu.ops.permute import (analyze_matrix, permute_matrix,
                                  permute_vector, sort_rows_by)
from amgx_tpu.solvers import make_solver

amgx.initialize()


# -- profiling ---------------------------------------------------------

def test_trace_regions_accumulate():
    profiling.reset_timers()
    A = gallery.poisson("5pt", 8, 8).init()
    s = make_solver("PCG", Config.from_string(
        "solver=PCG, max_iters=5, preconditioner=BLOCK_JACOBI"),
        "default").setup(A)
    s.solve(jnp.ones(64))
    t = profiling.timers()
    assert any(k.endswith(".setup") for k in t)
    assert any(k.endswith(".solve") for k in t)
    rpt = profiling.format_timers()
    assert "calls" in rpt and "PCG.solve" in rpt
    profiling.reset_timers()
    assert profiling.timers() == {}


# -- permute / analysis ------------------------------------------------

def test_symmetric_permute_preserves_spectrum():
    A = gallery.poisson("5pt", 6, 6).init()
    n = A.num_rows
    rng = np.random.default_rng(0)
    perm = jnp.asarray(rng.permutation(n), jnp.int32)
    B = permute_matrix(A, row_perm=perm, col_perm=perm).init()
    Ad = np.asarray(A.to_dense())
    Bd = np.asarray(B.to_dense())
    p = np.asarray(perm)
    np.testing.assert_allclose(Bd, Ad[np.ix_(p, p)], atol=0)
    # vector permute consistency: (PAP^T)(Px) = P(Ax)
    x = rng.standard_normal(n)
    lhs = np.asarray(amgx.ops.spmv(B, permute_vector(jnp.asarray(x), perm)))
    rhs = np.asarray(permute_vector(amgx.ops.spmv(A, jnp.asarray(x)), perm))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-12)


def test_sort_rows_by():
    A = gallery.poisson("5pt", 4, 4).init()
    key = -jnp.arange(16.0)          # reversal
    B, perm = sort_rows_by(A, key)
    np.testing.assert_array_equal(np.asarray(perm), np.arange(15, -1, -1))


def test_analyze_matrix():
    A = gallery.poisson("5pt", 8, 8).init()
    info = analyze_matrix(A)
    assert info.is_structurally_symmetric and info.is_symmetric
    assert info.diag_dominant_rows == 64          # Poisson: weakly dominant
    assert info.bandwidth == 8
    assert not info.has_zero_diag
    assert info.min_row_nnz == 3 and info.max_row_nnz == 5
    # asymmetric matrix detected
    B = CsrMatrix.from_coo(np.array([0, 0, 1]), np.array([0, 1, 1]),
                           np.array([2.0, -1.0, 2.0]), 2, 2).init()
    info2 = analyze_matrix(B)
    assert not info2.is_structurally_symmetric


# -- determinism checker ----------------------------------------------

def test_determinism_checker_pass_and_fail():
    chk = DeterminismChecker()
    A = gallery.poisson("5pt", 8, 8).init()
    s = make_solver("PCG", Config.from_string(
        "solver=PCG, max_iters=8, preconditioner=BLOCK_JACOBI"),
        "default").setup(A)
    b = jnp.ones(64)
    r1 = s.solve(b)
    chk.observe("x", r1.x)
    chk.start_verification()
    r2 = s.solve(b)
    chk.observe("x", r2.x)      # bit-exact repeat must pass
    chk.finish()
    # drift is caught
    chk2 = DeterminismChecker()
    chk2.observe("x", r1.x)
    chk2.start_verification()
    drift = np.asarray(r1.x).copy()
    drift[0] = np.nextafter(drift[0], np.inf)   # one-ulp drift
    with pytest.raises(DeterminismError):
        chk2.observe("x", drift)
    assert fingerprint(r1.x) == fingerprint(np.asarray(r1.x))


# -- complex -> real ERF ----------------------------------------------

def _random_complex_system(n=24, seed=0):
    rng = np.random.default_rng(seed)
    A5 = gallery.poisson("5pt", 6, 4)
    rows, cols, _ = [np.asarray(v) for v in A5.init().coo()]
    vals = rng.standard_normal(rows.size) + 1j * rng.standard_normal(
        rows.size)
    # make it solvable: diagonally dominant complex
    vals[rows == cols] = 8.0 + 2.0j
    A = CsrMatrix.from_coo(rows, cols, jnp.asarray(vals), n, n)
    z = rng.standard_normal(n) + 1j * rng.standard_normal(n)
    return A.init(), jnp.asarray(z)


@pytest.mark.parametrize("mode", [1, 2, 3, 4, 221, 222, 223, 224])
def test_erf_conversion_matches_dense_form(mode):
    """Every K-form reproduces its dense equivalent exactly, and the
    converted system is consistent: M x_erf = b_erf for the known
    complex solution."""
    from amgx_tpu.io.complex import complex_system_to_real
    A, zsol = _random_complex_system()
    Ad = np.asarray(A.to_dense())
    b = Ad @ np.asarray(zsol)
    A2, b2, x2 = complex_system_to_real(A, b, zsol, mode=mode)
    M = np.asarray(A2.init().to_dense())
    R, I = np.real(Ad), np.imag(Ad)
    forms = {1: np.block([[R, -I], [I, R]]),
             2: np.block([[R, I], [I, -R]]),
             3: np.block([[I, R], [R, -I]]),
             4: np.block([[I, -R], [R, I]])}
    m0 = mode - 220 if mode > 220 else mode
    ref = forms[m0]
    if mode > 220:
        n = Ad.shape[0]
        p = np.arange(2 * n).reshape(2, n).T.ravel()   # interleave blocks
        ref = ref[np.ix_(p, p)]
    np.testing.assert_allclose(M, ref, atol=0)
    # consistency: the converted solution solves the converted system
    np.testing.assert_allclose(M @ np.asarray(x2), np.asarray(b2),
                               rtol=1e-12, atol=1e-12)


def test_erf_k1_end_to_end_solve():
    """Solve the K1 real system and recover the complex solution."""
    from amgx_tpu.io.complex import (complex_system_to_real,
                                     real_solution_to_complex)
    A, zsol = _random_complex_system()
    b = np.asarray(A.to_dense()) @ np.asarray(zsol)
    A2, b2, _ = complex_system_to_real(A, b, None, mode=1)
    solver = make_solver("FGMRES", Config.from_string(
        "solver=FGMRES, max_iters=300, gmres_n_restart=60, "
        "tolerance=1e-12, monitor_residual=1, "
        "convergence=RELATIVE_INI_CORE"), "default").setup(A2.init())
    res = solver.solve(b2)
    z = np.asarray(real_solution_to_complex(res.x, mode=1))
    np.testing.assert_allclose(z, np.asarray(zsol), rtol=1e-7, atol=1e-8)


def test_capi_complex_read(tmp_path):
    """A complex MatrixMarket file + complex_conversion config reads as
    the ERF real system through the C API (readers.cu:221 analog)."""
    from amgx_tpu import capi
    from amgx_tpu.io import write_system
    A, zsol = _random_complex_system()
    b = np.asarray(A.to_dense()) @ np.asarray(zsol)
    p = str(tmp_path / "c.mtx")
    write_system(p, A, b=jnp.asarray(b))
    assert capi.AMGX_initialize() == capi.RC.OK
    rc, cfg = capi.AMGX_config_create(
        "config_version=2, solver=FGMRES, complex_conversion=1")
    rc, rsc = capi.AMGX_resources_create_simple(cfg)
    rc, mh = capi.AMGX_matrix_create(rsc, "dDDI")
    rc, bh = capi.AMGX_vector_create(rsc, "dDDI")
    assert capi.AMGX_read_system(mh, bh, None, p) == capi.RC.OK
    rc, n, bx, by = capi.AMGX_matrix_get_size(mh)
    assert n == 48 and bx == 1      # 2n scalar ERF
    capi.AMGX_finalize()


def test_convergence_analysis_report():
    """convergence_analysis=k runs the instrumented error-propagation
    cycle (convergence_analysis.cu analog) and reports per-level phase
    reductions; smoothing and the full cycle must actually reduce the
    error on Poisson."""
    from amgx_tpu.amg.hierarchy import AMG
    from amgx_tpu.amg.analysis import convergence_analysis
    from amgx_tpu.config import Config
    from amgx_tpu import gallery
    cfg = Config.from_string(
        "algorithm=AGGREGATION, selector=SIZE_2, smoother=BLOCK_JACOBI,"
        " relaxation_factor=0.9, presweeps=1, postsweeps=1,"
        " coarse_solver=DENSE_LU_SOLVER, min_coarse_rows=16,"
        " convergence_analysis=2")
    amg = AMG(cfg)
    amg.setup(gallery.poisson("7pt", 10, 10, 10).init())
    report = convergence_analysis(amg)
    lines = [ln for ln in report.splitlines()[2:] if ln.strip()]
    assert len(lines) == 2          # two instrumented levels
    for ln in lines:
        cols = ln.split()
        pre, total = float(cols[2]), float(cols[5])
        assert pre < 1.0 and total < 1.0


# ---------------------------------------------------------------------------
# native library: builds from source, and says so when it cannot
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_native(tmp_path, monkeypatch):
    """amgx_tpu.native pointed at an empty build directory with its
    per-process memo cleared (restored afterwards)."""
    import shutil
    from amgx_tpu import native
    if shutil.which("g++") is None:
        pytest.skip("no g++ on this machine")
    monkeypatch.setattr(native, "_BUILD", str(tmp_path / "_build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_attempted_hash", None)
    monkeypatch.setattr(native, "_build_error", None)
    return native


def test_native_builds_and_loads_from_empty_build_dir(fresh_native):
    """Every translation unit of native/src compiles with the installed
    g++ into an EMPTY _build/ and the library loads with its symbols —
    one TU that does not compile used to take the whole .so with it,
    behind a single warning."""
    import os
    native = fresh_native
    L = native.lib(required=True)
    assert L is not None
    built = os.listdir(native._BUILD)
    assert built == [f"libamgx_native-{native.source_hash()}.so"]
    for sym in ("amgx_rs_coarsen", "amgx_pmis", "amgx_rap_plan_values",
                "amgx_strength_ahat"):
        assert hasattr(L, sym)


def test_native_build_failure_raises_when_required(fresh_native,
                                                   tmp_path, monkeypatch):
    """A failed build is a warning plus None for callers that can fall
    back, and a RuntimeError carrying the compiler's words for callers
    that asked for the native form."""
    native = fresh_native
    src = tmp_path / "src"
    src.mkdir()
    (src / "broken.cpp").write_text("int f( { return size_t(0); }\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    with pytest.warns(RuntimeWarning, match="native library build failed"):
        assert native.lib() is None
    with pytest.raises(RuntimeError, match="broken.cpp"):
        native.lib(required=True)

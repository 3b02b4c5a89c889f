"""Fused smoother+residual kernel suite tests (ops/smooth.py,
ops/pallas_spmv.py dia_smooth, ops/pallas_swell.py swell_smooth_step).

The kernels run through the Pallas interpreter (force_pallas_interpret,
the CPU test path); the compiled path runs on the chip in the
benchmark's cells.
Covers: multi-sweep parity vs the sweep-by-sweep reference for
Jacobi-L1 and Chebyshev tau schedules on DIA and SWELL layouts, f32
(kernel) and f64 (the XLA slab fallback the custom_vmap routes to),
single-RHS and batched; a trace-count test proving the cycle does not
retrace when smooth_residual is enabled; and the HBM-pass regression
tooling: jaxpr inspection of the traced cycle asserting the fused path
removes the standalone residual SpMV at smoothed levels."""
import dataclasses
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops import smooth as fused
from amgx_tpu.ops.spmv import spmv

import _census

amgx.initialize()


def _ref_sweeps(A, b, x, taus, dinv=None, with_residual=True):
    """Sweep-by-sweep reference: x += tau_s * dinv . (b - A x)."""
    for t in range(taus.shape[0]):
        upd = taus[t] * (b - spmv(A, x))
        if dinv is not None:
            upd = upd * dinv
        x = x + upd
    if with_residual:
        return x, b - spmv(A, x)
    return x


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) /
                 jnp.maximum(jnp.linalg.norm(b), 1e-300))


def _swell_matrix(n=24, dtype=jnp.float32):
    """Poisson 5-pt with the layout forced to SWELL."""
    from amgx_tpu.ops.pallas_swell import build_swell_host
    A = gallery.poisson("5pt", n, n, dtype=dtype).init()
    out = build_swell_host(np.asarray(A.row_offsets),
                           np.asarray(A.col_indices),
                           np.asarray(A.values, np.float32),
                           A.num_rows, A.num_cols)
    assert out is not None
    c4, v4, c0r, nch, w128 = out
    return dataclasses.replace(
        A, dia_offsets=None, dia_vals=None, ell_cols=None, ell_vals=None,
        swell_cols=jnp.asarray(c4), swell_vals=jnp.asarray(v4),
        swell_c0row=jnp.asarray(c0r), swell_nchunk=jnp.asarray(nch),
        swell_w128=int(w128))


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule,with_dinv", [
    ("jacobi", True),       # constant tau + dinv (JACOBI / JACOBI_L1)
    ("cheb", False),        # per-step taus, no dinv (CHEBYSHEV_POLY)
])
@pytest.mark.parametrize("with_residual", [True, False])
def test_dia_fused_parity_f32(schedule, with_dinv, with_residual):
    A = gallery.poisson("7pt", 10, 10, 10, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), jnp.float32) \
        if with_dinv else None
    taus = jnp.asarray(np.full(3, 0.9) if schedule == "jacobi"
                       else rng.uniform(0.05, 0.2, 3), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, with_residual)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(A, dinv)
        out = fused.dia_fused_smooth(A, slabs, b, x, taus, dinv=dinv,
                                     with_residual=with_residual)
    assert out is not None
    if with_residual:
        assert _rel(out[0], ref[0]) < 1e-6
        assert _rel(out[1], ref[1]) < 1e-6
    else:
        assert _rel(out, ref) < 1e-6


def test_dia_fused_parity_multiblock_and_chained():
    """A VMEM budget the level's one block does not fit sends it to
    the carry form (several blocks, the levels' edge rows carried from
    block to block: tests/test_dia_carry.py), still one call."""
    A = gallery.poisson("7pt", 16, 16, 16, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), jnp.float32)
    taus = jnp.asarray(np.full(3, 0.8), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, True)
    old = ps._SMOOTH_VMEM_BUDGET
    try:
        for budget in (300 * 1024, 120 * 1024):
            ps._SMOOTH_VMEM_BUDGET = budget
            with ps.force_pallas_interpret():
                slabs = fused.build_fused_slabs(A, dinv)
                xf, rf = fused.dia_fused_smooth(A, slabs, b, x, taus,
                                                dinv=dinv,
                                                with_residual=True)
            assert _rel(xf, ref[0]) < 1e-6
            assert _rel(rf, ref[1]) < 1e-6
    finally:
        ps._SMOOTH_VMEM_BUDGET = old


def test_dia_slab_fallback_parity_f64():
    """The XLA multi-RHS slab form (what f64 and vmapped callers run)
    matches the sweep-by-sweep reference to f64 accuracy."""
    from amgx_tpu.ops.batched import smooth_dia_multi
    A = gallery.poisson("7pt", 8, 8, 8).init()      # f64
    n = A.num_rows
    rng = np.random.default_rng(2)
    B = jnp.asarray(rng.standard_normal((3, n)))
    X = jnp.asarray(rng.standard_normal((3, n)))
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n))
    taus = jnp.asarray(np.full(2, 0.85))
    XF, RF = smooth_dia_multi(A, B, X, taus, dinv, True)
    for i in range(3):
        xr, rr = _ref_sweeps(A, B[i], X[i], taus, dinv, True)
        assert _rel(XF[i], xr) < 1e-12
        assert _rel(RF[i], rr) < 1e-12


def test_dia_fused_vmap_routes_to_slab():
    """Under jax.vmap (the batched-solve subsystem's shape) the fused
    dispatch must take the multi-RHS slab form and match per-system
    references — single-RHS kernels have no batching rule."""
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(3)
    B = jnp.asarray(rng.standard_normal((4, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((4, n)), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(A, dinv)
        XF, RF = jax.vmap(
            lambda bb, xx: fused.dia_fused_smooth(
                A, slabs, bb, xx, taus, dinv=dinv, with_residual=True)
        )(B, X)
    for i in range(4):
        xr, rr = _ref_sweeps(A, B[i], X[i], taus, dinv, True)
        assert _rel(XF[i], xr) < 1e-6
        assert _rel(RF[i], rr) < 1e-6


@pytest.mark.parametrize("with_dinv", [True, False])
def test_swell_fused_step_parity(with_dinv):
    A = _swell_matrix()
    n = A.num_rows
    rng = np.random.default_rng(4)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(3, 6, n), jnp.float32) \
        if with_dinv else None
    taus = jnp.asarray(np.full(2, 0.7), jnp.float32)
    ref = _ref_sweeps(A, b, x, taus, dinv, True)
    with ps.force_pallas_interpret():
        out = fused.swell_fused_smooth(A, b, x, taus, dinv=dinv,
                                       with_residual=True)
    assert out is not None
    assert _rel(out[0], ref[0]) < 1e-6
    assert _rel(out[1], ref[1]) < 1e-6


def test_fused_smooth_solver_entry_matches_unfused():
    """Solver-level parity: JACOBI_L1.smooth_residual with the fused
    path engaged equals the fused_smoother=0 compose."""
    from amgx_tpu.solvers.base import make_solver
    A = gallery.poisson("7pt", 10, 10, 10, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(5)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    cfg = Config.from_string("solver=JACOBI_L1, max_iters=2")
    off = make_solver("JACOBI_L1", cfg, "default")
    off.fused_smoother = False
    off.setup(A)
    x_off, r_off = off.smooth_residual(off.solve_data(), b, x, 2)
    with ps.force_pallas_interpret():
        on = make_solver("JACOBI_L1", cfg, "default")
        on.setup(A)
        d = on.solve_data()
        assert "fused" in d, "fused payload missing from solve_data"
        x_on, r_on = on.smooth_residual(d, b, x, 2)
    assert _rel(x_on, x_off) < 1e-6
    assert _rel(r_on, r_off) < 1e-6


# ---------------------------------------------------------------------------
# cycle integration: trace count + HBM passes per level
# ---------------------------------------------------------------------------

_CYCLE_CFG = (
    "solver(s)=PCG, s:max_iters=30, s:tolerance=1e-7,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=GEO, amg:smoother=JACOBI_L1, amg:presweeps=2,"
    " amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10")


def _cycle_pallas_counts(extra_cfg=""):
    """Trace one V-cycle with the Pallas gates forced on; return
    (n_levels, fused_calls, plain_spmv_calls) from the jaxpr."""
    A = gallery.poisson("7pt", 16, 16, 16, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(
            Config.from_string(_CYCLE_CFG + extra_cfg))
        slv.setup(A)
        pc = slv.preconditioner
        d = pc.solve_data()
        jaxpr = str(jax.make_jaxpr(
            lambda bb, xx: pc.amg.cycle(d["amg"], bb, xx))(
                b, jnp.zeros_like(b)))
    names = _census.KERNEL_NAME_RE.findall(jaxpr)
    fused_calls = sum(1 for nm in names if "dia_smooth" in nm)
    plain = sum(1 for nm in names if "dia_spmv" in nm)
    return len(pc.amg.levels), fused_calls, plain


def test_cycle_hbm_passes_fused_removes_residual_spmv():
    """HBM-pass regression tooling: per smoothed DIA level the fused
    cycle must run exactly TWO single-pass kernels (presmooth+residual
    fused; postsmooth fused) and ZERO standalone dia-SpMV kernels —
    i.e. the presmooth->residual pair costs one pass over A instead of
    presweeps+1, at every level. The unfused trace of the same cycle
    shows the removed passes."""
    n_levels, fused_calls, plain = _cycle_pallas_counts()
    assert n_levels >= 2
    assert fused_calls == 2 * n_levels, \
        f"expected 2 fused kernels per level, got {fused_calls} for " \
        f"{n_levels} levels"
    assert plain == 0, \
        f"{plain} standalone dia-SpMV kernels remain in the fused cycle"
    n2, fused_off, plain_off = _cycle_pallas_counts(
        ", fused_smoother=0")
    assert n2 == n_levels
    assert fused_off == 0
    # the jaxpr counts SpMV *sites*, not dynamic passes (a fori_loop
    # body traces once for all sweeps): per level the unfused cycle
    # keeps >= 3 dia-SpMV sites — the smoother's sweep body (pre and
    # post) plus the standalone residual the fused path eliminates
    assert plain_off >= 3 * n_levels, \
        f"unfused cycle expected >= {3 * n_levels} dia-SpMV sites, " \
        f"got {plain_off}"


_CLASSICAL_CFG = (
    "solver(s)=PCG, s:max_iters=30, s:tolerance=1e-7,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
    " amg:selector=PMIS, amg:interpolator=D2,"
    " amg:smoother=JACOBI_L1, amg:presweeps=2, amg:postsweeps=1,"
    " amg:max_iters=1, amg:coarse_solver=DENSE_LU_SOLVER,"
    " amg:min_coarse_rows=16, amg:max_levels=10,"
    " amg:interp_max_elements=4")
# the hierarchies a solve is driven through: GEO aggregation (DIA
# levels, the GEO transfers) and classical (a DIA fine level, SWELL
# below it, P and R as operators)
_SOLVE_CFGS = {"geo": _CYCLE_CFG, "classical": _CLASSICAL_CFG}
_solve_cfgs = pytest.mark.parametrize("kind", sorted(_SOLVE_CFGS))


@_solve_cfgs
def test_cycle_does_not_retrace_with_fused_smoother(kind):
    """One jit trace serves repeated solves (and a value-only change)
    when smooth_residual/fused kernels are enabled."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(6)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_SOLVE_CFGS[kind]))
        slv.setup(A)
        r1 = slv.solve(jnp.asarray(rng.standard_normal(n), jnp.float32))
        assert len(slv._jit_cache) == 1
        r2 = slv.solve(jnp.asarray(rng.standard_normal(n), jnp.float32))
        assert len(slv._jit_cache) == 1, \
            "cycle retraced on a value-only change of b"
        assert r1.converged and r2.converged


def _plain_solve(cfg, A, b):
    """A fresh set-up and solve with no kernel anywhere: the answer the
    kernels' cycle is held to."""
    ref = amgx.create_solver(
        Config.from_string(cfg + ", fused_smoother=0"))
    ref.setup(A)
    return ref.solve(b)


@_solve_cfgs
def test_cycle_fused_matches_unfused_solution(kind):
    """End-to-end: the fused cycle converges to the same answer in the
    same iteration count as the unfused one."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    r0 = _plain_solve(_SOLVE_CFGS[kind], A, b)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_SOLVE_CFGS[kind]))
        slv.setup(A)
        r1 = slv.solve(b)
    assert r1.converged
    assert abs(r1.iterations - r0.iterations) <= 1
    assert _rel(r1.x, r0.x) < 1e-4


@_solve_cfgs
def test_solve_many_matches_single_solves(kind):
    """solve_many drives the cycle under vmap: the kernels' custom_vmap
    rules land in the slab forms and match per-system solves."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    rng = np.random.default_rng(8)
    Bs = jnp.asarray(rng.standard_normal((3, A.num_rows)), jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_SOLVE_CFGS[kind]))
        slv.setup(A)
        res = slv.solve_many(Bs)
        singles = [slv.solve(Bs[i]).x for i in range(3)]
    for i in range(3):
        assert _rel(res.x[i], singles[i]) < 1e-5


@pytest.mark.parametrize("route", ["value", "structure"])
def test_resetup_leaves_the_cycle_right(route):
    """structure_reuse_levels=-1 under the kernels: the value-only
    splice (GEO + CHEBYSHEV_POLY + DENSE_LU, the flagship's shape) and
    the structure-reuse rebuild (classical: P and R kept, Galerkin
    products anew) both solve the new coefficients as a fresh plain
    set-up on them does."""
    cfg = {"value": _CYCLE_CFG.replace(
        "amg:smoother=JACOBI_L1", "amg:smoother=CHEBYSHEV_POLY,"
        " amg:chebyshev_polynomial_order=2"),
        "structure": _CLASSICAL_CFG}[route]
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    A2 = A.with_values(A.values * 1.5)
    A2 = A2 if A2.initialized else A2.init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(
            cfg + ", amg:structure_reuse_levels=-1"))
        slv.setup(A)
        amg = slv.preconditioner.amg
        kept = [lv.P for lv in amg.levels] if route == "structure" \
            else None
        slv.solve(b)
        slv.resetup(A2)
        assert amg._last_resetup_value_only == (route == "value")
        if kept is not None:
            assert all(lv.P is p for lv, p in zip(amg.levels, kept))
        r2 = slv.solve(b)
    r0 = _plain_solve(cfg, A2, b)
    assert r2.converged
    assert abs(int(r2.iterations) - int(r0.iterations)) <= 1
    assert _rel(r2.x, r0.x) < 1e-4


def test_fused_payload_refreshes_on_resetup():
    """The quota-padded operand slabs are rebuilt when the matrix
    coefficients change (the solve-data resetup contract)."""
    from amgx_tpu.solvers.base import make_solver
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    cfg = Config.from_string("solver=JACOBI_L1, max_iters=2")
    with ps.force_pallas_interpret():
        s = make_solver("JACOBI_L1", cfg, "default")
        s.setup(A)
        v1 = s.solve_data()["fused"]["vals_q"]
        A2 = A.with_values(A.values * 2.0)
        s.resetup(A2 if A2.initialized else A2.init())
        v2 = s.solve_data()["fused"]["vals_q"]
    assert v1 is not v2
    np.testing.assert_allclose(np.asarray(v2), 2.0 * np.asarray(v1),
                               rtol=1e-6)

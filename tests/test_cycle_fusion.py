"""Cycle-fusion test suite (ops/smooth.py transfer dispatch,
ops/pallas_spmv.py dia_smooth_restrict / dia_prolong_smooth /
dia_coarse_tail kernels, amg/cycles.py hooks).

Kernels run through the Pallas interpreter (force_pallas_interpret, the
CPU test path); what the chip's compiler accepts of them is in
tests/test_chip_compile.py.
Covers: kernel parity for the restriction epilogue and the
prolongation/correction prologue vs the unfused reference (f32 through
the kernels, f64 through the XLA slab fallback in ops/batched.py),
single-RHS / multi-block / chained schedules / vmapped batches; the
VMEM-resident coarse-tail kernel against the per-level composition; the
jaxpr HBM-pass proof (<= 2 kernels per fused smoothed DIA level
including its grid transfers, 1 kernel for the tail, zero standalone
restrict/prolongate/correction ops outside the kernels); and the
cycle_fusion=0 escape hatch reproducing the PR 4 composition."""
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


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) /
                 jnp.maximum(jnp.linalg.norm(b), 1e-300))


def _ref_sweeps(A, b, x, taus, dinv=None):
    for t in range(taus.shape[0]):
        upd = taus[t] * (b - spmv(A, x))
        if dinv is not None:
            upd = upd * dinv
        x = x + upd
    return x, b - spmv(A, x)


def _geo_agg(nx, ny, nz):
    """The GEO selector's 2x2x2 aggregates map (host numpy)."""
    n = nx * ny * nz
    i = np.arange(n)
    x, t = i % nx, i // nx
    y, z = t % ny, t // ny
    cnx, cny, cnz = (nx + 1) // 2, (ny + 1) // 2, (nz + 1) // 2
    agg = ((z // 2) * cny + (y // 2)) * cnx + (x // 2)
    return agg.astype(np.int32), cnx * cny * cnz


def _problem(n=10, dtype=jnp.float32, seed=0):
    A = gallery.poisson("7pt", n, n, n, dtype=dtype).init()
    agg, nc = _geo_agg(n, n, n)
    rng = np.random.default_rng(seed)
    b = jnp.asarray(rng.standard_normal(A.num_rows), dtype)
    x = jnp.asarray(rng.standard_normal(A.num_rows), dtype)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, A.num_rows), dtype)
    xc = jnp.asarray(rng.standard_normal(nc), dtype)
    return A, agg, nc, b, x, dinv, xc


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule,with_dinv", [
    ("jacobi", True),       # constant tau + dinv (JACOBI / JACOBI_L1)
    ("cheb", False),        # per-step taus, no dinv (CHEBYSHEV_POLY)
])
def test_restrict_epilogue_parity_f32(schedule, with_dinv):
    A, agg, nc, b, x, dinv, _ = _problem()
    dinv = dinv if with_dinv else None
    rng = np.random.default_rng(7)
    taus = jnp.asarray(np.full(2, 0.9) if schedule == "jacobi"
                       else rng.uniform(0.05, 0.2, 2), jnp.float32)
    xr, rr = _ref_sweeps(A, b, x, taus, dinv)
    bc_ref = jax.ops.segment_sum(rr, jnp.asarray(agg), num_segments=nc)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(A, dinv)
        xfer = fused.build_transfer_slabs(A, agg, nc)
        out = fused.fused_smooth_restrict(
            {"A": A, "fused": slabs}, b, x, taus, xfer, dinv=dinv)
    assert out is not None
    assert _rel(out[0], xr) < 1e-6
    assert _rel(out[1], bc_ref) < 1e-6


@pytest.mark.parametrize("with_dinv", [True, False])
def test_prolong_prologue_parity_f32(with_dinv):
    A, agg, nc, b, x, dinv, xc = _problem(seed=1)
    dinv = dinv if with_dinv else None
    taus = jnp.asarray(np.full(2, 0.85), jnp.float32)
    xr, _ = _ref_sweeps(A, b, x + xc[jnp.asarray(agg)], taus, dinv)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(A, dinv)
        xfer = fused.build_transfer_slabs(A, agg, nc)
        out = fused.fused_corr_smooth(
            {"A": A, "fused": slabs}, b, x, xc, taus, xfer, dinv=dinv)
    assert out is not None
    assert _rel(out, xr) < 1e-6


def test_transfer_parity_multiblock_and_chained():
    """Small VMEM budgets force the multi-block path (straddling
    aggregates complete in the per-block window combine) and the
    chained dispatch (plain fused chunks + the transfer chunk)."""
    A, agg, nc, b, x, dinv, xc = _problem(n=16, seed=2)
    taus = jnp.asarray(np.full(3, 0.8), jnp.float32)
    xr, rr = _ref_sweeps(A, b, x, taus, dinv)
    bc_ref = jax.ops.segment_sum(rr, jnp.asarray(agg), num_segments=nc)
    xr2, _ = _ref_sweeps(A, b, x + xc[jnp.asarray(agg)], taus, dinv)
    old = ps._SMOOTH_VMEM_BUDGET
    try:
        for budget in (400 * 1024, 300 * 1024):  # multi-block; chained
            ps._SMOOTH_VMEM_BUDGET = budget
            with ps.force_pallas_interpret():
                slabs = fused.build_fused_slabs(A, dinv)
                xfer = fused.build_transfer_slabs(A, agg, nc)
                data = {"A": A, "fused": slabs}
                xf, bcf = fused.fused_smooth_restrict(
                    data, b, x, taus, xfer, dinv=dinv)
                xf2 = fused.fused_corr_smooth(
                    data, b, x, xc, taus, xfer, dinv=dinv)
            assert _rel(xf, xr) < 1e-6
            assert _rel(bcf, bc_ref) < 1e-6
            assert _rel(xf2, xr2) < 1e-6
    finally:
        ps._SMOOTH_VMEM_BUDGET = old


def test_transfer_slab_fallback_parity_f64():
    """The XLA slab forms (what f64 and vmapped callers run) match the
    unfused reference to f64 accuracy."""
    from amgx_tpu.ops.batched import (corr_smooth_dia_multi,
                                      smooth_restrict_dia_multi)
    A, agg, nc, _, _, _, _ = _problem(n=8)      # f64 below
    A = gallery.poisson("7pt", 8, 8, 8).init()
    agg, nc = _geo_agg(8, 8, 8)
    n = A.num_rows
    rng = np.random.default_rng(3)
    B = jnp.asarray(rng.standard_normal((3, n)))
    X = jnp.asarray(rng.standard_normal((3, n)))
    XC = jnp.asarray(rng.standard_normal((3, nc)))
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n))
    taus = jnp.asarray(np.full(2, 0.85))
    xfer = fused.build_transfer_slabs(A, agg, nc)
    assert xfer is not None
    XF, BCF = smooth_restrict_dia_multi(A, B, X, taus, dinv, xfer)
    XF2 = corr_smooth_dia_multi(A, B, X, XC, taus, dinv, xfer)
    for i in range(3):
        xr, rr = _ref_sweeps(A, B[i], X[i], taus, dinv)
        bc = jax.ops.segment_sum(rr, jnp.asarray(agg), num_segments=nc)
        assert _rel(XF[i], xr) < 1e-12
        assert _rel(BCF[i], bc) < 1e-12
        xr2, _ = _ref_sweeps(A, B[i], X[i] + XC[i][jnp.asarray(agg)],
                             taus, dinv)
        assert _rel(XF2[i], xr2) < 1e-12


def test_transfer_vmap_routes_to_slab():
    """Under jax.vmap (the batched-solve subsystem's shape) the fused
    transfer calls must take the multi-RHS slab forms and match
    per-system references — the single-RHS kernels have no batching
    rule."""
    A, agg, nc, _, _, dinv, _ = _problem(n=8, seed=4)
    n = A.num_rows
    rng = np.random.default_rng(4)
    B = jnp.asarray(rng.standard_normal((4, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((4, n)), jnp.float32)
    XC = jnp.asarray(rng.standard_normal((4, nc)), jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(A, dinv)
        xfer = fused.build_transfer_slabs(A, agg, nc)
        data = {"A": A, "fused": slabs}
        XF, BCF = jax.vmap(
            lambda bb, xx: fused.fused_smooth_restrict(
                data, bb, xx, taus, xfer, dinv=dinv))(B, X)
        XF2 = jax.vmap(
            lambda bb, xx, xcc: fused.fused_corr_smooth(
                data, bb, xx, xcc, taus, xfer, dinv=dinv))(B, X, XC)
    for i in range(4):
        xr, rr = _ref_sweeps(A, B[i], X[i], taus, dinv)
        bc = jax.ops.segment_sum(rr, jnp.asarray(agg), num_segments=nc)
        assert _rel(XF[i], xr) < 1e-6
        assert _rel(BCF[i], bc) < 1e-6
        xr2, _ = _ref_sweeps(A, B[i], X[i] + XC[i][jnp.asarray(agg)],
                             taus, dinv)
        assert _rel(XF2[i], xr2) < 1e-6


# ---------------------------------------------------------------------------
# cycle integration: kernel counts, tail, escape hatch
# ---------------------------------------------------------------------------

_CYCLE_CFG = (
    "solver(s)=PCG, s:max_iters=30, s:tolerance=1e-7,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=GEO, amg:smoother=JACOBI_L1, amg:presweeps=2,"
    " amg:postsweeps=1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16,"
    " amg:max_levels=10")


def _trace_cycle(extra_cfg="", n=16):
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_CYCLE_CFG
                                                    + extra_cfg))
        slv.setup(A)
        pc = slv.preconditioner
        d = pc.solve_data()
        jaxpr = jax.make_jaxpr(
            lambda bb, xx: pc.amg.cycle(d["amg"], bb, xx))(
                b, jnp.zeros_like(b))
    return pc.amg, jaxpr


# jaxpr census helpers shared across the fusion suites (tests/_census.py)
_kernel_counts = _census.kernel_counts
_outer_prims = _census.outer_prims


def test_jaxpr_proof_fused_cycle_kernel_budget():
    """HBM-pass proof: with the tail capped below L1, the fused GEO
    cycle runs EXACTLY two kernels for the smoothed fine level
    (presmooth+restrict, prolongate+postsmooth) and ONE kernel for the
    whole coarse tail — no standalone dia-SpMV passes, and zero
    standalone restrict / prolongate / correction ops (gather, scatter,
    interior pad) outside the kernels."""
    amg, jaxpr = _trace_cycle(", amg:cycle_fusion_tail_rows=600")
    assert len(amg.levels) == 2
    c = _kernel_counts(jaxpr)
    assert c.get("_dia_smooth_restrict_call", 0) == 1, c
    assert c.get("_dia_prolong_smooth_call", 0) == 1, c
    assert c.get("_dia_coarse_tail_call", 0) == 1, c
    assert c.get("_dia_smooth_call", 0) == 0, c
    assert c.get("_dia_spmv_call", 0) == 0, c
    outer = set(_outer_prims(jaxpr))
    # the unfused GEO transfers show up as interior pads (prolongation
    # broadcast) / gathers (generic aggregation) / scatter-adds
    # (segment-sum restriction); the fused trace must have none
    assert not outer & {"pad", "gather", "scatter-add", "scatter"}, \
        sorted(outer & {"pad", "gather", "scatter-add", "scatter"})


def test_jaxpr_proof_whole_cycle_tail():
    """With every level under the tail threshold the ENTIRE cycle is
    one pallas_call."""
    amg, jaxpr = _trace_cycle()
    c = _kernel_counts(jaxpr)
    assert c == {"_dia_coarse_tail_call": 1}, c


def test_cycle_fusion_off_restores_pr4_composition():
    """cycle_fusion=0 must trace the PR 4 composition exactly: two
    fused smoother kernels per level, zero transfer/tail kernels — and
    the same jaxpr as the fusion path's structural fallback (hooks
    returning None), proving the escape hatch IS the old code path."""
    amg, jaxpr = _trace_cycle(", amg:cycle_fusion=0")
    c = _kernel_counts(jaxpr)
    n_levels = len(amg.levels)
    assert c.get("_dia_smooth_call", 0) == 2 * n_levels
    assert c.get("_dia_smooth_restrict_call", 0) == 0
    assert c.get("_dia_prolong_smooth_call", 0) == 0
    assert c.get("_dia_coarse_tail_call", 0) == 0
    # structural fallback == knob off: force every hook to decline
    from amgx_tpu.amg.aggregation import AggregationAMGLevel
    old_r = AggregationAMGLevel.restrict_fused
    old_p = AggregationAMGLevel.prolongate_smooth
    try:
        AggregationAMGLevel.restrict_fused = lambda *a, **k: None
        AggregationAMGLevel.prolongate_smooth = lambda *a, **k: None
        amg2, jaxpr2 = _trace_cycle(", amg:cycle_fusion_tail_rows=0")
    finally:
        AggregationAMGLevel.restrict_fused = old_r
        AggregationAMGLevel.prolongate_smooth = old_p
    assert str(jaxpr2) == str(_trace_cycle(", amg:cycle_fusion=0")[1])


def test_classical_fused_cycle_matches_unfused():
    """Classical hierarchies now RIDE the fused hooks (ISSUE 12:
    weighted row-segment slabs — see tests/test_classical_fusion.py
    for the kernel-level proofs); this guards the integration from the
    aggregation suite's angle: the fused classical cycle solves to the
    same answer as the cycle_fusion=0 composition."""
    cfg = ("solver(s)=PCG, s:max_iters=40, s:tolerance=1e-7,"
           " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
           " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
           " amg:smoother=JACOBI_L1, amg:max_iters=1,"
           " amg:coarse_solver=DENSE_LU_SOLVER")
    A = gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        s1 = amgx.create_solver(Config.from_string(cfg))
        s1.setup(A)
        r1 = s1.solve(b)
    s0 = amgx.create_solver(Config.from_string(cfg
                                               + ", amg:cycle_fusion=0"))
    s0.setup(A)
    r0 = s0.solve(b)
    assert r1.converged and r0.converged
    assert abs(int(r1.iterations) - int(r0.iterations)) <= 1


# ---------------------------------------------------------------------------
# coarse tail: parity + shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cycle", ["V", "W", "F"])
def test_tail_cycle_matches_per_level_composition(cycle):
    """The VMEM-resident tail kernel reproduces the per-level fused
    composition (same hierarchy, tail disabled) to f32 accuracy for
    every fixed cycle shape."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    base = _CYCLE_CFG + f", amg:cycle={cycle}"
    with ps.force_pallas_interpret():
        s_tail = amgx.create_solver(Config.from_string(base))
        s_tail.setup(A)
        r_tail = s_tail.solve(b)
        s_lvl = amgx.create_solver(Config.from_string(
            base + ", amg:cycle_fusion_tail_rows=0"))
        s_lvl.setup(A)
        r_lvl = s_lvl.solve(b)
    assert r_tail.converged and r_lvl.converged
    assert abs(int(r_tail.iterations) - int(r_lvl.iterations)) <= 1
    assert _rel(r_tail.x, r_lvl.x) < 1e-4


def test_tail_respects_row_threshold():
    """cycle_fusion_tail_rows gates the tail entry level."""
    amg, jaxpr = _trace_cycle(", amg:cycle_fusion_tail_rows=0")
    c = _kernel_counts(jaxpr)
    assert c.get("_dia_coarse_tail_call", 0) == 0
    assert c.get("_dia_smooth_restrict_call", 0) == len(amg.levels)


def test_cheb_tail_and_transfers_end_to_end():
    """Flagship-shaped smoother (CHEBYSHEV_POLY, no dinv) through the
    fused cycle: converges to the unfused answer."""
    cfg = (_CYCLE_CFG.replace("amg:smoother=JACOBI_L1",
                              "amg:smoother=CHEBYSHEV_POLY,"
                              " amg:chebyshev_polynomial_order=2"))
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    ref = amgx.create_solver(Config.from_string(
        cfg + ", amg:cycle_fusion=0, amg:fused_smoother=0"))
    ref.setup(A)
    r0 = ref.solve(b)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        r1 = slv.solve(b)
    assert r1.converged
    assert abs(int(r1.iterations) - int(r0.iterations)) <= 1
    assert _rel(r1.x, r0.x) < 1e-4


# ---------------------------------------------------------------------------
# lifecycle: no-retrace, resetup, memoization
# ---------------------------------------------------------------------------


def test_fused_cycle_does_not_retrace():
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(6)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_CYCLE_CFG))
        slv.setup(A)
        r1 = slv.solve(jnp.asarray(rng.standard_normal(n), jnp.float32))
        assert len(slv._jit_cache) == 1
        r2 = slv.solve(jnp.asarray(rng.standard_normal(n), jnp.float32))
        assert len(slv._jit_cache) == 1, \
            "fused cycle retraced on a value-only change of b"
        assert r1.converged and r2.converged


def test_transfer_slabs_memoized_and_resetup_refreshes():
    """level_data() serves one TransferSlabs object per level build
    (structure-only payload); a structure-reuse resetup builds new
    level objects and fresh slabs, and the resetup solve still matches
    the unfused answer."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(
            _CYCLE_CFG + ", amg:structure_reuse_levels=-1"))
        slv.setup(A)
        lv0 = slv.preconditioner.amg.levels[0]
        x1 = lv0._transfer_slabs()
        assert x1 is not None
        assert lv0._transfer_slabs() is x1, "xfer slab memo broken"
        slv.solve(b)
        A2 = A.with_values(A.values * 2.0)
        slv.resetup(A2 if A2.initialized else A2.init())
        r2 = slv.solve(b)
    ref = amgx.create_solver(Config.from_string(
        _CYCLE_CFG + ", amg:cycle_fusion=0, amg:fused_smoother=0"))
    A2r = A.with_values(A.values * 2.0)
    ref.setup(A2r if A2r.initialized else A2r.init())
    r0 = ref.solve(b)
    assert r2.converged
    assert abs(int(r2.iterations) - int(r0.iterations)) <= 1
    assert _rel(r2.x, r0.x) < 1e-4


def test_value_resetup_keeps_fused_cycle_correct():
    """The one-dispatch value-only resetup (amg/value_resetup.py, the
    flagship/northstar production path: GEO + CHEBYSHEV_POLY +
    DENSE_LU) splices new coefficients under the fused cycle: the
    structure-only transfer slabs are reused, the coarse inverse
    refreshes from the new QR factors, and the resetup solve matches
    an unfused fresh setup."""
    cfg = (_CYCLE_CFG.replace("amg:smoother=JACOBI_L1",
                              "amg:smoother=CHEBYSHEV_POLY,"
                              " amg:chebyshev_polynomial_order=2")
           + ", amg:structure_reuse_levels=-1")
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(cfg))
        slv.setup(A)
        amg = slv.preconditioner.amg
        x1 = amg.levels[0]._transfer_slabs()
        slv.solve(b)
        A2 = A.with_values(A.values * 1.5)
        slv.resetup(A2 if A2.initialized else A2.init())
        assert amg._last_resetup_value_only, \
            "value-only resetup did not engage on the GEO/Cheb shape"
        assert amg.levels[0]._transfer_slabs() is x1, \
            "structure-only slabs rebuilt on a value-only resetup"
        r2 = slv.solve(b)
    ref = amgx.create_solver(Config.from_string(
        cfg + ", amg:cycle_fusion=0, amg:fused_smoother=0"))
    A2r = A.with_values(A.values * 1.5)
    ref.setup(A2r if A2r.initialized else A2r.init())
    r0 = ref.solve(b)
    assert r2.converged
    assert abs(int(r2.iterations) - int(r0.iterations)) <= 1
    assert _rel(r2.x, r0.x) < 1e-4


def test_solve_many_fused_cycle_parity():
    """solve_many drives the fused cycle under vmap: the custom_vmap
    rules must land in the slab forms and match per-system solves."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    n = A.num_rows
    rng = np.random.default_rng(8)
    Bs = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    with ps.force_pallas_interpret():
        slv = amgx.create_solver(Config.from_string(_CYCLE_CFG))
        slv.setup(A)
        res = slv.solve_many(Bs)
        singles = [slv.solve(Bs[i]).x for i in range(3)]
    for i in range(3):
        assert _rel(res.x[i], singles[i]) < 1e-5

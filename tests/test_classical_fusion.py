"""Classical-path cycle fusion (ISSUE 12 tentpole): the weighted
row-segment transfer slabs (`ops/smooth.py build_csr_transfer_slabs`),
the generalized restriction-epilogue / prolongation-prologue kernels
(`ops/pallas_spmv.py`, weighted ctab/cwt + multi-entry ptab/pwt), and
the classical `AMGLevel` fusion hooks consumed through the existing
`_fusion_caps` dispatch in `amg/cycles.py`.

Kernels run through the Pallas interpreter (force_pallas_interpret, the
CPU test path); what the chip's compiler accepts of them is in
tests/test_chip_compile.py.
Mirrors tests/test_cycle_fusion.py's aggregation proofs: kernel parity
f32 (interpret) and f64 (the XLA slab fallback in ops/batched.py — the
parity reference), the jaxpr HBM-pass proof (a smoothed classical DIA
level runs EXACTLY two fused kernels per cycle with zero standalone
SpMV/transfer primitives outside them), and the cycle_fusion=0 escape
hatch reproducing the unfused composition bit-for-bit."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import _census
import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
from amgx_tpu.amg.hierarchy import AMG
from amgx_tpu.ops import pallas_spmv as ps
from amgx_tpu.ops import smooth as fused
from amgx_tpu.ops.spmv import spmv

amgx.initialize()

# the benched classical shape: PMIS + truncated D2 (the reference's
# production settings) — short P rows, so the fused plans single-kernel
_AMG_CFG = ("algorithm=CLASSICAL, selector=PMIS, interpolator=D2,"
            " smoother=JACOBI_L1, coarse_solver=DENSE_LU_SOLVER,"
            " strength_threshold=0.25, interp_max_elements=4,"
            " max_row_sum=0.9, min_coarse_rows=16, max_levels=10")

_CYCLE_CFG = (
    "solver(s)=PCG, s:max_iters=40, s:tolerance=1e-7,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=CLASSICAL,"
    " amg:selector=PMIS, amg:interpolator=D2, amg:smoother=JACOBI_L1,"
    " amg:presweeps=2, amg:postsweeps=1, amg:max_iters=1,"
    " amg:strength_threshold=0.25, amg:interp_max_elements=4,"
    " amg:max_row_sum=0.9, amg:coarse_solver=DENSE_LU_SOLVER,"
    " amg:min_coarse_rows=16")


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


def _classical_level(n=10, dtype=jnp.float64, extra=""):
    """Finest classical level of a 7-pt Poisson hierarchy: DIA A plus
    real D2 interpolation P / R = P^T (the weighted-slab source)."""
    A = gallery.poisson("7pt", n, n, n, dtype=dtype).init()
    amg = AMG(Config.from_string(_AMG_CFG + extra)).setup(A)
    return amg.levels[0]


def _vectors(lv, dtype, seed=0):
    n = lv.A.num_rows
    nc = int(lv.P.num_cols)
    rng = np.random.default_rng(seed)
    b = jnp.asarray(rng.standard_normal(n), dtype)
    x = jnp.asarray(rng.standard_normal(n), dtype)
    xc = jnp.asarray(rng.standard_normal(nc), dtype)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), dtype)
    return b, x, xc, dinv


# ---------------------------------------------------------------------------
# slab build + XLA fallback (the f64 parity reference)
# ---------------------------------------------------------------------------


def test_csr_slab_fallback_parity_f64():
    """The weighted slab forms (what f64 and vmapped callers run)
    reproduce R @ r and x + P @ xc to f64 accuracy against the
    explicit transfer-operator SpMVs."""
    from amgx_tpu.ops.batched import prolong_corr_multi, restrict_multi
    lv = _classical_level()
    xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
    assert xfer is not None and xfer.cwt is not None \
        and xfer.ptab is not None
    n, nc = lv.A.num_rows, int(lv.P.num_cols)
    rng = np.random.default_rng(3)
    Rs = jnp.asarray(rng.standard_normal((3, n)))
    X = jnp.asarray(rng.standard_normal((3, n)))
    XC = jnp.asarray(rng.standard_normal((3, nc)))
    BC = restrict_multi(Rs, xfer)
    OUT = prolong_corr_multi(lv.A, X, XC, xfer)
    for i in range(3):
        assert _rel(BC[i], spmv(lv.R, Rs[i])) < 1e-12
        assert _rel(OUT[i], X[i] + spmv(lv.P, XC[i])) < 1e-12


def test_csr_slab_caps_decline():
    """A P/R row beyond the kernel child caps builds no slabs (the
    cycle then composes the explicit SpMVs — never a wrong answer)."""
    lv = _classical_level(n=8)
    old = ps.CSR_TRANSFER_MAX_CHILD
    try:
        ps.CSR_TRANSFER_MAX_CHILD = 1
        assert fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R) is None
    finally:
        ps.CSR_TRANSFER_MAX_CHILD = old


def test_smooth_restrict_dia_multi_weighted_f64():
    """The fused multi-RHS compose (smoother sweeps + weighted
    restriction) matches the unfused reference at 1e-12 — this is the
    slab route solve_many takes under vmap."""
    from amgx_tpu.ops.batched import (corr_smooth_dia_multi,
                                      smooth_restrict_dia_multi)
    lv = _classical_level()
    xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
    n, nc = lv.A.num_rows, int(lv.P.num_cols)
    rng = np.random.default_rng(5)
    B = jnp.asarray(rng.standard_normal((2, n)))
    X = jnp.asarray(rng.standard_normal((2, n)))
    XC = jnp.asarray(rng.standard_normal((2, nc)))
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n))
    taus = jnp.asarray(np.full(2, 0.85))
    XF, BCF = smooth_restrict_dia_multi(lv.A, B, X, taus, dinv, xfer)
    XF2 = corr_smooth_dia_multi(lv.A, B, X, XC, taus, dinv, xfer)
    for i in range(2):
        xr, rr = _ref_sweeps(lv.A, B[i], X[i], taus, dinv)
        assert _rel(XF[i], xr) < 1e-12
        assert _rel(BCF[i], spmv(lv.R, rr)) < 1e-12
        xr2, _ = _ref_sweeps(lv.A, B[i], X[i] + spmv(lv.P, XC[i]),
                             taus, dinv)
        assert _rel(XF2[i], xr2) < 1e-12


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("with_dinv", [True, False])
def test_weighted_restrict_epilogue_parity_f32(with_dinv):
    lv = _classical_level(dtype=jnp.float32)
    b, x, _, dinv = _vectors(lv, jnp.float32, seed=1)
    dinv = dinv if with_dinv else None
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    xr, rr = _ref_sweeps(lv.A, b, x, taus, dinv)
    bc_ref = spmv(lv.R, rr)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(lv.A, dinv)
        xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
        assert ps.dia_restrict_supported(lv.A, jnp.float32, 2, xfer)
        out = fused.fused_smooth_restrict(
            {"A": lv.A, "fused": slabs}, b, x, taus, xfer, dinv=dinv)
    assert out is not None
    assert _rel(out[0], xr) < 1e-6
    assert _rel(out[1], bc_ref) < 1e-6


@pytest.mark.parametrize("with_dinv", [True, False])
def test_weighted_prolong_prologue_parity_f32(with_dinv):
    lv = _classical_level(dtype=jnp.float32)
    b, x, xc, dinv = _vectors(lv, jnp.float32, seed=2)
    dinv = dinv if with_dinv else None
    taus = jnp.asarray(np.full(2, 0.85), jnp.float32)
    xr, _ = _ref_sweeps(lv.A, b, x + spmv(lv.P, xc), taus, dinv)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(lv.A, dinv)
        xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
        out = fused.fused_corr_smooth(
            {"A": lv.A, "fused": slabs}, b, x, xc, taus, xfer,
            dinv=dinv)
    assert out is not None
    assert _rel(out, xr) < 1e-6


@pytest.mark.slow
def test_weighted_transfer_parity_multiblock_and_chained():
    """Small VMEM budgets force the multi-block path (R rows straddling
    fine-block windows complete in the per-block combine) and the
    chained dispatch (plain fused chunks + the transfer chunk)."""
    lv = _classical_level(n=16, dtype=jnp.float32)
    b, x, xc, dinv = _vectors(lv, jnp.float32, seed=4)
    taus = jnp.asarray(np.full(3, 0.8), jnp.float32)
    xr, rr = _ref_sweeps(lv.A, b, x, taus, dinv)
    bc_ref = spmv(lv.R, rr)
    xr2, _ = _ref_sweeps(lv.A, b, x + spmv(lv.P, xc), taus, dinv)
    old = ps._SMOOTH_VMEM_BUDGET
    try:
        for budget in (1400 * 1024, 700 * 1024):
            ps._SMOOTH_VMEM_BUDGET = budget
            with ps.force_pallas_interpret():
                slabs = fused.build_fused_slabs(lv.A, dinv)
                xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
                data = {"A": lv.A, "fused": slabs}
                out = fused.fused_smooth_restrict(data, b, x, taus,
                                                  xfer, dinv=dinv)
                out2 = fused.fused_corr_smooth(data, b, x, xc, taus,
                                               xfer, dinv=dinv)
            if out is not None:
                assert _rel(out[0], xr) < 1e-6
                assert _rel(out[1], bc_ref) < 1e-6
            if out2 is not None:
                assert _rel(out2, xr2) < 1e-6
            assert out is not None or out2 is not None, \
                "both fused routes declined at this budget"
    finally:
        ps._SMOOTH_VMEM_BUDGET = old


def test_weighted_transfer_vmap_routes_to_slab():
    """Under jax.vmap (solve_many's shape) the fused transfer calls
    must land in the weighted multi-RHS slab forms and match
    per-system references — the single-RHS kernels have no batching
    rule."""
    lv = _classical_level(n=8, dtype=jnp.float32)
    n, nc = lv.A.num_rows, int(lv.P.num_cols)
    rng = np.random.default_rng(6)
    B = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    XC = jnp.asarray(rng.standard_normal((3, nc)), jnp.float32)
    dinv = jnp.asarray(1.0 / rng.uniform(4, 8, n), jnp.float32)
    taus = jnp.asarray(np.full(2, 0.9), jnp.float32)
    with ps.force_pallas_interpret():
        slabs = fused.build_fused_slabs(lv.A, dinv)
        xfer = fused.build_csr_transfer_slabs(lv.A, lv.P, lv.R)
        data = {"A": lv.A, "fused": slabs}
        XF, BCF = jax.vmap(
            lambda bb, xx: fused.fused_smooth_restrict(
                data, bb, xx, taus, xfer, dinv=dinv))(B, X)
        XF2 = jax.vmap(
            lambda bb, xx, xcc: fused.fused_corr_smooth(
                data, bb, xx, xcc, taus, xfer, dinv=dinv))(B, X, XC)
    for i in range(3):
        xr, rr = _ref_sweeps(lv.A, B[i], X[i], taus, dinv)
        assert _rel(XF[i], xr) < 1e-6
        assert _rel(BCF[i], spmv(lv.R, rr)) < 1e-6
        xr2, _ = _ref_sweeps(lv.A, B[i], X[i] + spmv(lv.P, XC[i]),
                             taus, dinv)
        assert _rel(XF2[i], xr2) < 1e-6


# ---------------------------------------------------------------------------
# cycle integration: jaxpr proof, escape hatch, solves
# ---------------------------------------------------------------------------


def _trace_cycle(extra_cfg="", n=12):
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


# shared census walk (amgx_tpu/telemetry/census.py via tests/_census.py)
_kernel_counts = _census.kernel_counts
_outer_prims = _census.outer_prims


def test_jaxpr_proof_classical_fused_kernel_budget():
    """HBM-pass proof (the ISSUE 12 acceptance gate): a smoothed
    classical DIA level runs EXACTLY two fused Pallas kernels per
    cycle — presmooth+weighted-restriction, weighted-prolongation+
    postsmooth — with zero standalone dia/SWELL SpMV kernels and zero
    standalone transfer primitives (gather/scatter/pad) outside them,
    exactly like the aggregation proof in tests/test_cycle_fusion.py."""
    amg, jaxpr = _trace_cycle(", amg:max_levels=2")
    assert len(amg.levels) == 1
    assert amg.levels[0].A.dia_vals is not None
    c = _kernel_counts(jaxpr)
    assert c.get("_dia_smooth_restrict_call", 0) == 1, c
    assert c.get("_dia_prolong_smooth_call", 0) == 1, c
    assert c.get("_dia_smooth_call", 0) == 0, c
    assert c.get("_dia_spmv_call", 0) == 0, c
    assert c.get("_swell_spmv_call", 0) == 0, c
    assert c.get("_swell_smooth_call", 0) == 0, c
    outer = set(_outer_prims(jaxpr))
    assert not outer & {"pad", "gather", "scatter-add", "scatter"}, \
        sorted(outer & {"pad", "gather", "scatter-add", "scatter"})


def test_cycle_fusion_off_restores_composition():
    """cycle_fusion=0 must trace the unfused classical composition
    (fused smoother kernels + standalone SWELL transfer SpMVs, zero
    transfer kernels) — and the same jaxpr as the fusion path's
    structural fallback (hooks declining), proving the escape hatch IS
    the old code path bit-for-bit."""
    amg, jaxpr = _trace_cycle(", amg:max_levels=2, amg:cycle_fusion=0")
    c = _kernel_counts(jaxpr)
    assert c.get("_dia_smooth_restrict_call", 0) == 0, c
    assert c.get("_dia_prolong_smooth_call", 0) == 0, c
    assert c.get("_swell_spmv_call", 0) == 2, c   # restrict + prolong
    from amgx_tpu.amg.classical import ClassicalAMGLevel
    old_r = ClassicalAMGLevel.restrict_fused
    old_p = ClassicalAMGLevel.prolongate_smooth
    try:
        ClassicalAMGLevel.restrict_fused = lambda *a, **k: None
        ClassicalAMGLevel.prolongate_smooth = lambda *a, **k: None
        _, jaxpr2 = _trace_cycle(", amg:max_levels=2")
    finally:
        ClassicalAMGLevel.restrict_fused = old_r
        ClassicalAMGLevel.prolongate_smooth = old_p
    assert str(jaxpr2) == str(jaxpr)


def test_classical_fused_solve_parity():
    """Fused-vs-unfused full classical solve: same iterations (+-1),
    matching answers, through a DEEP hierarchy (the fused DIA fine
    level above unfused SWELL coarse levels)."""
    A = gallery.poisson("7pt", 12, 12, 12, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    with ps.force_pallas_interpret():
        s1 = amgx.create_solver(Config.from_string(_CYCLE_CFG))
        s1.setup(A)
        r1 = s1.solve(b)
    s0 = amgx.create_solver(Config.from_string(
        _CYCLE_CFG + ", amg:cycle_fusion=0, amg:fused_smoother=0"))
    s0.setup(A)
    r0 = s0.solve(b)
    assert r1.converged and r0.converged
    assert abs(int(r1.iterations) - int(r0.iterations)) <= 1
    assert _rel(r1.x, r0.x) < 1e-4


def test_supports_fusion_gates():
    """The capability surface: slabs present -> advertises both hooks;
    no slabs (cycle_fusion=0) -> advertises nothing and the data
    carries no xfer leaf."""
    lv = _classical_level(n=8, dtype=jnp.float32)
    with ps.force_pallas_interpret():
        amg = AMG(Config.from_string(_AMG_CFG)).setup(
            gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init())
        d = amg.levels[0].level_data()
        assert "xfer" in d
        assert set(amg.levels[0].supports_fusion(d)) == \
            {"restrict", "prolongate"}
        amg0 = AMG(Config.from_string(
            _AMG_CFG + ", cycle_fusion=0")).setup(
            gallery.poisson("7pt", 8, 8, 8, dtype=jnp.float32).init())
        d0 = amg0.levels[0].level_data()
        assert "xfer" not in d0
        assert amg0.levels[0].supports_fusion(d0) == ()


@pytest.mark.slow
def test_structure_resetup_keeps_slabs_and_solves():
    """structure_reuse_levels=-1: the reused classical levels carry
    their weighted slabs over (P/R are kept, values included), and the
    resetup solve matches an unfused fresh setup on the new
    coefficients."""
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
        lv0b = slv.preconditioner.amg.levels[0]
        assert lv0b._transfer_slabs() is x1, \
            "structure reuse rebuilt the kept P/R's slabs"
        r2 = slv.solve(b)
    ref = amgx.create_solver(Config.from_string(
        _CYCLE_CFG + ", amg:cycle_fusion=0, amg:fused_smoother=0"))
    A2r = A.with_values(A.values * 2.0)
    ref.setup(A2r if A2r.initialized else A2r.init())
    r0 = ref.solve(b)
    assert r2.converged
    assert abs(int(r2.iterations) - int(r0.iterations)) <= 1
    assert _rel(r2.x, r0.x) < 1e-4

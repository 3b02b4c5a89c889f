"""Matrix-free GEO levels (ISSUE 18 tentpole): constant-coefficient
stencil detection (ops/stencil.py), the coeffs-mode fused kernels
(pallas_spmv's SMEM-scalar operand form, via force_pallas_interpret on
the CPU rig), the f64/XLA slab-fallback route, hierarchy routing
(`matrix_free=auto|0|1`, level_data forms), the
jaxpr census (NO value-slab operand on matrix-free levels;
`matrix_free=0` jaxpr-identical to the default slab build), the
value-resetup coefficient refresh, GeoRapPlan.coarse_coeffs, and the
serving-cache footprint of a matrix-free hierarchy.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu import gallery
from amgx_tpu.config import Config
import amgx_tpu.ops.pallas_spmv as ps
import amgx_tpu.ops.stencil as stencil
from amgx_tpu.ops.spmv import spmv
from amgx_tpu.solvers.relaxation import safe_recip, l1_strengthened_diag

import _census

amgx.initialize()

_GEO_CORE = (
    "solver=FGMRES, max_iters=40, monitor_residual=1, tolerance=1e-8,"
    " gmres_n_restart=20, convergence=RELATIVE_INI, norm=L2,"
    " preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=GEO, amg:max_iters=1, amg:max_levels=10,"
    " amg:min_coarse_rows=16,")


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _ref_sweeps(A, dinv, taus, b, x, with_residual=False):
    for t in np.asarray(taus):
        upd = jnp.asarray(t, x.dtype) * (b - spmv(A, x))
        if dinv is not None:
            upd = (upd * dinv).astype(x.dtype)
        x = x + upd
    if with_residual:
        return x, b - spmv(A, x)
    return x


def _amg_of(slv):
    x = slv
    while not hasattr(x, "amg"):
        x = x.preconditioner
    return x.amg


def _scaled(A, f):
    def s(v):
        return None if v is None else v * f
    return dataclasses.replace(
        A, values=A.values * f, dia_vals=s(A.dia_vals),
        ell_vals=s(A.ell_vals), swell_vals=s(A.swell_vals),
        diag=s(A.diag))


# ---------------------------------------------------------------------------
# detection
# ---------------------------------------------------------------------------


class TestDetection:
    def test_detects_constant_poisson(self):
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float32).init()
        st = stencil.detect_stencil(A, dinv_mode="l1")
        assert st is not None
        assert st.offsets == tuple(int(d) for d in A.dia_offsets)
        assert st.shape == (12, 12, 12)
        c = np.asarray(st.coeffs)
        ctr = st.offsets.index(0)
        assert c[ctr] == 6.0
        assert all(c[t] == -1.0 for t in range(len(c)) if t != ctr)

    def test_rejects_variable_coefficients(self):
        A = gallery.poisson("7pt", 10, 10, 10, dtype=np.float32).init()
        vals = np.array(A.dia_vals)
        vals[0, 1, 3] *= 1.5         # one in-grid entry off the constant
        Av = dataclasses.replace(A, dia_vals=jnp.asarray(vals))
        assert stencil.detect_stencil(Av) is None

    def test_rejects_no_grid_annotation(self):
        A = gallery.poisson("7pt", 10, 10, 10, dtype=np.float32).init()
        Ag = dataclasses.replace(A, grid_shape=None)
        assert stencil.detect_stencil(Ag) is None

    def test_stencil_matrix_roundtrip(self):
        """stencil_matrix rebuilds the exact value slab the detector
        consumed — the materialization escape every generic consumer
        routes through (level_operator)."""
        A = gallery.poisson("7pt", 10, 10, 10, dtype=np.float32).init()
        st = stencil.detect_stencil(A)
        M = stencil.stencil_matrix(stencil.mf_slim(A), st)
        np.testing.assert_array_equal(np.asarray(M.dia_vals),
                                      np.asarray(A.dia_vals))
        ld = {"A": stencil.mf_slim(A), "stencil": st}
        np.testing.assert_array_equal(
            np.asarray(stencil.level_operator(ld).dia_vals),
            np.asarray(A.dia_vals))


# ---------------------------------------------------------------------------
# kernel parity (coeffs mode vs slab reference, interpret mode)
# ---------------------------------------------------------------------------


class TestKernelParity:
    @pytest.mark.parametrize("n_steps", [1, 3, 9])
    def test_smooth_parity_f32(self, n_steps):
        A = gallery.poisson("7pt", 16, 16, 16, dtype=np.float32).init()
        st = stencil.detect_stencil(A, dinv_mode="l1")
        rng = np.random.default_rng(0)
        n = A.num_rows
        b = jnp.asarray(rng.standard_normal(n), jnp.float32)
        x0 = jnp.asarray(rng.standard_normal(n), jnp.float32)
        dinv = jnp.asarray(safe_recip(np.asarray(
            l1_strengthened_diag(A))), jnp.float32)
        taus = jnp.full((n_steps,), 0.8, jnp.float32)
        ref_x, ref_r = _ref_sweeps(A, dinv, taus, b, x0, True)
        with ps.force_pallas_interpret():
            mx, mr = stencil.stencil_fused_smooth(
                st, taus, b, x0, with_residual=True)
        assert _rel(mx, ref_x) < 1e-6
        assert _rel(mr, ref_r) < 1e-6

    def test_smooth_parity_jacobi_dinv(self):
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float32).init()
        st = stencil.detect_stencil(A, dinv_mode="jacobi")
        rng = np.random.default_rng(1)
        n = A.num_rows
        b = jnp.asarray(rng.standard_normal(n), jnp.float32)
        x0 = jnp.asarray(rng.standard_normal(n), jnp.float32)
        dinv = jnp.asarray(safe_recip(np.asarray(A.diagonal())),
                           jnp.float32)
        taus = jnp.full((2,), 0.8, jnp.float32)
        ref_x = _ref_sweeps(A, dinv, taus, b, x0)
        with ps.force_pallas_interpret():
            mx = stencil.stencil_fused_smooth(st, taus, b, x0,
                                              with_residual=False)
        assert _rel(mx, ref_x) < 1e-6

    def test_smooth_parity_bf16(self):
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float32).init()
        st = stencil.detect_stencil(A, dinv_mode="l1")
        rng = np.random.default_rng(2)
        n = A.num_rows
        b32 = jnp.asarray(rng.standard_normal(n), jnp.float32)
        x32 = jnp.asarray(rng.standard_normal(n), jnp.float32)
        dinv = jnp.asarray(safe_recip(np.asarray(
            l1_strengthened_diag(A))), jnp.float32)
        taus = jnp.full((2,), 0.8, jnp.float32)
        ref_x = _ref_sweeps(A, dinv, taus, b32, x32)
        with ps.force_pallas_interpret():
            mx = stencil.stencil_fused_smooth(
                st, taus.astype(jnp.bfloat16), b32.astype(jnp.bfloat16),
                x32.astype(jnp.bfloat16), with_residual=False)
        assert mx.dtype == jnp.bfloat16
        assert _rel(mx.astype(jnp.float32), ref_x) < 2e-2

    def test_chained_blocks_under_tight_budget(self):
        """A 9-sweep schedule is longer than SMOOTH_MAX_APPS: the
        smoother takes the XLA compose and still matches the
        reference."""
        nn = 10
        A = gallery.poisson("7pt", nn, nn, nn, dtype=np.float32).init()
        st = stencil.detect_stencil(A, dinv_mode="l1")
        rng = np.random.default_rng(7)
        n = A.num_rows
        b = jnp.asarray(rng.standard_normal(n), jnp.float32)
        x0 = jnp.asarray(rng.standard_normal(n), jnp.float32)
        dinv = jnp.asarray(safe_recip(np.asarray(
            l1_strengthened_diag(A))), jnp.float32)
        taus9 = jnp.full((9,), 0.8, jnp.float32)
        xr = _ref_sweeps(A, dinv, taus9, b, x0)
        old = ps._SMOOTH_VMEM_BUDGET
        try:
            ps._SMOOTH_VMEM_BUDGET = 300 * 1024
            with ps.force_pallas_interpret():
                mx = stencil.stencil_fused_smooth(
                    st, taus9, b, x0, with_residual=False)
        finally:
            ps._SMOOTH_VMEM_BUDGET = old
        assert _rel(mx, xr) < 1e-6

    def test_f64_slab_fallback_parity(self):
        """f64 is outside SMOOTH_DTYPES: the dispatch must compose the
        XLA masked-coefficient form and agree with the slab reference
        to f64 roundoff."""
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float64).init()
        st = stencil.detect_stencil(A, dinv_mode="l1")
        rng = np.random.default_rng(3)
        n = A.num_rows
        b = jnp.asarray(rng.standard_normal(n))
        x0 = jnp.asarray(rng.standard_normal(n))
        dinv = safe_recip(l1_strengthened_diag(A))
        taus = jnp.full((3,), 0.8)
        ref_x, ref_r = _ref_sweeps(A, dinv, taus, b, x0, True)
        mx, mr = stencil.stencil_fused_smooth(st, taus, b, x0,
                                              with_residual=True)
        assert _rel(mx, ref_x) < 1e-12
        assert _rel(mr, ref_r) < 1e-12


# ---------------------------------------------------------------------------
# hierarchy routing + end-to-end parity
# ---------------------------------------------------------------------------


_SMOOTHERS = {
    "bj": (" amg:smoother=BLOCK_JACOBI, amg:relaxation_factor=0.75,"
           " amg:presweeps=0, amg:postsweeps=3, amg:cycle=V"),
    "l1": (" amg:smoother=JACOBI_L1, amg:relaxation_factor=0.75,"
           " amg:presweeps=1, amg:postsweeps=2, amg:cycle=V"),
    "cheb": (" amg:smoother=CHEBYSHEV_POLY,"
             " amg:chebyshev_polynomial_order=4,"
             " amg:presweeps=1, amg:postsweeps=1, amg:cycle=V"),
}


class TestRouting:
    @pytest.mark.parametrize("sm", sorted(_SMOOTHERS))
    def test_e2e_solve_parity(self, sm):
        A = gallery.poisson("7pt", 16, 16, 16, dtype=np.float32).init()
        b = jnp.ones(A.num_rows, jnp.float32)
        xs = {}
        for mf in ("0", "1"):
            slv = amgx.create_solver(Config.from_string(
                _GEO_CORE + _SMOOTHERS[sm] + ", amg:matrix_free=" + mf))
            slv.setup(A)
            amg = _amg_of(slv)
            nmf = sum(getattr(lv.smoother, "_mf_stencil", None)
                      is not None for lv in amg.levels)
            if mf == "1":
                assert nmf == len(amg.levels)
                for ld in amg.solve_data()["levels"]:
                    assert "stencil" in ld
                    assert ld["A"].dia_vals is None
            else:
                assert nmf == 0
            res = slv.solve(b)
            assert res.converged
            xs[mf] = res.x
        assert _rel(xs["1"], xs["0"]) < 1e-4

    def test_e2e_solve_parity_f64(self):
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float64).init()
        b = jnp.ones(A.num_rows)
        xs = {}
        for mf in ("0", "1"):
            slv = amgx.create_solver(Config.from_string(
                _GEO_CORE + _SMOOTHERS["l1"]
                + ", amg:matrix_free=" + mf))
            slv.setup(A)
            res = slv.solve(b)
            assert res.converged
            xs[mf] = res.x
        assert _rel(xs["1"], xs["0"]) < 1e-10

    def test_auto_stays_off_on_cpu(self):
        """The default `auto` routes matrix-free only on a real TPU
        backend — the CPU tier-1 build must stay bit-identical to the
        slab path, so no stencil may install here."""
        A = gallery.poisson("7pt", 10, 10, 10, dtype=np.float32).init()
        slv = amgx.create_solver(Config.from_string(
            _GEO_CORE + _SMOOTHERS["bj"]))
        slv.setup(A)
        amg = _amg_of(slv)
        assert all(getattr(lv.smoother, "_mf_stencil", None) is None
                   for lv in amg.levels)
        assert all("stencil" not in ld
                   for ld in amg.solve_data()["levels"])

    def test_variable_coefficients_route_to_slabs(self):
        """matrix_free=1 with a variable-coefficient operator must
        keep every level on the slab path and still solve."""
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float32).init()
        n = A.num_rows
        d = np.ones(n, np.float32)
        d[n // 3] = 1.5
        k = len(A.dia_offsets)
        dv = np.asarray(A.dia_vals).reshape(k, -1).copy()
        dv[:, :n] *= d
        Av = dataclasses.replace(
            A, values=A.values * jnp.asarray(d)[A.row_ids],
            dia_vals=jnp.asarray(dv).reshape(A.dia_vals.shape),
            diag=None if A.diag is None else A.diag * jnp.asarray(d))
        slv = amgx.create_solver(Config.from_string(
            _GEO_CORE + _SMOOTHERS["l1"] + ", amg:matrix_free=1"))
        slv.setup(Av)
        amg = _amg_of(slv)
        assert getattr(amg.levels[0].smoother, "_mf_stencil",
                       None) is None
        assert all("stencil" not in ld
                   for ld in amg.solve_data()["levels"])
        res = slv.solve(jnp.ones(n, jnp.float32))
        assert res.converged


# ---------------------------------------------------------------------------
# jaxpr census
# ---------------------------------------------------------------------------


def _trace_cycle(extra="", n=12):
    A = gallery.poisson("7pt", n, n, n, dtype=jnp.float32).init()
    b = jnp.ones(A.num_rows, jnp.float32)
    slv = amgx.create_solver(Config.from_string(
        _GEO_CORE + _SMOOTHERS["l1"] + extra))
    slv.setup(A)
    amg = _amg_of(slv)
    d = amg.solve_data()
    jaxpr = jax.make_jaxpr(lambda bb, xx: amg.cycle(d, bb, xx))(
        b, jnp.zeros_like(b))
    return amg, jaxpr


# shared census helper (tests/_census.py)
def _slab_consts(jaxpr, k):
    return _census.slab_consts(jaxpr, k, lanes=ps.LANES)


class TestJaxprCensus:
    def test_no_value_slab_operand_on_matrix_free_levels(self):
        amg0, j0 = _trace_cycle(", amg:matrix_free=0")
        amg1, j1 = _trace_cycle(", amg:matrix_free=1")
        k = len(amg0.levels[0].A.dia_offsets)
        assert _slab_consts(j0, k), "slab build lost its DIA operand?"
        assert not _slab_consts(j1, k), _slab_consts(j1, k)
        # and the whole closed-over constant footprint shrinks
        by = lambda j: sum(int(np.size(c) * c.dtype.itemsize)
                           for c in j.consts if np.ndim(c))
        assert by(j1) < by(j0)

    def test_matrix_free_0_is_jaxpr_identical_to_default(self):
        """The escape hatch: matrix_free=0 must be THE slab build —
        same jaxpr text as the default (auto routes off on CPU)."""
        _, j_def = _trace_cycle()
        _, j_off = _trace_cycle(", amg:matrix_free=0")
        assert str(j_off) == str(j_def)

    def test_interpret_cycle_keeps_fused_kernels(self):
        """Under the Pallas runtime the matrix-free cycle runs the
        slab cycle's kernel set (the coeffs mode replaces the operand,
        not the kernel), and solves to the same answer."""
        A = gallery.poisson("7pt", 12, 12, 12, dtype=np.float32).init()
        b = jnp.ones(A.num_rows, jnp.float32)
        xs, kernels = {}, {}
        for mf in ("0", "1"):
            with ps.force_pallas_interpret():
                slv = amgx.create_solver(Config.from_string(
                    _GEO_CORE + _SMOOTHERS["l1"]
                    + ", amg:matrix_free=" + mf))
                slv.setup(A)
                amg = _amg_of(slv)
                d = amg.solve_data()
                jaxpr = jax.make_jaxpr(
                    lambda bb, xx: amg.cycle(d, bb, xx))(
                        b, jnp.zeros_like(b))
                res = slv.solve(b)
            assert res.converged
            xs[mf] = res.x
            kernels[mf] = set(
                nm for nm in _census.kernel_names(jaxpr)
                if nm.startswith("_dia_"))
        assert kernels["1"], kernels
        assert kernels["1"] == kernels["0"], kernels
        assert _rel(xs["1"], xs["0"]) < 1e-5


# ---------------------------------------------------------------------------
# value resetup + coarse coefficients
# ---------------------------------------------------------------------------


class TestResetup:
    def test_value_resetup_refreshes_coefficients(self):
        from amgx_tpu.presets import FLAGSHIP
        A = gallery.poisson("7pt", 16, 16, 16).init()
        slv = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", amg:structure_reuse_levels=-1,"
            " amg:matrix_free=1"))
        slv.setup(A)
        amg = _amg_of(slv)
        assert all(lv.smoother._mf_stencil is not None
                   for lv in amg.levels)
        c0 = [np.asarray(lv.smoother._mf_stencil.coeffs)
              for lv in amg.levels]
        slv.resetup(_scaled(A, 2.0))
        assert amg._last_resetup_value_only
        for lv, c in zip(amg.levels, c0):
            np.testing.assert_allclose(
                np.asarray(lv.smoother._mf_stencil.coeffs), 2.0 * c,
                rtol=1e-6)
        # and the spliced hierarchy answers exactly like a fresh setup
        b = jnp.ones(A.num_rows, jnp.float32)
        ref = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", amg:matrix_free=1"))
        ref.setup(_scaled(A, 2.0).init())
        assert _rel(slv.solve(b).x, ref.solve(b).x) < 1e-6

    def test_value_resetup_declines_non_constant_values(self):
        """New values that break the constant-stencil invariant must
        fall back to the generic resetup, which re-detects and drops
        the stencils — never serve stale coefficients."""
        from amgx_tpu.presets import FLAGSHIP
        A = gallery.poisson("7pt", 16, 16, 16).init()
        slv = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", amg:structure_reuse_levels=-1,"
            " amg:matrix_free=1"))
        slv.setup(A)
        amg = _amg_of(slv)
        n = A.num_rows
        d = np.ones(n, np.float32)
        d[n // 2] = 1.5
        k = len(A.dia_offsets)
        dv = np.asarray(A.dia_vals).reshape(k, -1).copy()
        dv[:, :n] *= d
        An = dataclasses.replace(
            A, values=A.values * jnp.asarray(d)[A.row_ids],
            dia_vals=jnp.asarray(dv).reshape(A.dia_vals.shape),
            diag=None if A.diag is None else A.diag * jnp.asarray(d))
        slv.resetup(An)
        assert not amg._last_resetup_value_only
        assert all(getattr(lv.smoother, "_mf_stencil", None) is None
                   for lv in amg.levels)
        b = jnp.ones(n, jnp.float32)
        res = slv.solve(b)
        rr = _rel(np.asarray(spmv(An.init(), res.x)), np.asarray(b))
        assert rr < 1e-4


class TestCoarseCoeffs:
    def test_matches_detected_coarse_stencil(self):
        from amgx_tpu.presets import FLAGSHIP
        A = gallery.poisson("7pt", 16, 16, 16).init()
        slv = amgx.create_solver(Config.from_string(
            FLAGSHIP + ", amg:matrix_free=1"))
        slv.setup(A)
        amg = _amg_of(slv)
        gp = amg.levels[0]._geo_plan_memo[0]
        derived = gp.coarse_coeffs(
            amg.levels[0].smoother._mf_stencil.coeffs)
        assert derived is not None
        np.testing.assert_allclose(
            np.asarray(derived),
            np.asarray(amg.levels[1].smoother._mf_stencil.coeffs),
            rtol=1e-6)

    def test_odd_extent_returns_none(self):
        from amgx_tpu.amg.aggregation.galerkin import GeoRapPlan
        shifts = ((0, 0, 0), (1, 0, 0), (-1, 0, 0))
        offsets = (0, 1, -1)
        plan = GeoRapPlan(offsets, shifts, (5, 4, 4), (0, 1, 2),
                          (3, 2, 2))
        assert plan.coarse_coeffs(jnp.ones(3, jnp.float32)) is None


# ---------------------------------------------------------------------------
# serving-cache footprint (satellite: solve_data_bytes)
# ---------------------------------------------------------------------------


def test_serving_cache_counts_matrix_free_payload_tiny():
    """A matrix-free bucket's byte estimate must be the stencil's true
    O(k) payload, not a phantom slab: the estimate drops by at least
    the fine level's DIA slab size versus the slab twin."""
    from amgx_tpu.serving.cache import solve_data_bytes
    A = gallery.poisson("7pt", 16, 16, 16, dtype=np.float32).init()
    sizes = {}
    for mf in ("0", "1"):
        slv = amgx.create_solver(Config.from_string(
            _GEO_CORE + _SMOOTHERS["l1"] + ", amg:matrix_free=" + mf))
        slv.setup(A)
        sizes[mf] = solve_data_bytes(_amg_of(slv).solve_data())
    slab_bytes = int(np.asarray(A.dia_vals).nbytes)
    assert sizes["1"] <= sizes["0"] - slab_bytes, (sizes, slab_bytes)
    st = stencil.detect_stencil(A)
    assert solve_data_bytes({"stencil": st}) == \
        int(np.asarray(st.coeffs).nbytes)

"""Krylov solvers: CG / PCG / PCGF / BiCGStab / PBiCGStab / Chebyshev.

Analogs of src/solvers/cg_solver.cu, pcg_solver.cu, pcgf_solver.cu,
bicgstab_solver.cu, pbicgstab_solver.cu, cheb_solver.cu. Each iteration
is a pure function over a dict state; the base driver compiles the whole
iteration loop (SpMV + reductions + preconditioner application) into one
XLA program, so dot products stay on device and distributed runs finish
reductions with psum instead of MPI_Allreduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import registry
from ..ops import blas
from ..ops.spmv import spmv, spmv_pdot, spmv_ddot
from .base import Solver


def _safe_div(a, b):
    return a / jnp.where(b == 0, 1.0, b) * (b != 0)


def _ldot(a, b):
    """LOCAL dot in f32+ accumulation (the epilogue dtype of the fused
    shell kernels); fused iterations finish their LOCAL scalars with
    ONE packed collective (blas.psum_bundle) instead of per-dot psums."""
    cdt = jnp.promote_types(a.dtype, jnp.float32)
    return jnp.vdot(a.astype(cdt), b.astype(cdt))


class _KrylovBase(Solver):
    def __init__(self, cfg, scope="default", name="?"):
        super().__init__(cfg, scope, name)
        # Krylov shell fusion (ops/spmv.spmv_pdot / blas.cg_update):
        # 0 restores the unfused SpMV + BLAS-1 composition bit-for-bit
        self.krylov_fusion = bool(int(cfg.get("krylov_fusion", scope)))

    def _precond(self, data, r):
        if self.preconditioner is not None:
            return self.preconditioner.apply(data["precond"], r)
        return r

    def _precond_dot(self, data, r):
        """(z, LOCAL r.z); identity preconditioner gives (r, r.r)."""
        z = self._precond(data, r)
        return z, _ldot(r, z)

    def _l2_scalar_norm(self) -> bool:
        """True when the driver's monitored norm is the plain scalar L2
        — the only shape a solver-maintained r.r scalar can stand in
        for (internal_res_norm)."""
        if self.norm_type.upper() != "L2":
            return False
        bs = self.A.block_dimx if self.A is not None else 1
        return bs <= 1 or self.use_scalar_norm


@registry.solvers.register("CG")
class CGSolver(_KrylovBase):
    """Unpreconditioned conjugate gradients (cg_solver.cu)."""

    def solve_init(self, data, b, x, r):
        if self.krylov_fusion:
            # fused state seeds the direction-update PROLOGUE: the
            # first iteration's p' = z + beta p with z=r, beta=0, p=0
            # reproduces the unfused p0 = r inside the SpMV kernel
            (rz,) = blas.psum_bundle((_ldot(r, r),))
            return {"p": jnp.zeros_like(r),
                    "beta": jnp.zeros((), rz.dtype), "rz": rz,
                    **self._guard_init()}
        return {"p": r, "rz": blas.dot(r, r), **self._guard_init()}

    def solve_iteration(self, data, b, st):
        if self.krylov_fusion:
            return self._fused_iteration(data, st)
        A = data["A"]
        x, r, p, rz = st["x"], st["r"], st["p"], st["rz"]
        Ap = spmv(A, p)
        pAp = blas.dot(p, Ap)
        alpha = _safe_div(rz, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        rz_new = blas.dot(r, r)
        beta = _safe_div(rz_new, rz)
        p = r + beta * p
        out = {**st, "x": x, "r": r, "p": p, "rz": rz_new}
        if self.health_guards:
            # p.Ap <= 0: the matrix is not SPD on this Krylov space —
            # a CG breakdown (p == 0 from exact convergence also lands
            # here, but the CONVERGED check wins in the driver)
            out["breakdown"] = pAp <= 0
        return out

    def _fused_iteration(self, data, st):
        """Two single-pass kernels per iteration: (p', Ap', p'.Ap')
        with the direction update folded in as a prologue, then
        (x', r', r'.r') — every n-vector is read once per kernel and
        the iteration's scalars psum in at most two packed bundles."""
        A = data["A"]
        x, r, rz = st["x"], st["r"], st["rz"]
        p, Ap, pAp = spmv_pdot(A, st["p"], r, st["beta"])
        (pAp,) = blas.psum_bundle((pAp,))
        alpha = _safe_div(rz, pAp)
        x, r, rr = blas.cg_update(x, p, r, Ap, alpha)
        (rz_new,) = blas.psum_bundle((rr,))
        beta = _safe_div(rz_new, rz)
        out = {**st, "x": x, "r": r, "p": p, "rz": rz_new,
               "beta": beta}
        if self.health_guards:
            out["breakdown"] = pAp <= 0
        return out

    def internal_res_norm(self, state):
        # CG's rz IS r.r — the monitored scalar L2 norm squared — on
        # BOTH routes, so the driver's standalone blas.norm(r)
        # full-vector pass is dead code under the monitor
        if not self._l2_scalar_norm():
            return None
        return jnp.sqrt(state["rz"])


@registry.solvers.register("PCG")
class PCGSolver(_KrylovBase):
    """Preconditioned CG (pcg_solver.cu)."""

    uses_preconditioner = True

    def solve_init(self, data, b, x, r):
        if self.krylov_fusion:
            z, rz_l = self._precond_dot(data, r)
            rr, rz = blas.psum_bundle((_ldot(r, r), rz_l))
            return {"p": jnp.zeros_like(r), "z": z,
                    "beta": jnp.zeros((), rz.dtype), "rz": rz,
                    "rr": rr, **self._guard_init()}
        z = self._precond(data, r)
        return {"p": z, "z": z, "rz": blas.dot(r, z),
                **self._guard_init()}

    def solve_iteration(self, data, b, st):
        if self.krylov_fusion:
            return self._fused_iteration(data, st)
        A = data["A"]
        x, r, p, rz = st["x"], st["r"], st["p"], st["rz"]
        Ap = spmv(A, p)
        pAp = blas.dot(p, Ap)
        alpha = _safe_div(rz, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        z = self._precond(data, r)
        rz_new = blas.dot(r, z)
        beta = _safe_div(rz_new, rz)
        p = z + beta * p
        out = {**st, "x": x, "r": r, "p": p, "z": z, "rz": rz_new}
        if self.health_guards:
            out["breakdown"] = pAp <= 0
        return out

    def _fused_iteration(self, data, st):
        """Fused-hierarchy PCG iteration: the p-update+SpMV+p.Ap
        kernel and the x/r-update+r.r kernel; r.z is the one explicit
        reduction, and the post-alpha scalars (r.r, r.z) share ONE
        packed psum."""
        A = data["A"]
        x, r, rz = st["x"], st["r"], st["rz"]
        p, Ap, pAp = spmv_pdot(A, st["p"], st["z"], st["beta"])
        (pAp,) = blas.psum_bundle((pAp,))
        alpha = _safe_div(rz, pAp)
        x, r, rr = blas.cg_update(x, p, r, Ap, alpha)
        z, rz_l = self._precond_dot(data, r)
        rr, rz_new = blas.psum_bundle((rr, rz_l))
        beta = _safe_div(rz_new, rz)
        out = {**st, "x": x, "r": r, "p": p, "z": z, "rz": rz_new,
               "rr": rr, "beta": beta}
        if self.health_guards:
            out["breakdown"] = pAp <= 0
        return out

    def internal_res_norm(self, state):
        # the fused route's r.r exits the x/r-update kernel's epilogue
        # — the monitor's norm costs zero extra passes
        if "rr" not in state or not self._l2_scalar_norm():
            return None
        return jnp.sqrt(state["rr"])


@registry.solvers.register("PCGF")
class PCGFSolver(_KrylovBase):
    """Flexible PCG (pcgf_solver.cu): Polak-Ribiere beta so the
    preconditioner may vary between iterations."""

    uses_preconditioner = True

    def solve_init(self, data, b, x, r):
        if self.krylov_fusion:
            z, rz_l = self._precond_dot(data, r)
            rr, rz = blas.psum_bundle((_ldot(r, r), rz_l))
            return {"p": jnp.zeros_like(r), "z": z,
                    "beta": jnp.zeros((), rz.dtype), "rz": rz,
                    "rr": rr, **self._guard_init()}
        z = self._precond(data, r)
        return {"p": z, "z": z, "r_old": r, "rz": blas.dot(r, z),
                **self._guard_init()}

    def solve_iteration(self, data, b, st):
        if self.krylov_fusion:
            return self._fused_iteration(data, st)
        A = data["A"]
        x, r, p, rz = st["x"], st["r"], st["p"], st["rz"]
        Ap = spmv(A, p)
        pAp = blas.dot(p, Ap)
        alpha = _safe_div(rz, pAp)
        x = x + alpha * p
        r_new = r - alpha * Ap
        z = self._precond(data, r_new)
        # flexible beta: <z, r_new - r> / <r, z_old-ish rz>
        rz_new = blas.dot(r_new, z)
        beta = _safe_div(blas.dot(r_new - r, z), rz)
        p = z + beta * p
        out = {**st, "x": x, "r": r_new, "p": p, "z": z, "r_old": r,
               "rz": rz_new}
        if self.health_guards:
            out["breakdown"] = pAp <= 0
        return out

    def _fused_iteration(self, data, st):
        """Fused flexible PCG: the same two shell kernels as PCG; the
        Polak-Ribiere numerator <z, r_new - r> (it needs the OLD r
        after the new one exists) packs into the same psum bundle as
        r.z."""
        A = data["A"]
        x, r, rz = st["x"], st["r"], st["rz"]
        p, Ap, pAp = spmv_pdot(A, st["p"], st["z"], st["beta"])
        (pAp,) = blas.psum_bundle((pAp,))
        alpha = _safe_div(rz, pAp)
        x, r_new, rr = blas.cg_update(x, p, r, Ap, alpha)
        z, rz_l = self._precond_dot(data, r_new)
        dz_l = _ldot(r_new - r, z)
        rr, rz_new, dz = blas.psum_bundle((rr, rz_l, dz_l))
        beta = _safe_div(dz, rz)
        out = {**st, "x": x, "r": r_new, "p": p, "z": z, "rz": rz_new,
               "rr": rr, "beta": beta}
        if self.health_guards:
            out["breakdown"] = pAp <= 0
        return out

    def internal_res_norm(self, state):
        if "rr" not in state or not self._l2_scalar_norm():
            return None
        return jnp.sqrt(state["rr"])


@registry.solvers.register("BICGSTAB")
class BiCGStabSolver(_KrylovBase):
    """BiCGStab (bicgstab_solver.cu)."""

    def solve_init(self, data, b, x, r):
        if self.krylov_fusion:
            (rho,) = blas.psum_bundle((_ldot(r, r),))
            one = jnp.ones((), rho.dtype)
        else:
            rho = blas.dot(r, r)
            one = jnp.ones((), r.dtype)
        return {"r_tld": r, "p": r, "v": jnp.zeros_like(r),
                "rho": rho, "alpha": one, "omega": one,
                **self._guard_init()}

    def solve_iteration(self, data, b, st):
        if self.krylov_fusion:
            return self._fused_iteration(data, st)
        A = data["A"]
        x, r = st["x"], st["r"]
        r_tld, p, rho = st["r_tld"], st["p"], st["rho"]
        v = spmv(A, p)
        alpha = _safe_div(rho, blas.dot(r_tld, v))
        s = r - alpha * v
        t = spmv(A, s)
        omega = _safe_div(blas.dot(t, s), blas.dot(t, t))
        x = x + alpha * p + omega * s
        r = s - omega * t
        rho_new = blas.dot(r_tld, r)
        beta = _safe_div(rho_new * alpha, rho * omega)
        p = r + beta * (p - omega * v)
        out = {**st, "x": x, "r": r, "p": p, "v": v, "rho": rho_new,
               "alpha": alpha, "omega": omega}
        if self.health_guards:
            # rho underflow (shadow residual orthogonal to r) or omega
            # collapse: the BiCGStab recurrence is dead — exit cleanly
            out["breakdown"] = (rho_new == 0) | (omega == 0)
        return out

    def _fused_iteration(self, data, st):
        """Both SpMVs carry their dots as kernel epilogues: r_tld.v
        with v = A p, and the t.s / t.t PAIR with t = A s (self_dot)
        — four standalone full-vector reductions become two epilogue
        reads plus the one rho dot the kernels cannot see."""
        A = data["A"]
        x, r = st["x"], st["r"]
        r_tld, p, rho = st["r_tld"], st["p"], st["rho"]
        v, rtv = spmv_ddot(A, p, r_tld)
        (rtv,) = blas.psum_bundle((rtv,))
        alpha = _safe_div(rho, rtv)
        s = r - alpha.astype(r.dtype) * v
        t, ts, tt = spmv_ddot(A, s, s, self_dot=True)
        ts, tt = blas.psum_bundle((ts, tt))
        omega = _safe_div(ts, tt)
        w = omega.astype(r.dtype)
        x = x + alpha.astype(r.dtype) * p + w * s
        r = s - w * t
        (rho_new,) = blas.psum_bundle((_ldot(r_tld, r),))
        beta = _safe_div(rho_new * alpha, rho * omega)
        p = r + beta.astype(r.dtype) * (p - w * v)
        out = {**st, "x": x, "r": r, "p": p, "v": v, "rho": rho_new,
               "alpha": alpha, "omega": omega}
        if self.health_guards:
            out["breakdown"] = (rho_new == 0) | (omega == 0)
        return out


@registry.solvers.register("PBICGSTAB")
class PBiCGStabSolver(_KrylovBase):
    """Preconditioned BiCGStab (pbicgstab_solver.cu)."""

    uses_preconditioner = True
    precond_applications_per_iteration = 2     # p_hat and s_hat

    def solve_init(self, data, b, x, r):
        if self.krylov_fusion:
            (rho,) = blas.psum_bundle((_ldot(r, r),))
            one = jnp.ones((), rho.dtype)
        else:
            rho = blas.dot(r, r)
            one = jnp.ones((), r.dtype)
        return {"r_tld": r, "p": r, "v": jnp.zeros_like(r),
                "rho": rho, "alpha": one, "omega": one,
                **self._guard_init()}

    def solve_iteration(self, data, b, st):
        if self.krylov_fusion:
            return self._fused_iteration(data, st)
        A = data["A"]
        x, r = st["x"], st["r"]
        r_tld, rho = st["r_tld"], st["rho"]
        p = st["p"]
        p_hat = self._precond(data, p)
        v = spmv(A, p_hat)
        alpha = _safe_div(rho, blas.dot(r_tld, v))
        s = r - alpha * v
        s_hat = self._precond(data, s)
        t = spmv(A, s_hat)
        omega = _safe_div(blas.dot(t, s), blas.dot(t, t))
        x = x + alpha * p_hat + omega * s_hat
        r = s - omega * t
        rho_new = blas.dot(r_tld, r)
        beta = _safe_div(rho_new * alpha, rho * omega)
        p = r + beta * (p - omega * v)
        out = {**st, "x": x, "r": r, "p": p, "v": v, "rho": rho_new,
               "alpha": alpha, "omega": omega}
        if self.health_guards:
            out["breakdown"] = (rho_new == 0) | (omega == 0)
        return out

    def _fused_iteration(self, data, st):
        """Preconditioned twin of BiCGStab's fused iteration: both
        SpMVs act on preconditioned vectors while the dot operands
        (r_tld, s) stream through the kernels' epilogue slot."""
        A = data["A"]
        x, r = st["x"], st["r"]
        r_tld, rho = st["r_tld"], st["rho"]
        p = st["p"]
        p_hat = self._precond(data, p)
        v, rtv = spmv_ddot(A, p_hat, r_tld)
        (rtv,) = blas.psum_bundle((rtv,))
        alpha = _safe_div(rho, rtv)
        a = alpha.astype(r.dtype)
        s = r - a * v
        s_hat = self._precond(data, s)
        t, ts, tt = spmv_ddot(A, s_hat, s, self_dot=True)
        ts, tt = blas.psum_bundle((ts, tt))
        omega = _safe_div(ts, tt)
        w = omega.astype(r.dtype)
        x = x + a * p_hat + w * s_hat
        r = s - w * t
        (rho_new,) = blas.psum_bundle((_ldot(r_tld, r),))
        beta = _safe_div(rho_new * alpha, rho * omega)
        p = r + beta.astype(r.dtype) * (p - w * v)
        out = {**st, "x": x, "r": r, "p": p, "v": v, "rho": rho_new,
               "alpha": alpha, "omega": omega}
        if self.health_guards:
            out["breakdown"] = (rho_new == 0) | (omega == 0)
        return out


@registry.solvers.register("CHEBYSHEV")
class ChebyshevSolver(_KrylovBase):
    """Chebyshev iteration (cheb_solver.cu:150-216) with eigenvalue-
    estimation modes: 0/1 = power iteration on the (preconditioned)
    operator at setup (mode 0's separate lmin eigensolve collapses to the
    lmax/8 smoothing interval here — one power sweep, documented
    deviation); 2 = Gershgorin max row sum (0.9 under a preconditioner);
    3 = user cheby_max_lambda/cheby_min_lambda under a preconditioner,
    Gershgorin otherwise."""

    uses_preconditioner = True
    is_smoother = True
    # _d/_c are Python floats baked into the trace (see
    # _resetup_kept_static below) — one trace cannot serve per-system
    # spectra, so multi-matrix batching rejects this solver
    trace_bakes_values = True

    def __init__(self, cfg, scope="default", name="CHEBYSHEV"):
        super().__init__(cfg, scope, name)
        self.estimate_mode = int(cfg.get("chebyshev_lambda_estimate_mode",
                                         scope))
        self.lmax = float(cfg.get("cheby_max_lambda", scope))
        self.lmin = float(cfg.get("cheby_min_lambda", scope))

    def solver_setup(self):
        mode = self.estimate_mode
        has_precond = self.preconditioner is not None
        if mode in (0, 1):
            precond_apply = None
            if has_precond:
                pdata = self.preconditioner.solve_data_part()
                precond_apply = lambda v: self.preconditioner.apply(pdata, v)
            lmax = _power_lambda_max(self.A, precond_apply)
            self.lmax = float(lmax) * 1.05
            self.lmin = self.lmax / 8.0  # standard smoothing interval
        elif mode == 2:
            if has_precond:
                # reference assumption: preconditioner compresses the
                # spectrum to ~1 (cheb_solver.cu:193-196)
                self.lmax = 0.9
            else:
                self.lmax = float(_gershgorin_lambda_max(self.A))
            self.lmin = self.lmax * 0.125
        elif mode == 3:
            if has_precond:
                pass  # user-provided cheby_max_lambda / cheby_min_lambda
            else:
                self.lmax = float(_gershgorin_lambda_max(self.A))
                self.lmin = self.lmax * 0.125
        self._d = (self.lmax + self.lmin) / 2.0
        self._c = (self.lmax - self.lmin) / 2.0

    def _resetup_kept_static(self):
        # _d/_c are VALUE-derived Python floats baked into the trace as
        # constants (solve_iteration reads them directly) — a value-only
        # resetup changes them, so the cached solve must re-trace
        return False

    def computes_residual(self):
        return False

    def solve_init(self, data, b, x, r):
        dt = x.dtype
        return {"p": jnp.zeros_like(x), "rho": jnp.zeros((), dt),
                "k": jnp.zeros((), jnp.int32)}

    def solve_iteration(self, data, b, st):
        A = data["A"]
        d, c = self._d, self._c
        sigma = d / c
        x, p, rho, k = st["x"], st["p"], st["rho"], st["k"]
        r = b - spmv(A, x)
        z = self._precond(data, r)
        first = (k == 0)
        rho_new = jnp.where(first, 1.0 / sigma,
                            1.0 / (2.0 * sigma - rho))
        p = jnp.where(first, z / d,
                      rho_new * rho * p + (2.0 * rho_new / c) * z)
        x = x + p
        return {**st, "x": x, "p": p, "rho": rho_new, "k": k + 1}


def _gershgorin_lambda_max(A):
    """Max diag-scaled absolute row sum — the reference's
    compute_eigenmax_estimate (cheb_solver.cu:46-74) lambda bound."""
    absA = A.with_values(jnp.abs(A.values),
                         jnp.abs(A.diag) if A.has_external_diag else None)
    n = A.num_rows * A.block_dimx
    row_abs = spmv(absA, jnp.ones(n, dtype=A.dtype))
    d = A.diagonal()
    if d.ndim == 3:  # block diagonal -> per-unknown diagonal entries
        d = jnp.diagonal(d, axis1=1, axis2=2).reshape(-1)
    return jnp.max(row_abs / jnp.abs(d))


def _power_lambda_max(A, precond_apply=None, iters: int = 20, seed: int = 0):
    """Power-iteration estimate of lambda_max of the (preconditioned)
    operator M^{-1}A (setup-time; cheb_solver.cu eigenvalue estimation)."""
    import numpy as np
    n = A.num_rows * A.block_dimx
    v = jnp.asarray(np.random.default_rng(seed).standard_normal(n),
                    dtype=A.dtype)

    def op(v):
        w = spmv(A, v)
        return precond_apply(w) if precond_apply is not None else w

    def body(_, carry):
        v, lam = carry
        w = op(v)
        lam = blas.nrm2(w)
        return w / jnp.where(lam == 0, 1.0, lam), lam

    _, lam = jax.lax.fori_loop(0, iters, body,
                               (v / blas.nrm2(v), jnp.zeros((), v.dtype)))
    return lam

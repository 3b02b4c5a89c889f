"""Polynomial smoothers: POLYNOMIAL, KPZ_POLYNOMIAL, CHEBYSHEV_POLY.

TPU-native analogs of src/solvers/polynomial_solver.cu (351 LoC),
kpz_polynomial_solver.cu (227), chebyshev_poly.cu (371). Polynomial
smoothers are ideal TPU smoothers: no coloring, no triangular solves —
each application is `order` SpMVs plus AXPYs, which XLA fuses into a
short straight-line program.

- POLYNOMIAL: Chebyshev relaxation on the interval [rho/30, 1.1*rho]
  (the bundled-CUSP convention the reference delegates to,
  polynomial_solver.cu:146-155: ritz_spectral_radius_symmetric +
  chebyshev_polynomial_coefficients); rho estimated at setup with a
  short device Lanczos, degree = kpz_order.
- KPZ_POLYNOMIAL: the KPZ three-term recurrence exactly as in
  kpz_polynomial_solver.cu:140-193 (smax = ||A||_inf via the transpose
  row sums, smin = smax/kpz_mu, delta/beta/chi coefficients).
- CHEBYSHEV_POLY: the "magic damping" tau sequence of chebyshev_poly.cu
  (tau_i = cos^2(beta) / (cos^2(beta(2i+1)) - sin^2(beta)) / lambda,
  beta = pi/(4m+2), lambda = Gershgorin max row sum,
  chebyshev_poly.cu:65-74,188-198), applied as x += tau_i (b - A x).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry
from ..errors import BadParametersError
from ..ops.spmv import spmv
from ..telemetry import metrics as _tm
from .base import Solver


def chebyshev_poly_coeffs(m: int):
    """The 'magic damping' tau numerators (chebyshev_poly.cu damping
    schedule); divide by the spectral bound to get the taus. Single
    implementation shared by the single-device and sharded setups."""
    beta = np.pi / (4.0 * m + 2.0)
    return np.asarray([
        np.cos(beta) ** 2
        / (np.cos(beta * (2 * i + 1)) ** 2 - np.sin(beta) ** 2)
        for i in range(m)
    ])


@functools.partial(jax.jit, static_argnames=("num_rows",))
def dia_abs_row_sums(dia_vals, num_rows: int):
    """Row abs-sums of a scalar DIA slab (k, rows_pad, LANES): one dense
    pass over the diagonals in the layout they are stored in, no scatter.
    Sound only while the slab's off-grid and pad slots hold ZERO, which
    every builder keeps (matrix._build_dia_vals and the host bincount
    fill a zeroed slab; galerkin._geo_value_phase packs with
    zeros(...).at[:, :nc].set). The ONE expression behind the Gershgorin
    bound of a rebuilt level (`_abs_row_sums`) and of a value-re-set-up
    one (amg/value_resetup._lam_rowmax): same values, same lam."""
    return jnp.sum(jnp.abs(dia_vals), axis=0).reshape(-1)[:num_rows]


def _abs_row_sums(A):
    """Row abs-sums of a scalar matrix for a smoother's set-up, by the
    layout the matrix holds: a built DIA slab is reduced in place
    (`dia_abs_row_sums`); every other matrix (CSR, ELL, SWELL, not yet
    initialised) sums its COO triplets by row. Duplicate entries, which
    the slab holds summed, read |a + b| there and |a| + |b| here: the
    slab's is the bound of the operator the SpMV kernels apply."""
    if A.dia_vals is not None:
        _tm.inc("smoother.row_sums.slab")
        s = dia_abs_row_sums(A.dia_vals, A.num_rows)
    else:
        _tm.inc("smoother.row_sums.coo")
        rows, cols, vals = A.coo()
        s = jax.ops.segment_sum(jnp.abs(vals), rows, num_segments=A.num_rows,
                                indices_are_sorted=True)
    if A.has_external_diag:
        s = s + jnp.abs(A.diag)
    return s


def _lanczos_rho(A, steps: int = 8) -> float:
    """Spectral-radius estimate by a short Lanczos run
    (cusp ritz_spectral_radius_symmetric analog). Host-orchestrated at
    setup; each step is one device SpMV."""
    n = A.num_rows
    rng = np.random.default_rng(17)
    v = jnp.asarray(rng.standard_normal(n), A.dtype)
    v = v / jnp.linalg.norm(v)
    steps = min(steps, n)
    alphas, betas = [], []
    v_prev = jnp.zeros_like(v)
    beta = 0.0
    for _ in range(steps):
        w = spmv(A, v) - beta * v_prev
        alpha = float(jnp.dot(v, w))
        w = w - alpha * v
        beta = float(jnp.linalg.norm(w))
        alphas.append(alpha)
        betas.append(beta)
        if beta < 1e-12:
            break
        v_prev, v = v, w / beta
    k = len(alphas)
    T = np.diag(alphas)
    for i in range(k - 1):
        T[i, i + 1] = T[i + 1, i] = betas[i]
    return float(np.max(np.abs(np.linalg.eigvalsh(T)))) * 1.01


@registry.solvers.register("POLYNOMIAL")
class PolynomialSolver(Solver):
    """Chebyshev relaxation smoother (polynomial_solver.cu scalar path).
    One application = `kpz_order` SpMVs via the stable three-term
    Chebyshev semi-iteration on [rho/30, 1.1 rho]."""

    is_smoother = True

    def __init__(self, cfg, scope="default", name="POLYNOMIAL"):
        super().__init__(cfg, scope, name)
        order = int(cfg.get("kpz_order", scope))
        self.order = order if order > 0 else 6   # ndeg0==0 -> 6 (:114)

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError(
                "POLYNOMIAL smoother supports scalar matrices")
        rho = _lanczos_rho(self.A)
        self.lmax = 1.1 * rho
        self.lmin = rho / 30.0

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["lmin"] = jnp.asarray(self.lmin, self.A.dtype)
        d["lmax"] = jnp.asarray(self.lmax, self.A.dtype)
        return d

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A = data["A"]
        lmin, lmax = data["lmin"], data["lmax"]
        theta = 0.5 * (lmax + lmin)
        delta = 0.5 * (lmax - lmin)
        x = st["x"]
        r = b - spmv(A, x)
        # Chebyshev semi-iteration (fixed `order` steps, unrolled)
        sigma = theta / delta
        rho_c = 1.0 / sigma
        d = r / theta
        for _ in range(self.order):
            x = x + d
            r = r - spmv(A, d)
            rho_new = 1.0 / (2.0 * sigma - rho_c)
            d = rho_new * rho_c * d + 2.0 * rho_new / delta * r
            rho_c = rho_new
        out = dict(st)
        out["x"] = x
        return out


@registry.solvers.register("KPZ_POLYNOMIAL")
class KPZPolynomialSolver(Solver):
    """KPZ polynomial smoother (kpz_polynomial_solver.cu:140-193)."""

    is_smoother = True

    def __init__(self, cfg, scope="default", name="KPZ_POLYNOMIAL"):
        super().__init__(cfg, scope, name)
        self.mu = int(cfg.get("kpz_mu", scope))
        self.order = max(int(cfg.get("kpz_order", scope)), 1)

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError(
                "KPZ_POLYNOMIAL supports scalar matrices")
        # l_inf = max column abs-sum (computed on A^T in the reference,
        # kpz_polynomial_solver.cu:89-99)
        rows, cols, vals = self.A.coo()
        colsum = jax.ops.segment_sum(jnp.abs(vals), cols,
                                     num_segments=self.A.num_cols)
        if self.A.has_external_diag:
            colsum = colsum + jnp.abs(self.A.diag)
        self.l_inf = float(jnp.max(colsum))

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["l_inf"] = jnp.asarray(self.l_inf, self.A.dtype)
        return d

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A = data["A"]
        smax = data["l_inf"]
        smin = smax / self.mu
        smu0 = 1.0 / smax
        smu1 = 1.0 / smin
        skappa = jnp.sqrt(smax / smin)
        delta = (skappa - 1.0) / (skappa + 1.0)
        beta = (jnp.sqrt(smu0) + jnp.sqrt(smu1)) ** 2
        chi = 4.0 * smu0 * smu1 / beta
        x = st["x"]
        r = b - spmv(A, x)
        v0 = (smu0 + smu1) / 2.0 * r
        v = beta / 2.0 * r - smu0 * smu1 * spmv(A, r)
        for _ in range(2, self.order + 1):
            sn = r - spmv(A, v)
            sn = chi * sn + delta * delta * v - delta * delta * v0
            v0 = v
            v = v + sn
        out = dict(st)
        out["x"] = x + v
        return out


@registry.solvers.register("CHEBYSHEV_POLY")
class ChebyshevPolySolver(Solver):
    """'Magic damping' Chebyshev smoother (chebyshev_poly.cu). One
    application = `chebyshev_polynomial_order` damped Richardson steps
    x += tau_i (b - A x)."""

    is_smoother = True
    # matrix-free capable (amg/hierarchy.py `matrix_free` knob): the
    # damped-Richardson steps need only the stencil coefficients; no
    # diagonal inverse is synthesized (dinv-free schedule)
    supports_matrix_free = True
    matrix_free_dinv = None

    def __init__(self, cfg, scope="default", name="CHEBYSHEV_POLY"):
        super().__init__(cfg, scope, name)
        order = int(cfg.get("chebyshev_polynomial_order", scope))
        self.order = min(10, max(order, 1))      # clamp (:102-103)
        self.fused_smoother = bool(int(cfg.get("fused_smoother", scope)))

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError(
                "CHEBYSHEV_POLY supports scalar matrices")
        # lambda stays ON DEVICE: a float() fetch here would block on
        # a device->host sync per AMG level; taus ships to the solve
        # program as a device array
        lam = jnp.max(_abs_row_sums(self.A))   # Gershgorin bound
        self._taus = jnp.asarray(chebyshev_poly_coeffs(self.order),
                                 self.A.dtype) / lam.astype(self.A.dtype)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["taus"] = self._taus
        st = getattr(self, "_mf_stencil", None)
        if st is not None:
            # matrix-free level: drop the A value slab from the
            # operator view; no fused slabs — the kernels read the
            # stencil coefficients from SMEM (ops/stencil.py)
            from ..ops.stencil import mf_slim
            d["A"] = mf_slim(d["A"])
            d["stencil"] = st
            return d
        if self.fused_smoother and self.A is not None \
                and not getattr(self.A, "is_block", True):
            from ..ops import smooth as fused
            slabs = fused.solver_fused_slabs(self, self.A)
            if slabs is not None:
                d["fused"] = slabs
        return d

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A = data["A"]
        x = st["x"]
        for i in range(self.order):
            x = x + data["taus"][i] * (b - spmv(A, x))
        out = dict(st)
        out["x"] = x
        return out

    # -- fused smoothing (ops/smooth.py) --------------------------------
    # One smoother application is `order` damped-Richardson steps
    # x += tau_i (b - A x); `sweeps` applications are the tiled tau
    # schedule, which the fused kernel runs (with the trailing cycle
    # residual) in one HBM pass over A, x and b.
    def _fused_taus(self, data, sweeps: int, dtype):
        taus = jnp.asarray(data["taus"], dtype)
        return jnp.tile(taus, sweeps) if sweeps > 1 else taus

    def smooth(self, data, b, x, sweeps: int):
        st = data.get("stencil")
        if st is not None:
            if sweeps < 1:
                return x
            from ..ops import stencil as mf
            return mf.stencil_fused_smooth(
                st, self._fused_taus(data, sweeps, x.dtype), b, x,
                with_residual=False)
        if sweeps > 0 and self.fused_smoother:
            from ..ops import smooth as fused
            out = fused.fused_smooth(
                data, b, x, self._fused_taus(data, sweeps, x.dtype),
                with_residual=False)
            if out is not None:
                return out
        return super().smooth(data, b, x, sweeps)

    def smooth_residual(self, data, b, x, sweeps: int):
        st = data.get("stencil")
        if st is not None:
            from ..ops import stencil as mf
            taus = (self._fused_taus(data, sweeps, x.dtype)
                    if sweeps > 0 else jnp.zeros((0,), x.dtype))
            return mf.stencil_fused_smooth(st, taus, b, x,
                                           with_residual=True)
        if sweeps > 0 and self.fused_smoother:
            from ..ops import smooth as fused
            out = fused.fused_smooth(
                data, b, x, self._fused_taus(data, sweeps, x.dtype),
                with_residual=True)
            if out is not None:
                return out
        return super().smooth_residual(data, b, x, sweeps)

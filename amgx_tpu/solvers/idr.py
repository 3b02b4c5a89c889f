"""IDR(s) induced-dimension-reduction Krylov solvers.

Analogs of src/solvers/idr_solver.cu (586 LoC) and idrmsync_solver.cu
(686 LoC). The algorithm is the biorthogonal IDR(s) of van Gijzen &
Sonneveld (ACM TOMS 38(1), 2011 — public); the shadow space dimension is
`subspace_dim_s`.

One `solve_iteration` here performs a full IDR cycle (s intermediate
steps + the dimension-reduction step = s+1 SpMVs), with the per-step
inner products expressed as batched (n,s) matrix contractions. That
batching is exactly the "minimized synchronization" reformulation
idrmsync exists for on GPUs — under XLA a whole cycle compiles into one
program and the compiler schedules the reductions, so both registered
names run this formulation; iteration counts match the biortho IDR(s)
recurrence either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry
from ..ops import blas
from .base import Solver
from .krylov import _KrylovBase, _safe_div
from ..ops.spmv import spmv


@registry.solvers.register("IDR")
@registry.solvers.register("IDRMSYNC")
class IDRSolver(_KrylovBase):
    """IDR(s) with biorthogonalization of the shadow residuals."""

    uses_preconditioner = True

    def __init__(self, cfg, scope="default", name="IDR"):
        super().__init__(cfg, scope, name)
        self.s = max(int(cfg.get("subspace_dim_s", scope)), 1)
        self.kappa = 0.7          # omega angle correction (standard)

    def solver_setup(self):
        n = self.A.num_rows * self.A.block_dimx
        s = self.s
        # fixed-seed shadow space: deterministic runs (determinism_flag
        # semantics); orthonormalized columns
        P = np.random.default_rng(271828).standard_normal((n, s))
        P, _ = np.linalg.qr(P)
        self._P = jnp.asarray(P, dtype=self.A.dtype)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["P"] = self._P
        return d

    def solve_init(self, data, b, x, r):
        n, s = r.shape[0], self.s
        dt = r.dtype
        return {
            "G": jnp.zeros((n, s), dt), "U": jnp.zeros((n, s), dt),
            "M": jnp.eye(s, dtype=dt), "omega": jnp.ones((), dt),
            **self._guard_init(),
        }

    def solve_iteration(self, data, b, st):
        A, P = data["A"], data["P"]
        s = self.s
        x, r = st["x"], st["r"]
        G, U, M, omega = st["G"], st["U"], st["M"], st["omega"]
        f = P.T @ r                                   # (s,)
        for k in range(s):
            # solve M[k:,k:] c = f[k:]  (lower triangular, small static
            # s); a zero pivot is a shadow-space breakdown — guard it to
            # keep NaN out of x (the _safe_div convention of krylov.py)
            dM = jnp.diagonal(M)
            M_safe = M + jnp.diag((dM == 0).astype(M.dtype))
            c = jax.scipy.linalg.solve_triangular(M_safe[k:, k:], f[k:],
                                                  lower=True)
            v = r - G[:, k:] @ c
            v = self._precond(data, v)
            u_k = omega * v + U[:, k:] @ c
            g_k = spmv(A, u_k)
            # biorthogonalize g_k against P[:, :k]
            if k > 0:
                dMk = jnp.diagonal(M)[:k]
                alpha = (P[:, :k].T @ g_k) / jnp.where(dMk == 0, 1.0, dMk) \
                    * (dMk != 0)
                g_k = g_k - G[:, :k] @ alpha
                u_k = u_k - U[:, :k] @ alpha
            G = G.at[:, k].set(g_k)
            U = U.at[:, k].set(u_k)
            # new column k of M
            Mk = P.T @ g_k                            # (s,)
            M = M.at[:, k].set(Mk)
            beta = _safe_div(f[k], M[k, k])
            r = r - beta * g_k
            x = x + beta * u_k
            if k + 1 < s:
                f = f.at[k + 1:].add(-beta * M[k + 1:, k])
                f = f.at[:k + 1].set(0.0)
        # dimension-reduction step
        v = self._precond(data, r)
        t = spmv(A, v)
        tt = blas.dot(t, t)
        tr = blas.dot(t, r)
        om = _safe_div(tr, tt)
        # angle correction: keep |cos| >= kappa for robustness
        nr, nt = blas.nrm2(r), jnp.sqrt(jnp.where(tt == 0, 1.0, tt))
        rho = jnp.abs(_safe_div(tr, nt * jnp.where(nr == 0, 1.0, nr)))
        om = jnp.where(rho < self.kappa,
                       om * _safe_div(jnp.asarray(self.kappa, om.dtype), rho),
                       om)
        x = x + om * v
        r = r - om * t
        out = {**st, "x": x, "r": r, "G": G, "U": U, "M": M, "omega": om}
        if self.health_guards:
            # omega collapse: the dimension-reduction step degenerated
            # (t == 0 or t orthogonal to r) — IDR(s) cannot proceed
            out["breakdown"] = om == 0
        return out

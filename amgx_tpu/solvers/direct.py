"""Dense direct solver (coarse-grid solver).

Analog of src/solvers/dense_lu_solver.cu (cuSolverDn getrf/getrs,
:514-580): densify the (small) matrix once at setup, factor it, and
back-substitute per application. XLA:TPU does not implement f64 LU
(see ops/dense.py), so the factorization is Householder QR — same
O(n^3) setup / O(n^2) apply split as getrf/getrs, and the triangular
solve runs on the MXU. The coarsest AMG level is replicated across the
mesh, so this factorization is the `exact_coarse_solve` analog (the
distributed layer all-gathers the coarse rhs before calling this,
mirroring dense_lu_solver.cu:783-930).
"""
from __future__ import annotations

import jax.numpy as jnp
import jax.scipy.linalg as jsl

from .. import registry
from ..ops.spmv import residual
from .base import Solver


@registry.solvers.register("DENSE_LU_SOLVER")
class DenseLUSolver(Solver):
    def __init__(self, cfg, scope="default", name="DENSE_LU_SOLVER"):
        super().__init__(cfg, scope, name)
        self.dense_lu_num_rows = int(cfg.get("dense_lu_num_rows", scope))
        self.dense_lu_max_rows = int(cfg.get("dense_lu_max_rows", scope))

    def solver_setup(self):
        dense = self.A.to_dense()
        # guard singular rows (e.g. empty coarse rows) with unit diagonal
        zero_rows = jnp.all(dense == 0, axis=1)
        dense = jnp.where(
            jnp.diag(zero_rows), jnp.eye(dense.shape[0], dtype=dense.dtype),
            dense)
        self._qt, self._r = self._factor(dense)

    @staticmethod
    def _factor(dense):
        q, r = jnp.linalg.qr(dense)
        return q.T, r

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["qt"] = self._qt
        d["r"] = self._r
        return d

    def _direct(self, data, rhs):
        return jsl.solve_triangular(data["r"], data["qt"] @ rhs, lower=False)

    def solve_iteration(self, data, b, st):
        x = self._direct(data, b)
        out = dict(st)
        out["x"] = x
        out["r"] = residual(data["A"], x, b)
        return out

    def apply(self, data, rhs):
        return self._direct(data, rhs)

    def smooth(self, data, b, x, sweeps):
        return self._direct(data, b)

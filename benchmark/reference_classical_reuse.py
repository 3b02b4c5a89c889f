"""The plain reference of a classical structure-reuse re-setup, and the
control of `classical-reuse-p7-128.time-step`: numpy + scipy, float64,
nothing of `amgx_tpu`.

Under `structure_reuse_levels=-1` a classical hierarchy keeps strength,
C/F split and the transfer operators of its first setup and recomputes,
from each step's values, every level's Galerkin operator `R A P` with
`R = P^T`, every Jacobi smoother's diagonal and the coarsest level's
dense matrix. The structure IS what is reused, so it is an input here:
each level's kept `P` as CSR arrays, taken from the level under test.

- `rebuild` gives, for the fine values it is handed, each level's
  operator (scipy CSR, sorted, duplicates summed), each operator's
  diagonal and the coarsest operator as a dense matrix;
- `solve` is a textbook PCG round one V(1,1) cycle of damped Jacobi
  over that hierarchy, dense solve at the bottom, stopped on the
  recurrence residual relative to the initial one (`RELATIVE_INI`):
  the shape of `PCG_CLASSICAL_V_JACOBI.json`, whose iteration count a
  re-set-up solver has to meet within one;
- `largest_difference` is `reference_reuse`'s.

`correct` in the cell stays what `reference.py` decides (the float64
residual of the answer). This file is what the tests and the builder's
chip comparison (tools/classical_reuse_check.py) hold the re-set-up
HIERARCHY to: a solve preconditioned by a stale coarse level still
converges, so the residual alone would not see a resetup that skipped
a level.

The cell's control lives here too: `ReferenceCGSteps`, the plain CG
with matrix and vectors in the control's dtype, taking each step's new
values. The configuration names it (`control.entry` with `control.module`
= this module), and `python3 -m benchmark.control --workload
classical-reuse-p7-128.time-step --seed <n> --seconds <s>` runs the cell
with it in the program's place, exit 0 when it came out NOT correct.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from .reference import ReferenceCG
from .reference_reuse import largest_difference  # noqa: F401


def _csr(row_offsets, col_indices, values, cols=None) -> sp.csr_matrix:
    rows = int(np.asarray(row_offsets).shape[0]) - 1
    M = sp.csr_matrix((np.asarray(values, dtype=np.float64),
                       np.asarray(col_indices), np.asarray(row_offsets)),
                      shape=(rows, rows if cols is None else int(cols)))
    M.sum_duplicates()
    M.sort_indices()
    return M


def rebuild(row_offsets, col_indices, values, prolongators) -> dict:
    """The hierarchy a structure-reuse re-setup has to give for these
    fine values. `prolongators` is one (row_offsets, col_indices,
    values, columns) per level, the fine level's first: the kept `P`.
    Returns `operators` (the fine level's and every coarse one's, in
    order), `prolongators` (as matrices), `diagonals` (one per
    operator), `terms` (per operator, the most products `r a p` that
    one of its entries sums: what a rounding limit scales with) and
    `coarsest` (the last operator, dense)."""
    operators = [_csr(row_offsets, col_indices, values)]
    kept, terms = [], [0]
    for p_ro, p_ci, p_vals, cols in prolongators:
        P = _csr(p_ro, p_ci, p_vals, cols)
        assert P.shape[0] == operators[-1].shape[0], (
            f"P has {P.shape[0]} rows, its level {operators[-1].shape[0]}")
        Ac = sp.csr_matrix(P.T @ (operators[-1] @ P))
        Ac.sum_duplicates()
        Ac.sort_indices()
        ones = [sp.csr_matrix((np.ones(M.nnz, np.int64), M.indices,
                               M.indptr), shape=M.shape)
                for M in (P, operators[-1])]
        terms.append(int((ones[0].T @ (ones[1] @ ones[0])).max()))
        operators.append(Ac)
        kept.append(P)
    return {"operators": operators, "prolongators": kept, "terms": terms,
            "diagonals": [Ak.diagonal() for Ak in operators],
            "coarsest": operators[-1].toarray()}


def cycle(hierarchy: dict, b, relaxation: float, level: int = 0):
    """One V(1,1) cycle of damped Jacobi from a zero guess: the
    preconditioner's answer to `b` at `level`."""
    ops = hierarchy["operators"]
    if level == len(ops) - 1:
        return np.linalg.solve(hierarchy["coarsest"], b)
    A, P = ops[level], hierarchy["prolongators"][level]
    d = hierarchy["diagonals"][level]
    x = relaxation * b / d
    x = x + P @ cycle(hierarchy, P.T @ (b - A @ x), relaxation, level + 1)
    return x + relaxation * (b - A @ x) / d


def solve(hierarchy: dict, b, relaxation: float = 0.9,
          tolerance: float = 1e-6, max_iters: int = 100):
    """(x, iterations) of preconditioned CG from a zero guess, stopped
    when ||r|| <= tolerance * ||r0|| on the recurrence residual."""
    A = hierarchy["operators"][0]
    b = np.asarray(b, dtype=np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    stop = tolerance * np.linalg.norm(r)
    z = cycle(hierarchy, r, relaxation)
    p, rz = z, float(r @ z)
    for k in range(1, max_iters + 1):
        Ap = A @ p
        alpha = rz / float(p @ Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        if np.linalg.norm(r) <= stop:
            return x, k
        z = cycle(hierarchy, r, relaxation)
        rz, rz_old = float(r @ z), rz
        p = z + (rz / rz_old) * p
    return x, max_iters


class ReferenceCGSteps(ReferenceCG):
    """`ReferenceCG` for a time-step cell: the diagonals are an
    argument of the jitted CG, `replace` rounds a step's new values to
    the control's dtype, and `resetup` has nothing to set up."""

    def upload(self, ro, ci, vals, rhs):
        import jax
        import jax.numpy as jnp
        dt = jnp.dtype(self.dtype)
        n = ro.shape[0] - 1
        self._row = np.repeat(np.arange(n), np.diff(ro))
        offsets, self._which = np.unique(ci - self._row,
                                         return_inverse=True)
        if offsets.size > 64:
            raise ValueError(f"{offsets.size} diagonals: ReferenceCG is "
                             f"for banded operators")
        self._shape = (offsets.size, n)
        reach = int(np.abs(offsets).max())
        self.rhs = [jnp.asarray(b.astype(self.vector_dtype)).astype(dt)
                    for b in rhs]

        def cg(diags, b):
            def matvec(v):
                vp = jnp.pad(v, reach)
                y = jnp.zeros_like(v)
                for k, o in enumerate(offsets.tolist()):
                    y = y + diags[k] * vp[reach + o:reach + o + n]
                return y

            def cond(st):
                k, _x, _r, _p, rr = st
                return (k < self.max_iters) & (
                    jnp.sqrt(rr / rr0).astype(jnp.float32) > self.tol)

            def body(st):
                k, x, r, p, rr = st
                Ap = matvec(p)
                alpha = rr / jnp.vdot(p, Ap)
                x = x + alpha * p
                r = r - alpha * Ap
                rr_new = jnp.vdot(r, r)
                p = r + (rr_new / rr) * p
                return k + 1, x, r, p, rr_new

            rr0 = jnp.vdot(b, b)
            k, x, _r, _p, _rr = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.zeros_like(b), b, b, rr0))
            return x, k

        self._cg = jax.jit(cg)
        self.replace(vals)

    def replace(self, vals):
        import jax.numpy as jnp
        diags = np.zeros(self._shape, np.float64)
        diags[self._which, self._row] = vals
        self._diags = jnp.asarray(diags).astype(jnp.dtype(self.dtype))

    def resetup(self):
        pass

    def solve(self, i: int):
        import jax
        self.res = jax.block_until_ready(
            self._cg(self._diags, self.rhs[i]))

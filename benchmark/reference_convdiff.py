"""The plain reference of `convdiff-pbicgstab-classical`: what
`PBICGSTAB_CLASSICAL_JACOBI.json` asks for on a NONSYMMETRIC operator,
written out in numpy + scipy, float64, nothing of `amgx_tpu`.

`correct` in the cell stays what `reference.py` decides (the float64
residual of the answer). A residual cannot see a transposed coupling
in an interpolation row, a restriction that is not the transpose of
the prolongation, or a shell computed in a lower precision that still
converges. This file is what the tests and the builder's chip
comparison (tools/convdiff_check.py) hold the program to, for CSR
arrays they hand it (`(row_offsets, col_indices, values)`, columns
ascending; a P also gives its number of columns):

- `d2_interpolation(A, strong, cf)`: the extended+i interpolation of
  De Sterck, Falgout, Nolting & Yang, "Distance-two interpolation for
  parallel algebraic multigrid", Numer. Linear Algebra Appl. 15 (2008),
  formula (4.10), for a strength mask that is ONE-SIDED (i may depend
  on k without k depending on i, which first-order upwinding produces
  by construction): for an F point i with strong C neighbours C_i and
  strong F neighbours F_i,

      C^_i = C_i + union over k in F_i of C_k
      w_ij = -(1 / a~_ii) (a_ij + sum_{k in F_i} a_ik a-_kj / d_ik),  j in C^_i
      d_ik = sum_{l in C^_i + {i}} a-_kl
      a~_ii = a_ii + sum_{n weak, n not in C^_i} a_in
                   + sum_{k in F_i} a_ik a-_ki / d_ik

  with a-_kl = a_kl where its sign is opposite to a_kk's and 0
  otherwise. Every coupling is read in the direction the formula
  names: a_ik from row i, a-_kj from row k; on a symmetric operator a
  transposed read gives the same numbers, here it does not;
- `galerkin(A, P)` (reference_spe10's): P^T A P with R = P^T, which is
  what the reference's classical level builds for a nonsymmetric A too
  (csr_galerkin_product over the transposed P);
- `hierarchy(..., prolongators)`: the Galerkin chain over the P of the
  hierarchy under test, with the Jacobi diagonals;
- `cycle`: V(1,1) from a zero guess, damped Jacobi (0.9) before and
  after, a dense LU solve at the coarsest level;
- `solve`: textbook right-preconditioned BiCGStab (van der Vorst, SIAM
  J. Sci. Stat. Comput. 13 (1992), with the preconditioner applied to
  p and to s) from a zero guess, stopped when ||r|| <= 1e-6 ||r0||
  (`RELATIVE_INI`), with the residual history;
- `own_hierarchy`: the reference's OWN hierarchy from the fine matrix
  alone (its own strength mask, reference_spe10's PMIS after hypre's
  par_coarsen.c, its own D2), taking nothing of the program's set-up:
  its iteration count is the yardstick a wrong split or a transposed
  coupling cannot pass;
- `ReferenceBiCGStab`: plain BiCGStab with the matrix and every vector
  in one dtype, the cell's control in bfloat16 (plain CG is no control
  for a nonsymmetric operator: it fails for the wrong reason).

Departures from the published forms, each where it is made: in
`d2_interpolation` a strong F neighbour k whose d_ik is zero (k has no
a- coupling into C^_i + {i}) cannot be distributed and its a_ik goes to
the diagonal (hypre's par_lr_interp.c does the same); an F point with
no strong dependency keeps an empty row. `solve` tests the residual
once an iteration, at its end, as the program's shell does; van der
Vorst's optional test on ||s|| after the first half step (which the
reference's pbicgstab_solver.cu makes) is left out, so a count here
can be one over a solver that makes it. The recurrence residual is
what is tested, in float64.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg
import scipy.sparse as sp

from .entries import Solved
from .reference_spe10 import (csr, galerkin, pmis, split_faults,  # noqa: F401
                              strength)

OMEGA = 0.9                 # relaxation_factor's default
DENSE_LU_NUM_ROWS = 128     # dense_lu_num_rows' default


def _pattern(M) -> sp.csr_matrix:
    M = sp.csr_matrix(M)
    M.eliminate_zeros()
    return sp.csr_matrix((np.ones(M.nnz), M.indices, M.indptr),
                         shape=M.shape)


def _select(A: sp.csr_matrix, keep) -> sp.csr_matrix:
    """A's entries where `keep` (a mask over them) holds."""
    out = sp.csr_matrix((np.where(keep, A.data, 0.0), A.indices.copy(),
                         A.indptr.copy()), shape=A.shape)
    out.eliminate_zeros()
    return out


def d2_interpolation(A: sp.csr_matrix, strong, cf) -> sp.csr_matrix:
    """Extended+i interpolation rows over the C/F split `cf` (1 / 0)
    and the strength mask `strong` over A's entries (row i: what i
    depends on)."""
    n = A.shape[0]
    rows = np.repeat(np.arange(n), np.diff(A.indptr))
    cols = A.indices
    C = np.asarray(cf) == 1
    strong = np.asarray(strong, bool) & (rows != cols)
    diag = A.diagonal()
    F_rows = sp.diags((~C).astype(np.float64))
    # what row i reads of its own couplings
    a_sc = _select(A, strong & C[cols])           # a_ij, j in C_i
    a_sf = _select(A, strong & ~C[cols])          # a_ik, k in F_i
    # a-_kl: the couplings of sign opposite to the row's diagonal
    a_bar = _select(A, (rows != cols)
                    & (np.sign(A.data) != np.sign(diag[rows])))
    # C^_i, and C^_i + {i}
    c_hat = _pattern(_pattern(a_sc) + _pattern(a_sf) @ _pattern(a_sc))
    c_hat_i = _pattern(c_hat + sp.identity(n, format="csr"))
    # d_ik = sum_l a-_kl [l in C^_i + {i}], on the pairs (i, k in F_i)
    d = (c_hat_i @ a_bar.T).multiply(_pattern(a_sf)).tocsr()
    d.eliminate_zeros()
    # k that cannot be distributed: a_ik goes to the diagonal
    dist = _pattern(d).multiply(a_sf).tocsr()     # a_ik where d_ik != 0
    lumped = np.asarray((a_sf - dist).sum(axis=1)).ravel()
    inv_d = d.copy()
    inv_d.data = 1.0 / inv_d.data
    W = dist.multiply(inv_d).tocsr()              # a_ik / d_ik
    spread = (W @ a_bar).tocsr()                  # sum_k a_ik a-_kj / d_ik
    numer = (A.multiply(c_hat) + spread.multiply(c_hat)).tocsr()
    # a~_ii: the diagonal, the weak couplings outside C^_i, the part of
    # each distributed k that comes back to i, and what was lumped
    in_hat = np.asarray(A.multiply(c_hat).sum(axis=1)).ravel()
    strong_sum = np.asarray((a_sc + a_sf).sum(axis=1)).ravel()
    strong_in_hat = np.asarray(
        (a_sc + a_sf).multiply(c_hat).sum(axis=1)).ravel()
    offd = np.asarray(A.sum(axis=1)).ravel() - diag
    weak_outside = (offd - strong_sum) - (in_hat - strong_in_hat)
    d_tilde = diag + weak_outside + spread.diagonal() + lumped
    scale = np.where(d_tilde != 0, -1.0 / np.where(d_tilde == 0, 1.0,
                                                   d_tilde), 0.0)
    Wf = (F_rows @ sp.diags(scale) @ numer).tocsc()[:, np.flatnonzero(C)]
    inject = sp.csr_matrix(
        (np.ones(int(C.sum())), (np.flatnonzero(C),
                                 np.arange(int(C.sum())))),
        shape=(n, int(C.sum())))
    P = sp.csr_matrix(Wf + inject)
    P.eliminate_zeros()
    P.sort_indices()
    return P


def hierarchy(row_offsets, col_indices, values, prolongators) -> dict:
    """What the preset's cycle runs on, over the hierarchy's own `P`
    (one (row_offsets, col_indices, values, columns) a level, the fine
    level's first): `operators` (the fine one and every Galerkin
    product P^T A P), `prolongators`, `diagonals`, `terms` (the most
    products an entry of each product sums; 0 for the fine level) and
    `scales` (the largest entry of |P|^T |A| |P| chained from the fine
    level: what a rounding of a Galerkin entry is measured against, as
    reference_spe10.hierarchy has it)."""
    operators, kept, terms = [csr(row_offsets, col_indices, values)], [], [0]
    absolute = abs(operators[0])
    scales = [float(absolute.max())]
    for p_ro, p_ci, p_vals, cols in prolongators:
        P = csr(p_ro, p_ci, p_vals, cols)
        assert P.shape[0] == operators[-1].shape[0], (
            f"P has {P.shape[0]} rows, its level {operators[-1].shape[0]}")
        Ac, most = galerkin(operators[-1], P)
        operators.append(Ac)
        kept.append(P)
        terms.append(most)
        absolute = sp.csr_matrix(abs(P).T @ (absolute @ abs(P)))
        scales.append(float(absolute.max()))
    return {"operators": operators, "prolongators": kept, "terms": terms,
            "scales": scales,
            "diagonals": [Ak.diagonal() for Ak in operators]}


def _coarse_factor(levels: dict):
    if "coarse_factor" not in levels:
        levels["coarse_factor"] = scipy.linalg.lu_factor(
            levels["operators"][-1].toarray())
    return levels["coarse_factor"]


def cycle(levels: dict, b, level: int = 0):
    """One V(1,1) cycle from a zero guess: damped Jacobi, R = P^T, a
    dense LU solve at the coarsest level."""
    A = levels["operators"][level]
    if level == len(levels["operators"]) - 1:
        return scipy.linalg.lu_solve(_coarse_factor(levels), b)
    d = levels["diagonals"][level]
    P = levels["prolongators"][level]
    x = OMEGA * b / d                     # the sweep from zero
    x = x + P @ cycle(levels, P.T @ (b - A @ x), level + 1)
    return x + OMEGA * (b - A @ x) / d


def solve(levels: dict, b, tolerance: float = 1e-6, max_iters: int = 100):
    """(x, iterations, converged, history) of right-preconditioned
    BiCGStab round `cycle`; `history[k]` is ||r|| after k iterations
    (the recurrence residual)."""
    A = levels["operators"][0]
    b = np.asarray(b, dtype=np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    r_tld = r.copy()
    p = r.copy()
    rho = float(r_tld @ r)
    history = [float(np.linalg.norm(r))]
    stop = tolerance * history[0]
    done = 0
    while done < max_iters and history[-1] > stop:
        p_hat = cycle(levels, p)
        v = A @ p_hat
        alpha = rho / float(r_tld @ v)
        s = r - alpha * v
        s_hat = cycle(levels, s)
        t = A @ s_hat
        omega = float(t @ s) / float(t @ t)
        x = x + alpha * p_hat + omega * s_hat
        r = s - omega * t
        rho_new = float(r_tld @ r)
        beta = (rho_new / rho) * (alpha / omega)
        p = r + beta * (p - omega * v)
        rho = rho_new
        done += 1
        history.append(float(np.linalg.norm(r)))
    return x, done, history[-1] <= stop, history


def own_hierarchy(row_offsets, col_indices, values, keys: dict,
                  seed: int = 0) -> dict:
    """`hierarchy`'s dictionary for a hierarchy that is the reference's
    own from the strength mask on, under the defaults the preset leaves
    open (`strength_threshold`, `max_row_sum` of `keys`; PMIS; D2
    untruncated), down to `dense_lu_num_rows` rows or `max_levels`."""
    rng = np.random.default_rng(seed)
    operators, kept = [csr(row_offsets, col_indices, values)], []
    lu_rows = int(keys.get("dense_lu_num_rows", DENSE_LU_NUM_ROWS))
    while len(operators) < int(keys.get("max_levels", 50)):
        A = operators[-1]
        if A.shape[0] <= 2 or (kept and A.shape[0] <= lu_rows):
            break
        mask, _weak = strength(A, keys["strength_threshold"],
                               keys["max_row_sum"])
        G = _select(A, mask)
        cf = pmis(_pattern(G), rng, start_fine=np.diff(G.indptr) == 0)
        P = d2_interpolation(A, mask, cf)
        if P.shape[1] < 2 or P.shape[1] == A.shape[0]:
            break
        kept.append(P)
        operators.append(galerkin(A, P)[0])
    return {"operators": operators, "prolongators": kept,
            "diagonals": [Ak.diagonal() for Ak in operators]}


class ReferenceBiCGStab:
    """Plain BiCGStab on the CSR arrays, every array in `dtype`; an
    entry like those of entries.py, so a control runs through the same
    harness. Its status is always "success": only the true residual
    judges it."""

    def __init__(self, solver: dict, operator: dict):
        self.dtype = solver["dtype"]
        self.max_iters = int(solver["max_iters"])
        self.tol = float(solver["tolerance"])
        self.vector_dtype = np.dtype(operator["dtype"])

    def upload(self, ro, ci, vals, rhs):
        import jax
        import jax.numpy as jnp
        dt = jnp.dtype(self.dtype)
        n = ro.shape[0] - 1
        # by diagonals, as reference.ReferenceCG: one shifted
        # multiply-add for each distinct col - row
        row = np.repeat(np.arange(n), np.diff(ro))
        offsets, which = np.unique(ci - row, return_inverse=True)
        if offsets.size > 64:
            raise ValueError(f"{offsets.size} diagonals: "
                             f"ReferenceBiCGStab is for banded operators")
        diags = np.zeros((offsets.size, n), np.float64)
        diags[which, row] = vals
        diags = jnp.asarray(diags).astype(dt)
        reach = int(np.abs(offsets).max())
        self.rhs = [jnp.asarray(b.astype(self.vector_dtype)).astype(dt)
                    for b in rhs]

        def matvec(v):
            vp = jnp.pad(v, reach)
            y = jnp.zeros_like(v)
            for k, o in enumerate(offsets.tolist()):
                y = y + diags[k] * vp[reach + o:reach + o + n]
            return y

        def safe(num, den):
            return jnp.where(den == 0, jnp.zeros_like(num),
                             num / jnp.where(den == 0, 1, den))

        def bicgstab(b):
            rr0 = jnp.vdot(b, b)

            def cond(st):
                k, _x, r, *_ = st
                return (k < self.max_iters) & (jnp.sqrt(
                    jnp.vdot(r, r) / rr0).astype(jnp.float32) > self.tol)

            def body(st):
                k, x, r, p, rho = st
                v = matvec(p)
                alpha = safe(rho, jnp.vdot(b, v))
                s = r - alpha * v
                t = matvec(s)
                omega = safe(jnp.vdot(t, s), jnp.vdot(t, t))
                x = x + alpha * p + omega * s
                r = s - omega * t
                rho_new = jnp.vdot(b, r)
                beta = safe(rho_new * alpha, rho * omega)
                p = r + beta * (p - omega * v)
                return k + 1, x, r, p, rho_new

            k, x, *_ = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.zeros_like(b), b, b, rr0))
            return x, k

        self._solve = jax.jit(bicgstab)

    def setup(self):
        pass

    def solve(self, i: int):
        import jax
        self.res = jax.block_until_ready(self._solve(self.rhs[i]))

    def last(self) -> Solved:
        x, k = self.res
        return Solved(np.asarray(x).astype(np.float64), int(k), True)

    def solver_tree(self):
        return None

    def close(self):
        pass

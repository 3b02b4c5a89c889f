"""The plain reference of `spe10-classical-l1trunc`: what the keys of
`AMG_CLASSICAL_AGGRESSIVE_L1_TRUNC.json` mean, written out in numpy +
scipy, float64, nothing of `amgx_tpu`.

`correct` in the cell stays what `reference.py` decides (the float64
residual of the answer). A residual cannot see whether a key of the
preset took effect: a hierarchy built without the row-sum rule, with
untruncated interpolation or with plain Jacobi still converges. This
file is what the tests and the builder's chip comparison
(tools/spe10_check.py) hold the HIERARCHY to, key by key, for CSR
arrays they hand it (`(row_offsets, col_indices, values)`, columns
ascending; a P also gives its number of columns):

- `strength(A, theta, max_row_sum)`: the reference's AHAT rule
  (src/classical/strength/strength_base.cu): a_ij is strong when
  -a_ij sgn(a_ii) >= theta max_k(-a_ik sgn(a_ii)), k != i, and
  positive; with `max_row_sum` < 1, a row whose |row sum| is over
  max_row_sum |a_ii| has no strong connection at all;
- `truncate(P, max_elements)`: src/truncate.cu: of each row the
  `max_elements` largest entries by magnitude are kept (the earlier
  column wins a tie) and rescaled to the row's sum;
- `l1_diagonal(A)`: src/solvers/jacobi_l1_solver.cu: a_ii + sgn(a_ii)
  sum_{j != i} |a_ij|;
- `galerkin(A, P)`: P^T A P, sorted, duplicates summed, and the most
  products an entry of it sums (what a rounding limit scales with);
- `solve(levels, b)`: a textbook FGMRES(10) from a zero guess, stopped
  when the estimated residual is under 1e-6 of the initial one
  (`RELATIVE_INI`), round one V(2,2) cycle of L1-Jacobi.

PMIS draws its own weights, so the C/F split is no function of the
matrix alone: the structure is an INPUT here, as the kept `P` was for
`reference_classical_reuse.py`. `hierarchy` takes each level's `P` from
the hierarchy under test and gives the operators, L1 diagonals and
term counts that `solve` and the comparisons read.

A split that is an input can still be WRONG, and a `P` taken from the
program cannot say so (PR 47's first hierarchy passed every comparison
above and took 138 iterations where 9 do: its PMIS made F every point
that merely INFLUENCED a C point, which left points with nothing to
interpolate from). So two more pieces, which take nothing from the
program but the matrix:

- `split_faults(A, strong, cf, P)`: what any PMIS split and the `P`
  built on it have to satisfy whatever the weights: no C point that
  depends on nothing, and no F point that depends on something and has
  an empty row of `P` (two C points may depend on each other: a point
  that only influences a C point is not made F by it);
- `own_hierarchy(A, keys)`: the reference's OWN hierarchy from the
  fine matrix alone (its own PMIS after hypre's par_coarsen.c, one
  aggressive level of PMIS over two-step dependence with Stueben's
  multipass interpolation, standard interpolation below, truncated),
  whose iteration count under `solve` is the yardstick the program's
  count is held to.

Departures from the reference's source, each where it is made:
`strength` has no weighting by the C/F split's random numbers (that is
the selector's, not the mask's); `solve` orthogonalises by modified
Gram-Schmidt in float64 where the program runs CGS2 in float32 (the
same Krylov space); its coarsest level takes `coarsest_sweeps` sweeps
of L1-Jacobi from zero, and 0 gives what a NOSOLVER coarse solver that
returns zero gives.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp



def csr(row_offsets, col_indices, values, cols=None) -> sp.csr_matrix:
    """The arrays as a float64 scipy matrix, sorted, duplicates summed."""
    rows = int(np.asarray(row_offsets).shape[0]) - 1
    M = sp.csr_matrix((np.asarray(values, dtype=np.float64),
                       np.asarray(col_indices), np.asarray(row_offsets)),
                      shape=(rows, rows if cols is None else int(cols)))
    M.sum_duplicates()
    M.sort_indices()
    return M


def _rows_of(M: sp.csr_matrix):
    return np.repeat(np.arange(M.shape[0]), np.diff(M.indptr))


def strength(A: sp.csr_matrix, theta: float, max_row_sum: float):
    """(mask over A's entries, rows the row-sum rule weakened)."""
    n = A.shape[0]
    rows, cols, vals = _rows_of(A), A.indices, A.data
    diag = A.diagonal()
    sgn = np.where(diag < 0, -1.0, 1.0)
    offd = rows != cols
    coupling = np.where(offd, -vals * sgn[rows], 0.0)
    # reduceat reads the NEXT row's first entry for an empty row
    row_max = np.where(np.diff(A.indptr) > 0, np.maximum.reduceat(
        np.append(coupling, 0.0), A.indptr[:-1]), 0.0)
    strong = offd & (coupling > 0) & (coupling >= theta * row_max[rows])
    weak = np.zeros(n, bool)
    if max_row_sum < 1.0:
        row_sum = np.bincount(rows, weights=vals, minlength=n)
        weak = np.abs(row_sum) > max_row_sum * np.abs(diag)
        strong &= ~weak[rows]
    return strong, int(np.count_nonzero(weak))


def truncate(P: sp.csr_matrix, max_elements: int) -> sp.csr_matrix:
    """P with at most `max_elements` entries a row."""
    n = P.shape[0]
    rows, vals = _rows_of(P), P.data
    # within each row by descending magnitude, stable: the earlier
    # column wins a tie
    order = np.lexsort((np.arange(vals.shape[0]), -np.abs(vals), rows))
    rank = np.empty(vals.shape[0], np.int64)
    rank[order] = np.arange(vals.shape[0]) - P.indptr[rows[order]]
    keep = rank < max_elements
    row_sum = np.bincount(rows, weights=vals, minlength=n)
    kept_sum = np.bincount(rows, weights=np.where(keep, vals, 0.0),
                           minlength=n)
    scale = np.where(kept_sum == 0, 1.0,
                     row_sum / np.where(kept_sum == 0, 1.0, kept_sum))
    out = sp.csr_matrix(((vals * scale[rows])[keep],
                         (rows[keep], P.indices[keep])), shape=P.shape)
    out.sort_indices()
    return out


def l1_diagonal(A: sp.csr_matrix):
    diag = A.diagonal()
    off = np.asarray(abs(A).sum(axis=1)).ravel() - np.abs(diag)
    return diag + np.sign(diag) * off


def galerkin(A: sp.csr_matrix, P: sp.csr_matrix):
    """(P^T A P, the most products r a p one of its entries sums)."""
    Ac = sp.csr_matrix(P.T @ (A @ P))
    Ac.sum_duplicates()
    Ac.sort_indices()
    ones = [sp.csr_matrix((np.ones(M.nnz, np.int64), M.indices, M.indptr),
                          shape=M.shape) for M in (P, A)]
    return Ac, int((ones[0].T @ (ones[1] @ ones[0])).max())


def hierarchy(row_offsets, col_indices, values, prolongators) -> dict:
    """What the preset's cycle runs on, over the hierarchy's own `P`
    (one (row_offsets, col_indices, values, columns) a level, the fine
    level's first): `operators` (the fine one and every Galerkin
    product), `prolongators`, `l1_diagonals` (one an operator), `terms`
    (as `galerkin` counts them, 0 for the fine level) and `scales`: the
    largest entry of |P|^T |A| |P| chained from the fine level, the size
    of the products a coarse entry sums. A Galerkin entry of an
    M-matrix is a difference of such products (a coarse diagonal of
    this operator is thousands of times smaller than what cancelled in
    it), so a rounding is measured against them and not against the
    entry that is left."""
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
            "l1_diagonals": [l1_diagonal(Ak) for Ak in operators]}


def cycle(levels: dict, b, sweeps: int = 2, coarsest_sweeps: int = 2,
          level: int = 0):
    """One V(sweeps, sweeps) cycle of L1-Jacobi from a zero guess."""
    A = levels["operators"][level]
    d = levels["l1_diagonals"][level]

    def smooth(x, count):
        for _ in range(count):
            x = x + (b - A @ x) / d
        return x

    x = np.zeros_like(b)
    if level == len(levels["operators"]) - 1:
        return smooth(x, coarsest_sweeps)
    P = levels["prolongators"][level]
    x = smooth(x, sweeps)
    x = x + P @ cycle(levels, P.T @ (b - A @ x), sweeps, coarsest_sweeps,
                      level + 1)
    return smooth(x, sweeps)


def solve(levels: dict, b, restart: int = 10, tolerance: float = 1e-6,
          max_iters: int = 100, sweeps: int = 2, coarsest_sweeps: int = 2):
    """(x, iterations, converged) of right-preconditioned flexible
    GMRES(restart); an iteration is one Arnoldi step, and the estimate
    |g[i+1]| of the residual stops it."""
    A = levels["operators"][0]
    b = np.asarray(b, dtype=np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    beta = float(np.linalg.norm(r))
    stop = tolerance * beta
    done = 0
    while done < max_iters and beta > stop:
        V = [r / beta]
        Z, H = [], np.zeros((restart + 1, restart))
        g = np.zeros(restart + 1)
        g[0] = beta
        cs, sn = np.zeros(restart), np.zeros(restart)
        i = 0
        while i < restart and done < max_iters:
            z = cycle(levels, V[i], sweeps, coarsest_sweeps)
            w = A @ z
            for j in range(i + 1):
                H[j, i] = float(w @ V[j])
                w = w - H[j, i] * V[j]
            H[i + 1, i] = float(np.linalg.norm(w))
            Z.append(z)
            V.append(w / (H[i + 1, i] or 1.0))
            for j in range(i):
                hj = cs[j] * H[j, i] + sn[j] * H[j + 1, i]
                H[j + 1, i] = -sn[j] * H[j, i] + cs[j] * H[j + 1, i]
                H[j, i] = hj
            denom = float(np.hypot(H[i, i], H[i + 1, i])) or 1.0
            cs[i], sn[i] = H[i, i] / denom, H[i + 1, i] / denom
            H[i, i], H[i + 1, i] = denom, 0.0
            g[i + 1] = -sn[i] * g[i]
            g[i] = cs[i] * g[i]
            i += 1
            done += 1
            if abs(g[i]) <= stop:
                break
        y = np.linalg.solve(np.triu(H[:i, :i]), g[:i])
        x = x + sum(yk * zk for yk, zk in zip(y, Z))
        if abs(g[i]) <= stop:
            return x, done, True
        r = b - A @ x
        beta = float(np.linalg.norm(r))
    return x, done, beta <= stop


# -- what takes nothing from the program but the matrix ----------------

def _mask_matrix(A: sp.csr_matrix, strong) -> sp.csr_matrix:
    S = sp.csr_matrix((np.asarray(strong, bool).astype(np.int32),
                       A.indices.copy(), A.indptr.copy()), shape=A.shape)
    S.eliminate_zeros()
    return S


def two_step(S: sp.csr_matrix) -> sp.csr_matrix:
    """i depends on j in exactly two steps of S (no diagonal)."""
    S2 = sp.csr_matrix(S @ S)
    S2.setdiag(0)
    S2.eliminate_zeros()
    S2.data[:] = 1
    return S2


def split_faults(A: sp.csr_matrix, strong, cf, P: sp.csr_matrix) -> dict:
    """Counts that have to be 0 for any PMIS split over `strong`
    (a mask over A's entries) and the `P` built on it."""
    depends = np.diff(_mask_matrix(A, strong).indptr) > 0
    C = np.asarray(cf) == 1
    empty = np.diff(P.indptr) == 0
    return {
        "c_without_dependency": int(np.count_nonzero(C & ~depends)),
        "f_left_alone": int(np.count_nonzero(~C & depends & empty))}


def pmis(G: sp.csr_matrix, rng, start_fine=None):
    """A C/F split (1 / 0) of the dependence graph G (row i: what i
    depends on) after hypre's par_coarsen.c: weight = the points that
    depend on i + a random number; a point nothing depends on, or in
    `start_fine`, starts F; then, until all are decided, every undecided
    local maximum over G | G^T becomes C and every undecided point that
    DEPENDS on a new C point becomes F."""
    n = G.shape[0]
    both = sp.csr_matrix(G + G.T)
    rows = _rows_of(both)
    influence = np.asarray(G.sum(axis=0)).ravel()
    w = influence + rng.random(n)
    cf = np.full(n, -1)
    cf[influence == 0] = 0
    if start_fine is not None:
        cf[start_fine] = 0
    while (cf == -1).any():
        undecided = cf == -1
        best = np.full(n, -1.0)
        np.maximum.at(best, rows, np.where(undecided, w, -1.0)[both.indices])
        new_c = undecided & (w > best)
        cf[new_c] = 1
        cf[(cf == -1) & ((G @ new_c.astype(np.int32)) > 0)] = 0
    return cf


def _injection(C):
    cols = np.cumsum(C) - 1
    return sp.csr_matrix((np.ones(int(C.sum())),
                          (np.flatnonzero(C), cols[C])),
                         shape=(C.shape[0], int(C.sum())))


def standard_interpolation(A: sp.csr_matrix, S: sp.csr_matrix, cf):
    """Stueben's standard interpolation: direct interpolation of the
    row in which every strong F neighbour k was replaced by its own
    row over a_kk (so a C point two steps away interpolates too)."""
    C = np.asarray(cf) == 1
    F = sp.diags((~C).astype(np.float64))
    strong = A.multiply(S > 0).tocsr()
    ff = (F @ strong @ F).tocsr()
    hat = (A - ff @ sp.diags(1.0 / A.diagonal()) @ A).tocsr()
    pattern = sp.csr_matrix((strong != 0).astype(np.int32)
                            + (ff != 0).astype(np.int32)
                            @ (strong != 0).astype(np.int32))
    pattern = sp.csr_matrix(pattern @ sp.diags(C.astype(np.int32), dtype=np.int32))
    pattern.eliminate_zeros()
    pattern.data[:] = 1
    to_c = hat.multiply(pattern).tocsr()
    d = hat.diagonal()
    off = np.asarray(hat.sum(axis=1)).ravel() - d
    c_sum = np.asarray(to_c.sum(axis=1)).ravel()
    alpha = np.where(c_sum != 0, off / np.where(c_sum == 0, 1.0, c_sum), 0.0)
    W = (F @ sp.diags(-alpha / d) @ to_c).tocsc()[:, np.flatnonzero(C)]
    return sp.csr_matrix(W + _injection(C))


def multipass_interpolation(A: sp.csr_matrix, S: sp.csr_matrix, cf):
    """Stueben's multipass interpolation: an F point that depends on C
    points interpolates from them directly; pass by pass, every other
    one through the rows of the points it depends on that have one."""
    n = A.shape[0]
    C = np.asarray(cf) == 1
    strong = A.multiply(S > 0).tocsr()
    d = A.diagonal()
    off = np.asarray(A.sum(axis=1)).ravel() - d
    far = 1 << 30
    passes = np.where(C, 0, far)
    rows = _rows_of(S)
    while True:
        nearest = np.full(n, far)
        np.minimum.at(nearest, rows, passes[S.indices])
        new = np.where(C, 0, np.minimum(passes, nearest + 1))
        if np.array_equal(new, passes):
            break
        passes = new
    P = _injection(C)
    for p in range(1, int(passes[passes < far].max(initial=0)) + 1):
        through = (sp.diags((passes == p).astype(np.float64)) @ strong
                   @ sp.diags((passes < p).astype(np.float64))).tocsr()
        total = np.asarray(through.sum(axis=1)).ravel()
        alpha = np.where(total != 0,
                         off / np.where(total == 0, 1.0, total), 0.0)
        P = P + sp.diags(-alpha / d) @ (through @ P)
    return sp.csr_matrix(P)


def own_hierarchy(row_offsets, col_indices, values, keys: dict,
                  seed: int = 0, aggressive_levels: int = 1) -> dict:
    """`hierarchy`'s dictionary for a hierarchy that is the reference's
    own from the C/F split on, under the preset's `strength_threshold`,
    `max_row_sum`, `interp_max_elements`, down to `min_coarse_rows` 2."""
    rng = np.random.default_rng(seed)
    operators, kept = [csr(row_offsets, col_indices, values)], []
    while operators[-1].shape[0] > 2 and len(operators) < 50:
        A = operators[-1]
        mask, _weak = strength(A, keys["strength_threshold"],
                               keys["max_row_sum"])
        S = _mask_matrix(A, mask)
        alone = np.diff(S.indptr) == 0
        if len(kept) < aggressive_levels:
            cf = pmis(two_step(S), rng, start_fine=alone)
            P = multipass_interpolation(A, S, cf)
        else:
            cf = pmis(S, rng, start_fine=alone)
            P = standard_interpolation(A, S, cf)
        if P.shape[1] < 2 or P.shape[1] == A.shape[0]:
            break
        P = truncate(P, keys["interp_max_elements"])
        kept.append(P)
        operators.append(galerkin(A, P)[0])
    return {"operators": operators, "prolongators": kept,
            "l1_diagonals": [l1_diagonal(Ak) for Ak in operators]}

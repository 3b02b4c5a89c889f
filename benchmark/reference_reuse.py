"""The plain reference of a structure-reuse re-setup: numpy + scipy,
float64, nothing of `amgx_tpu`.

`structure_reuse_levels=-1` keeps the aggregates of the first setup and
recomputes, from each step's values, every level's Galerkin operator,
the bound every Chebyshev tau divides by, and the coarsest level's
dense matrix. The structure IS what is reused, so it is an input here:
the aggregates map of each level (for every fine row the number of its
aggregate), taken from the level under test or made from the grid by
`paired_aggregates`.

`rebuild` returns, for the fine values it is given,

- each level's operator `P^T A P`, with `P` piecewise constant over
  the aggregates (`P[i, agg[i]] = 1`), as a scipy CSR matrix with
  sorted columns and duplicates summed;
- each level's Gershgorin bound, the largest absolute row sum (of the
  fine level too: level k's taus divide by level k's bound);
- the coarsest level as a dense matrix.

`correct` in a cell stays what `reference.py` decides (the float64
residual of the answer). This file is what the tests and a builder's
chip comparison hold the re-set-up HIERARCHY to: a solve preconditioned
by a stale coarse level still converges, so the residual alone would
not see a resetup that skipped a level.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def paired_aggregates(fine_shape, axes):
    """(aggregates, coarse rows) of a grid (x fastest) whose cells are
    paired along each of `axes`: cell (x, y, z) goes to (x // 2, ...)
    along a paired axis, an odd extent leaving its last cell alone."""
    nx, ny, nz = (int(e) for e in fine_shape)
    i = np.arange(nx * ny * nz, dtype=np.int64)
    cell = [i % nx, (i // nx) % ny, i // (nx * ny)]
    ext = [nx, ny, nz]
    for a in axes:
        cell[a] = cell[a] // 2
        ext[a] = (ext[a] + 1) // 2
    return (cell[2] * ext[1] + cell[1]) * ext[0] + cell[0], \
        ext[0] * ext[1] * ext[2]


def galerkin(A: sp.csr_matrix, aggregates, coarse_rows: int,
             slab_rows: int = 0) -> sp.csr_matrix:
    """`P^T A P` for the piecewise-constant `P` of `aggregates`. With
    `slab_rows` the fine rows are taken that many at a time (whole
    z-planes of a large grid) and the slabs' products summed, which is
    the same matrix: sum_s P_s^T A_s P."""
    n = A.shape[0]
    agg = np.asarray(aggregates, dtype=np.int64)
    assert agg.shape == (n,) and agg.min() >= 0 and agg.max() < coarse_rows
    P = sp.csr_matrix((np.ones(n), (np.arange(n), agg)),
                      shape=(n, coarse_rows))
    step = int(slab_rows) or n
    Ac = sp.csr_matrix((coarse_rows, coarse_rows), dtype=np.float64)
    for r0 in range(0, n, step):
        r1 = min(r0 + step, n)
        Ac = Ac + P[r0:r1].T @ (A[r0:r1] @ P)
    Ac = sp.csr_matrix(Ac)
    Ac.sum_duplicates()
    Ac.sort_indices()
    return Ac


def gershgorin(A: sp.spmatrix) -> float:
    """The largest absolute row sum."""
    return float(np.max(np.asarray(abs(A).sum(axis=1))))


def rebuild(row_offsets, col_indices, values, aggregates,
            slab_rows: int = 0) -> dict:
    """The hierarchy a structure-reuse re-setup has to give for these
    fine values. `aggregates` is one (map, coarse rows) pair per level,
    the fine level's first. Returns `operators` (the fine level's and
    every coarse one's, in order), `bounds` (one per operator) and
    `coarsest` (the last operator, dense)."""
    n = int(np.asarray(row_offsets).shape[0]) - 1
    A = sp.csr_matrix((np.asarray(values, dtype=np.float64),
                       np.asarray(col_indices), np.asarray(row_offsets)),
                      shape=(n, n))
    A.sum_duplicates()
    A.sort_indices()
    operators = [A]
    for k, (agg, coarse_rows) in enumerate(aggregates):
        operators.append(galerkin(operators[-1], agg, int(coarse_rows),
                                  slab_rows if k == 0 else 0))
    return {"operators": operators,
            "bounds": [gershgorin(Ak) for Ak in operators],
            "coarsest": operators[-1].toarray()}


def largest_difference(A: sp.spmatrix, ref: sp.spmatrix) -> float:
    """Largest entry of |A - ref| over the largest entry of |ref|: how
    far an operator is from the reference's, on the scale of the level
    (an entry that is missing on either side counts whole)."""
    diff = abs(sp.csr_matrix(A) - sp.csr_matrix(ref))
    scale = abs(ref).max()
    return float(diff.max() / scale) if diff.nnz else 0.0

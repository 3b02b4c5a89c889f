"""The plain reference of the `hpcg-p27-192` configuration.

HPCG 3.1's solver written from its equations in numpy, float64
throughout: conjugate gradients (CG_ref.cpp) preconditioned by one
V-cycle of a multigrid (ComputeMG_ref.cpp) whose every level is smoothed
by one symmetric Gauss-Seidel sweep before and one after
(ComputeSYMGS_ref.cpp), the coarsest level by one symmetric sweep in
place of a solve. Nothing of `amgx_tpu` is imported; the operator is
not even a matrix here. A level's operator is 27 coefficients c[dz+1,
dy+1, dx+1] of a box stencil with Dirichlet truncation (a neighbour
outside the grid is left out; GenerateProblem_ref.cpp: 26 on the
diagonal, -1 elsewhere), applied as shifted slices of the (nz, ny, nx)
array, x fastest.

Where this departs from HPCG, as the configuration's `assumed` says, a
comment beside the line says so: Galerkin coarse operators over 2x2x2
aggregates, a sweep ordered by 8 parity colors, a stop on the residual.

A 192^3 iteration is about 6 s of numpy: this is for the tests and for
one comparison at size, not for any timed path.
"""
from __future__ import annotations

import itertools

import numpy as np

SHIFTS = tuple(itertools.product((-1, 0, 1), repeat=3))      # (dz, dy, dx)


def stencil27():
    """HPCG's operator: 26 on the diagonal, -1 to each of the 26 box
    neighbours."""
    c = np.full((3, 3, 3), -1.0)
    c[1, 1, 1] = 26.0
    return c


def galerkin(c):
    """The coarse stencil of P^T A P for piecewise-constant P over 2x2x2
    aggregates: c'(D) = sum of c(d) over fine shifts d and positions p
    in the block with (p + d) div 2 = D, per axis. HPCG re-discretises
    (its coarse operator is stencil27 again); an AMG library multiplies
    out, and the centre comes to 8 * 26 - 56 = 152."""
    out = np.zeros((3, 3, 3))
    for d in SHIFTS:
        for p in itertools.product((0, 1), repeat=3):
            D = tuple((pa + da) // 2 for pa, da in zip(p, d))
            out[D[0] + 1, D[1] + 1, D[2] + 1] += c[d[0] + 1, d[1] + 1,
                                                   d[2] + 1]
    return out


def apply(c, x):
    """y = A x: 27 shifted slices of the zero-padded array."""
    nz, ny, nx = x.shape
    xp = np.pad(x, 1)
    y = np.zeros_like(x)
    for dz, dy, dx in SHIFTS:
        y += c[dz + 1, dy + 1, dx + 1] * xp[1 + dz:1 + dz + nz,
                                            1 + dy:1 + dy + ny,
                                            1 + dx:1 + dx + nx]
    return y


def _lattice(par, shift, extent):
    """The slice, in the padded array, of the points of parity `par`
    moved by `shift` along an axis of `extent` points."""
    count = len(range(par, extent, 2))
    start = 1 + par + shift
    return slice(start, start + 2 * count - 1, 2)


def symgs(c, b, x):
    """One symmetric Gauss-Seidel sweep, in place on a copy:
    x <- x + (b - A x) / a0 on one parity sub-lattice at a time, the 8
    colors px + 2 py + 4 pz ascending, then descending. HPCG's
    reference sweeps the rows in lexicographic order and back; it
    permits reordering, and a sub-lattice's points are not coupled, so
    each color step is exact Gauss-Seidel for its rows."""
    shape = x.shape
    xp = np.pad(x, 1)
    colors = sorted(itertools.product((0, 1), repeat=3),
                    key=lambda p: p[2] + 2 * p[1] + 4 * p[0])
    for p in colors + colors[::-1]:
        own = tuple(_lattice(pa, 0, e) for pa, e in zip(p, shape))
        if 0 in xp[own].shape:          # an axis of one point has no odd
            continue
        r = b[tuple(slice(pa, None, 2) for pa in p)].copy()
        for d in SHIFTS:
            nb = tuple(_lattice(pa, da, e)
                       for pa, da, e in zip(p, d, shape))
            r -= c[d[0] + 1, d[1] + 1, d[2] + 1] * xp[nb]
        xp[own] += r / c[1, 1, 1]
    return xp[1:-1, 1:-1, 1:-1].copy()


def restrict(r):
    """R r: the sum over each 2x2x2 block (R = P^T). HPCG injects the
    block's first point."""
    nz, ny, nx = r.shape
    return r.reshape(nz // 2, 2, ny // 2, 2, nx // 2, 2).sum(axis=(1, 3, 5))


def prolong(xc):
    """P xc: every point of a block takes the block's value."""
    return xc.repeat(2, axis=0).repeat(2, axis=1).repeat(2, axis=2)


class Multigrid:
    """`levels` operators, each the Galerkin stencil of the one above
    (HPCG: 4 levels, three coarsenings by 2 in every axis)."""

    def __init__(self, shape, levels: int = 4, c=None):
        nz, ny, nx = shape
        if any(e % 2 ** (levels - 1) for e in shape):
            raise ValueError(f"{shape} does not halve {levels - 1} times")
        self.shape = (nz, ny, nx)
        self.stencils = [stencil27() if c is None else np.asarray(c, float)]
        for _ in range(levels - 1):
            self.stencils.append(galerkin(self.stencils[-1]))

    def vcycle(self, b, level: int = 0):
        """x = M b, zero initial guess: one sweep before and one after
        on every level, one sweep alone on the coarsest."""
        c = self.stencils[level]
        x = symgs(c, b, np.zeros_like(b))
        if level + 1 == len(self.stencils):
            return x
        xc = self.vcycle(restrict(b - apply(c, x)), level + 1)
        return symgs(c, b, x + prolong(xc))

    def pcg(self, b, tolerance: float, max_iters: int = 500):
        """CG_ref.cpp with zero initial guess: (x, iterations, history)
        with history[k] = ||r_k|| / ||r_0|| of the recurrence residual,
        stopped when that is at or under `tolerance`. HPCG's timed sets
        run a fixed 50 iterations instead."""
        c = self.stencils[0]
        b = np.asarray(b, np.float64).reshape(self.shape)
        x = np.zeros_like(b)
        r = b.copy()
        normr0 = np.linalg.norm(r)
        history = [1.0]
        p = rtz = None
        k = 0
        while k < max_iters and history[-1] > tolerance:
            z = self.vcycle(r)
            old, rtz = rtz, float(np.vdot(r, z))
            p = z if p is None else z + (rtz / old) * p
            Ap = apply(c, p)
            alpha = rtz / float(np.vdot(p, Ap))
            x += alpha * p
            r -= alpha * Ap
            k += 1
            history.append(float(np.linalg.norm(r) / normr0))
        return x.reshape(-1), k, history

    def residual(self, b, x):
        """||b - A x||_2 / ||b||_2."""
        b3 = np.asarray(b, np.float64).reshape(self.shape)
        r = b3 - apply(self.stencils[0], np.asarray(
            x, np.float64).reshape(self.shape))
        return float(np.linalg.norm(r) / np.linalg.norm(b3))

    def refine(self, b, tolerance: float, inner_tolerance: float,
               max_steps: int = 20, max_iters: int = 500):
        """The configuration's defect-correction loop round `pcg`, all
        in float64: (x, PCG iterations of each step, true relative
        residual after each step). Not HPCG's: the configuration solves
        in float32 inside it."""
        b = np.asarray(b, np.float64).reshape(-1)
        x = np.zeros_like(b)
        r = b.copy()
        steps, residuals = [], []
        while len(steps) < max_steps and (
                not residuals or residuals[-1] > tolerance):
            d, k, _history = self.pcg(r, inner_tolerance, max_iters)
            x += d
            r = b - apply(self.stencils[0],
                          x.reshape(self.shape)).reshape(-1)
            steps.append(k)
            residuals.append(float(np.linalg.norm(r) / np.linalg.norm(b)))
        return x, steps, residuals

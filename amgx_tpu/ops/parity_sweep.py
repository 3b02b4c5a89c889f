"""A colored Gauss-Seidel sweep whose color step touches its own rows
only.

The masked form of solvers/multicolor.py computes a whole SpMV for
every color and keeps one color's rows of it: a symmetric sweep is
2 x colors passes over the operator where the arithmetic of a sweep is
2. On a grid colored by parity (ops/coloring.parity_coloring) the
points of one (z, y) parity are every other grid row of every other
plane, and a box stencil couples such a row set to the three others by
shifts of 0 or 1 in its own index. So the vectors and the DIA values
are cut once into the four row sets, whole grid rows along x, and a
step reads the neighbours' arrays shifted and writes its own.

The x parities stay interleaved in the lanes: parting them would be a
permutation of the minor dimension at every cut and join. The two
colors of a row set are consecutive in the order of the colors, and
the second is coupled to the first within the row set by the shifts
(+-1, 0, 0) alone. One step therefore takes both: the residual of all
the row set's points from the vector as it stands, the update of the
first color's lanes, the second color's residual corrected by what the
first moved, and the second's update. That is exact Gauss-Seidel in
the order of the colors, and a symmetric sweep reads every coefficient
twice. Where only faces couple there are two colors and they alternate
between row sets, so a step updates one parity of lanes and a sweep
reads a coefficient once a color.

Layout. The row sets have one shape (ceil(nz / 2), ceil(ny / 2), nx):
an odd extent leaves the odd row set a last layer outside the grid,
whose values, diagonal inverse and right-hand side are zero, so it
stays zero. The running arrays carry a zero layer all round, which
serves the Dirichlet truncation: a neighbour outside the grid reads as
zero, and the coefficient there is zero too.

The steps of a sweep are one rolled loop over a switch with one branch
a row set: the row set and the lanes that go first are data, what
depends on the row set's parities (static slices of the neighbours and
of the coefficients) is in its branch, and the stencil's terms are
traced once a row set, not once a step.

Plain XLA, so it runs where the matrix does: any backend, under
`jax.vmap`, in any dtype.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .coloring import Coloring, box_shifts, faces_only, parity_color


@dataclasses.dataclass(frozen=True)
class ParityPlan:
    """What is static about a sweep: the grid, the stencil's shifts in
    DIA order, the row sets (pz, py) in color order, and whether only
    faces couple."""
    shape: Tuple[int, int, int]                    # (nx, ny, nz)
    shifts: Tuple[Tuple[int, int, int], ...]       # (dx, dy, dz) per offset
    rows: Tuple[Tuple[int, int], ...]              # (pz, py), ascending
    faces: bool

    @property
    def sub_shape(self):
        nx, ny, nz = self.shape
        return (-(-nz // 2), -(-ny // 2), nx)

    def steps(self, symmetric: bool):
        """(row set's index, parity of the lanes that go first) of every
        step, in the order of the colors and back."""
        if self.faces:
            up = [(i, (c + pz + py) % 2) for c in (0, 1)
                  for i, (pz, py) in enumerate(self.rows)]
            down = up[::-1]
        else:
            up = [(i, 0) for i in range(len(self.rows))]
            down = [(i, 1) for i, _ in up[::-1]]
        return up + down if symmetric else up


def make_plan(A, coloring: Coloring) -> Optional[ParityPlan]:
    """The plan for A under its parity coloring, or None where the
    matrix has no DIA box stencil on that grid (the masked form then
    runs)."""
    if coloring.grid is None or getattr(A, "dia_vals", None) is None \
            or A.is_block or A.has_external_diag \
            or tuple(A.grid_shape or ()) != coloring.grid \
            or 0 not in A.dia_offsets:
        return None
    shifts = box_shifts(A.dia_offsets, coloring.grid)
    if shifts is None:
        return None
    faces = faces_only(shifts)
    _nx, ny, nz = coloring.grid
    rows = [(pz, py) for pz in range(min(nz, 2)) for py in range(min(ny, 2))]
    rows.sort(key=lambda p: parity_color(0, p[1], p[0], coloring.grid,
                                         faces))
    return ParityPlan(coloring.grid, shifts, tuple(rows), faces)


def _cut(v3, plan: ParityPlan):
    """The row sets of a (..., nz, ny, nx) array, stacked in plan order
    in front of the last three dimensions: (..., rows, mz, my, nx),
    zero beyond an odd extent. The z parities are parted by a reshape
    of the major dimension, the y parities by folding a pair of grid
    rows into one row of 2 nx lanes and slicing it; no entry moves
    within its grid row."""
    mz, my, nx = plan.sub_shape
    lead = v3.shape[:-3]
    _nx, ny, nz = plan.shape
    v3 = jnp.pad(v3, [(0, 0)] * len(lead) + [
        (0, 2 * mz - nz), (0, 2 * my - ny), (0, 0)])
    r = v3.reshape(lead + (mz, 2, my, 2 * nx))
    return jnp.stack([r[..., :, pz, :, py * nx:(py + 1) * nx]
                      for pz, py in plan.rows], axis=len(lead))


def _join(rows, plan: ParityPlan):
    """The (nz, ny, nx) array of its (rows, mz, my, nx) row sets: `_cut`
    backwards."""
    mz, my, nx = plan.sub_shape
    _nx, ny, nz = plan.shape
    have = {p: rows[i] for i, p in enumerate(plan.rows)}
    zeros = jnp.zeros_like(rows[0])
    r = jnp.stack([jnp.concatenate(
        [have.get((pz, py), zeros) for py in (0, 1)], axis=-1)
        for pz in (0, 1)], axis=1)
    return r.reshape(2 * mz, 2 * my, nx)[:nz, :ny]


@functools.partial(jax.jit, static_argnames=("plan",))
def build_slabs(dia_vals, dinv, plan: ParityPlan):
    """The operator by row set, in plan order: {"vals": a (k, mz, my, nx)
    array a row set, "dinv": a (mz, my, nx) array a row set}. Arrays of
    their own, not slices of one: a step's branch is handed its row
    set's coefficients as they lie, where a slice of a stacked array
    would be copied on the way into the switch, every sweep."""
    nx, ny, nz = plan.shape
    n = nx * ny * nz
    k = len(plan.shifts)
    vals = _cut(dia_vals.reshape(k, -1)[:, :n].reshape(k, nz, ny, nx), plan)
    dinv = _cut(dinv.reshape(nz, ny, nx), plan)
    rows = range(len(plan.rows))
    return {"vals": tuple(vals[:, i] for i in rows),
            "dinv": tuple(dinv[i] for i in rows)}


def _step(plan: ParityPlan, slabs, bs, i: int, omega):
    """The step on row set i as a function of (X, first): the haloed
    row sets, an array each, and which lanes go first; returns the row
    set's new haloed array. What depends on the row set's parities
    (which arrays the shifts reach, and at which planes and rows) is
    static in here."""
    mz, my, nx = plan.sub_shape
    row = plan.rows[i]
    index = {p: k for k, p in enumerate(plan.rows)}
    vals, scale, b = slabs["vals"][i], omega * slabs["dinv"][i], bs[i]
    # the shifts along the grid row itself: what couples a row set's
    # two colors
    along = [(t, s[0]) for t, s in enumerate(plan.shifts)
             if s[0] and not s[1] and not s[2]]

    def reach(p, d, m):
        # in sub-lattice index the neighbour is at j + (p + d) // 2:
        # -1 or 0 from an even point, 0 or +1 from an odd one
        lo = 1 + (p + d) // 2
        return (p + d) % 2, slice(lo, lo + m)

    def step(X, first):
        r = b
        for t, (dx, dy, dz) in enumerate(plan.shifts):
            (sz, wz), (sy, wy) = reach(row[0], dz, mz), reach(row[1], dy, my)
            if (sz, sy) in index:        # an axis of extent 1 has no odd
                r = r - vals[t] * X[index[sz, sy]][wz, wy,
                                                   1 + dx:1 + dx + nx]
        moved = jnp.where(first, scale * r, 0)
        if not plan.faces:
            # the other lanes: their residual once the first have moved
            halo = jnp.pad(moved, [(0, 0), (0, 0), (1, 1)])
            for t, dx in along:
                r = r - vals[t] * halo[..., 1 + dx:1 + dx + nx]
            moved = jnp.where(first, moved, scale * r)
        return jnp.pad(X[i][1:-1, 1:-1, 1:-1] + moved, [(1, 1)] * 3)
    return step


def _relax(plan: ParityPlan, slabs, bs, X, omega, symmetric: bool):
    """The steps of a sweep on the haloed row sets X, right-hand side
    bs by row set."""
    steps = np.asarray(plan.steps(symmetric), np.int32)
    row_of, first_of = jnp.asarray(steps[:, 0]), jnp.asarray(steps[:, 1])
    lane = jax.lax.broadcasted_iota(jnp.int32, (plan.shape[0],), 0) & 1
    branches = [_step(plan, slabs, bs, i, omega)
                for i in range(len(plan.rows))]

    def step(s, X):
        i = row_of[s]
        # the row sets as arrays of their own: 27 windows into the one
        # stacked array are 27 reads of it, windows into its own array
        # are one (4.7 ms a symmetric sweep at 192^3 against 7.2)
        apart = jax.lax.optimization_barrier(tuple(X))
        new = jax.lax.switch(i, branches, apart, lane == first_of[s])
        return jax.lax.dynamic_update_slice(
            X, new[None], (i,) + (jnp.int32(0),) * 3)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(len(steps)), step, X)


def sweep(plan: ParityPlan, slabs, b, x, omega, symmetric: bool):
    """One sweep over the colors in ascending order, and back in
    descending order if `symmetric`: exact Gauss-Seidel in the order
    of the colors, x <- x + omega dinv (b - A x) on one color's points
    at a time."""
    nx, ny, nz = plan.shape
    X = jnp.pad(_cut(x.reshape(nz, ny, nx), plan), [(0, 0)] + [(1, 1)] * 3)
    X = _relax(plan, slabs, _cut(b.reshape(nz, ny, nx), plan), X, omega,
               symmetric)
    return _join(X[:, 1:-1, 1:-1, 1:-1], plan).reshape(-1)

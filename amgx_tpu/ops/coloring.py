"""Parallel graph coloring for multicolor smoothers.

Analog of src/matrix_coloring/ (10 schemes, 6860 LoC of CUDA; registry
src/core.cu:669-678). The workhorse is Jones-Plassmann-Luby expressed as
segment-max fixed points (the same machinery as PMIS/matching):

- MIN_MAX: per round, uncolored local *maxima* of a hash weight get the
  round's low color and local *minima* the round's high color (two colors
  per round, min_max.cu behavior);
- MULTI_HASH: several independent hashes per round (multi_hash.cu);
- MIN_MAX_2RING / GREEDY_MIN_MAX_2RING: the same fixed point run on the
  squared adjacency graph (distance-2 coloring, needed by ILU/DILU with
  reordering);
- ROUND_ROBIN / UNIFORM: trivial index-based colorings (round_robin.cu,
  uniform.cu);
- SERIAL_GREEDY_BFS: host-side deterministic greedy (quality reference).

A matrix that carries `grid_shape` and couples each point only to its
box neighbours (every offset within +-1 per axis) is colored by the
parity of its grid coordinates instead, under the default scheme's
name: 8 colors for a 27-point operator where JPL gives 33, 2 for face
couplings alone. It is chosen from the matrix, not by a knob; a matrix
without a grid is colored by JPL as ever. GRID_PARITY names the same
coloring for a configuration that counts on it: on any other matrix it
is an error where the default would turn to JPL without a word.

Returns a Coloring(row_colors, num_colors). Colorings are validated by
tests the way src/tests/valid_coloring.cu does: no edge joins two
vertices of one color.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry
from ..errors import BadParametersError
from ..matrix import CsrMatrix


@dataclasses.dataclass(frozen=True)
class Coloring:
    row_colors: jax.Array          # (n,) int32
    num_colors: int
    # (nx, ny, nz) when the colors are the parity classes of that grid
    # and the matrix's DIA diagonals are box shifts of it with no entry
    # wrapping round a grid row (parity_coloring): a smoother can then
    # take a color's points as every other grid row of every other
    # plane (ops/parity_sweep.py) instead of a mask over all rows
    grid: Optional[tuple] = None

    def color_counts(self):
        return jnp.bincount(self.row_colors, length=self.num_colors)


def _hash_w(n, salt: int):
    i = jnp.arange(n, dtype=jnp.uint32)
    h = (i + jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)) * \
        jnp.uint32(2654435761)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return h


def _sym_edges(A: CsrMatrix):
    rows, cols, _ = A.coo()
    offd = rows != cols
    r = jnp.concatenate([rows[offd], cols[offd]])
    c = jnp.concatenate([cols[offd], rows[offd]])
    order = jnp.argsort(r, stable=True)
    return r[order], c[order]


def _hash_w_np(n, salt: int):
    i = np.arange(n, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = (i + np.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)) * \
            np.uint32(2654435761)
        h = (h ^ (h >> 15)) * np.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
    return h


def _jpl_min_max_np(n: int, sr, sc, max_rounds: int, use_min: bool):
    """Host (numpy) twin of the JPL fixed point below — identical hash,
    round structure, and straggler handling, so colors are bit-equal.
    The host-setup hierarchy build (amg_host_setup) runs smoother
    setup on numpy-backed matrices; one eager XLA:CPU dispatch per
    round per color would otherwise dominate the whole classical setup
    (measured: ~minutes at 96^3)."""
    order = np.argsort(sr, kind="stable")
    sr, sc = sr[order], sc[order]
    ro = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(sr, minlength=n), out=ro[1:])
    colors = np.full(n, -1, np.int32)
    has_nbr = np.zeros(n, bool)
    has_nbr[sr] = True
    colors[~has_nbr] = 0
    next_color = 0

    def extract(colors, w, ncol, maximize):
        from ..matrix import _np_row_reduce
        un = colors < 0
        fill = np.uint32(0) if maximize else np.uint32(0xFFFFFFFF)
        wm = np.where(un, w, fill)
        op = np.maximum if maximize else np.minimum
        nbest = _np_row_reduce(op, wm[sc], ro, n, fill)
        take = un & ((w > nbest) if maximize else (w < nbest))
        colors[take] = ncol

    for rnd in range(max_rounds):
        if not (colors < 0).any():
            break
        w = _hash_w_np(n, rnd)
        extract(colors, w, next_color, True)
        next_color += 1
        if use_min:
            if not (colors < 0).any():
                break
            extract(colors, w, next_color, False)
            next_color += 1
    colors[colors < 0] = next_color
    num = int(colors.max()) + 1 if n else 0
    return Coloring(jnp.asarray(colors), num)


def _host_sym_edges(A: CsrMatrix):
    """Host (numpy) symmetrized off-diagonal edge lists via the
    mirrors, or None when the arrays cannot be served host-side."""
    from ..matrix import host_arrays
    ha = host_arrays(A.row_offsets, A.col_indices)
    if ha is None:
        return None
    ro, ci = ha
    rows = np.repeat(np.arange(A.num_rows, dtype=np.int32), np.diff(ro))
    offd = rows != ci
    return (np.concatenate([rows[offd], ci[offd]]),
            np.concatenate([ci[offd], rows[offd]]))


def _jpl_min_max(A: CsrMatrix, max_rounds: int = 64, use_min: bool = True,
                 edges=None):
    """Jones-Plassmann-Luby with (max, min) extraction per round."""
    n = A.num_rows
    if edges is None:
        he = _host_sym_edges(A)
        if he is not None:
            return _jpl_min_max_np(n, he[0], he[1], max_rounds, use_min)
    sr, sc = _sym_edges(A) if edges is None else edges
    colors = jnp.full((n,), -1, jnp.int32)
    has_nbr = jnp.zeros((n,), bool).at[sr].set(True)
    colors = jnp.where(~has_nbr, 0, colors)       # isolated: color 0
    next_color = 0
    for rnd in range(max_rounds):
        un = colors < 0
        if not bool(jnp.any(un)):
            break
        w = _hash_w(n, rnd)
        active = un[sr] & un[sc]
        nmax = jax.ops.segment_max(
            jnp.where(active, w[sc], jnp.uint32(0)), sr, num_segments=n,
            indices_are_sorted=True)
        is_max = un & (w > nmax)
        colors = jnp.where(is_max, next_color, colors)
        next_color += 1
        if use_min:
            un = colors < 0
            if not bool(jnp.any(un)):
                break
            active = un[sr] & un[sc]
            nmin = jax.ops.segment_min(
                jnp.where(active, w[sc], jnp.uint32(0xFFFFFFFF)), sr,
                num_segments=n, indices_are_sorted=True)
            is_min = un & (w < nmin)
            colors = jnp.where(is_min, next_color, colors)
            next_color += 1
    colors = jnp.where(colors < 0, next_color, colors)  # stragglers
    num = int(jnp.max(colors)) + 1 if n else 0
    return Coloring(colors.astype(jnp.int32), num)


def parity_color(px, py, pz, shape, faces_only: bool):
    """The color of the grid points whose coordinates have parities
    (px, py, pz): px + 2 py + 4 pz (an axis of extent 1 takes no bit,
    so the colors in use are dense), or (px + py + pz) % 2 where only
    faces couple. Works on ints and on arrays alike."""
    if faces_only:
        return (px + py + pz) % 2
    nx, ny, _nz = shape
    wy = 2 if nx > 1 else 1
    wz = wy * (2 if ny > 1 else 1)
    return px + wy * py + wz * pz


def box_shifts(dia_offsets, shape):
    """The (dx, dy, dz) of each DIA offset where every one is a shift
    of at most one point per axis of the grid `shape`, else None."""
    from .stencil import stencil_shifts
    shifts = stencil_shifts(dia_offsets, shape)
    if shifts is None or any(max(map(abs, s)) > 1 for s in shifts):
        return None
    return shifts


def faces_only(shifts) -> bool:
    """Do the shifts couple a point to its face neighbours alone?"""
    return all(sum(map(abs, s)) <= 1 for s in shifts)


def _parity_colors(shape, faces_only: bool, xp=jnp):
    """(row colors, x fastest; how many) by grid parity."""
    nx, ny, nz = shape
    i = xp.arange(nx * ny * nz, dtype=xp.int32)
    colors = parity_color(i % nx % 2, (i // nx) % ny % 2,
                          i // (nx * ny) % 2, shape, faces_only)
    if faces_only:
        num = 2 if max(shape) > 1 else 1
    else:
        top = (min(e, 2) - 1 for e in shape)
        num = parity_color(*top, shape, False) + 1
    return colors, num


@functools.partial(jax.jit, static_argnames=("shape", "faces_only"))
def _parity_colors_device(shape, faces_only: bool):
    # one program a grid, not a dozen eager ones
    colors, num = _parity_colors(shape, faces_only)
    return colors.astype(jnp.int32), num


def parity_coloring(A: CsrMatrix) -> Optional[Coloring]:
    """The geometric coloring of a grid operator that couples a point
    to its box neighbours only, or None where the parity classes are
    not a proper coloring of the matrix (no `grid_shape`, a reach of 2
    along an axis). Read from the DIA offsets where they say so (they
    are static; one jitted pass over the values looks for nonzeros that
    wrap round a grid row), else tried on the CSR pattern."""
    shape = getattr(A, "grid_shape", None)
    if shape is None or len(shape) != 3 or A.num_rows != A.num_cols:
        return None
    shape = tuple(int(s) for s in shape)
    n = A.num_rows
    if shape[0] * shape[1] * shape[2] != n or n == 0:
        return None
    if A.dia_offsets is not None and A.dia_vals is not None:
        from ..amg.aggregation.galerkin import _any_wrapped
        shifts = box_shifts(A.dia_offsets, shape)
        if shifts is not None:
            vals = A.dia_vals.reshape(len(shifts), -1)[:, :n]
            if not bool(_any_wrapped(vals, shifts, shape)):
                colors, num = _parity_colors_device(shape,
                                                    faces_only(shifts))
                return Coloring(colors, int(num), grid=shape)
        # offsets that do not read as box shifts (a 3-wide grid's are
        # ambiguous): the pattern decides
    from ..matrix import host_arrays
    ha = host_arrays(A.row_offsets, A.col_indices)
    if ha is not None:
        xp = np
        rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(ha[0]))
        cols = ha[1]
    else:
        xp = jnp
        rows, cols, _ = A.coo()
    for faces in (True, False):
        colors, num = _parity_colors(shape, faces, xp)
        if not bool(xp.any((colors[rows] == colors[cols])
                           & (rows != cols))):
            return Coloring(jnp.asarray(colors, jnp.int32), num)
    return None


def _square_edges(A: CsrMatrix):
    """Distance-2 adjacency (pattern of A@A) as symmetric edges."""
    from .spgemm import csr_multiply
    rows, cols, _ = A.coo()
    pattern = CsrMatrix(row_offsets=A.row_offsets,
                        col_indices=A.col_indices,
                        values=jnp.ones((A.nnz,), jnp.float64),
                        num_rows=A.num_rows, num_cols=A.num_cols)
    S2 = csr_multiply(pattern, pattern)
    r2, c2, v2 = S2.coo()
    keep = (np.asarray(v2) > 0) & (np.asarray(r2) != np.asarray(c2))
    r = jnp.concatenate([r2[keep], c2[keep]])
    c = jnp.concatenate([c2[keep], r2[keep]])
    order = jnp.argsort(r, stable=True)
    return r[order], c[order]


class MatrixColoring:
    """Base (include/matrix_coloring/matrix_coloring.h:27)."""

    def __init__(self, cfg, scope):
        self.cfg = cfg
        self.scope = scope
        self.coloring_level = int(cfg.get("coloring_level", scope))

    def color_matrix(self, A: CsrMatrix) -> Coloring:
        raise NotImplementedError


@registry.matrix_coloring.register("MIN_MAX")
@registry.matrix_coloring.register("PARALLEL_GREEDY")
@registry.matrix_coloring.register("LOCALLY_DOWNWIND")
class MinMaxColoring(MatrixColoring):
    """LOCALLY_DOWNWIND documented deviation: the reference's downwind
    ordering (locally_downwind.cu) targets DILU sweep quality on
    convection problems; here it aliases MIN_MAX (GREEDY_RECOLOR below
    is the real quality scheme of this port)."""

    def color_matrix(self, A):
        if self.coloring_level >= 2:
            return _jpl_min_max(A, edges=_square_edges(A))
        return parity_coloring(A) or _jpl_min_max(A)


def _greedy_recolor_np(n, ro_e, sc, colors, num_colors):
    """Descending-class first-fit recolor over the symmetrized edge
    lists (rows CSR-ordered): each color class is an independent set,
    so its vertices reassign simultaneously to their smallest
    neighbor-free color. One pass; the count never increases (a
    vertex's own class is always free). O(nnz) per class sweep total."""
    colors = colors.copy()
    K = int(num_colors)
    if K <= 2 or n == 0:
        return colors, K
    for c in range(K - 1, 0, -1):
        rows_c = np.flatnonzero(colors == c)
        if rows_c.size == 0:
            continue
        used = np.zeros((rows_c.size, K), bool)
        # neighbor colors of each class-c vertex (fresh gather — earlier
        # classes may already have moved); flat edge positions of the
        # class rows, fully vectorized
        cnt = ro_e[rows_c + 1] - ro_e[rows_c]
        tot = int(cnt.sum())
        if tot:
            tgt = np.repeat(np.arange(rows_c.size), cnt)
            pos = (np.arange(tot) - np.repeat(np.cumsum(cnt) - cnt, cnt)
                   + np.repeat(ro_e[rows_c], cnt))
            used[tgt, colors[sc[pos]]] = True
        new = np.argmax(~used, axis=1)      # smallest free color (<= c)
        colors[rows_c] = new
    return colors, int(colors.max()) + 1


@registry.matrix_coloring.register("GREEDY_RECOLOR")
class GreedyRecolorColoring(MatrixColoring):
    """JPL MIN_MAX followed by a greedy recoloring pass that shrinks
    the color count (greedy_recolor.cu:1-1172 role): fewer colors
    directly cuts the serial sweep depth of MULTICOLOR_DILU/GS.
    Reassignment runs class-by-class in descending color order; each
    class is an independent set, so the whole class moves at once to
    its smallest neighbor-free color."""

    def color_matrix(self, A):
        n = A.num_rows
        # one edge build serves both the base JPL and the recolor pass
        # (at distance 2 the _square_edges SpGEMM is the dominant cost;
        # at distance 1 the host edge lists are shared via
        # _host_sym_edges)
        sq_edges = _square_edges(A) if self.coloring_level >= 2 else None
        he = _host_sym_edges(A) if self.coloring_level < 2 else None
        if he is not None:
            base = _jpl_min_max_np(n, he[0], he[1], 64, True)
        else:
            base = _jpl_min_max(A, edges=sq_edges) \
                if sq_edges is not None else _jpl_min_max(A)
        if base.num_colors <= 2:
            return base
        if he is not None:
            sr, sc = he
        else:
            sr, sc = sq_edges if sq_edges is not None else _sym_edges(A)
            sr, sc = np.asarray(sr), np.asarray(sc)
        order = np.argsort(sr, kind="stable")
        sr, sc = sr[order], sc[order]
        ro_e = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(sr, minlength=n), out=ro_e[1:])
        colors, num = _greedy_recolor_np(
            n, ro_e, sc, np.asarray(base.row_colors), base.num_colors)
        return Coloring(jnp.asarray(colors), num)


@registry.matrix_coloring.register("MIN_MAX_2RING")
@registry.matrix_coloring.register("GREEDY_MIN_MAX_2RING")
class MinMax2RingColoring(MatrixColoring):
    def color_matrix(self, A):
        return _jpl_min_max(A, edges=_square_edges(A))


@registry.matrix_coloring.register("MULTI_HASH")
class MultiHashColoring(MatrixColoring):
    def __init__(self, cfg, scope):
        super().__init__(cfg, scope)
        self.max_num_hash = int(cfg.get("max_num_hash", scope))

    def color_matrix(self, A):
        # several independent hash rounds folded into the same fixed point
        return _jpl_min_max(A, max_rounds=max(self.max_num_hash * 4, 16))


@registry.matrix_coloring.register("ROUND_ROBIN")
class RoundRobinColoring(MatrixColoring):
    def __init__(self, cfg, scope):
        super().__init__(cfg, scope)
        self.num_colors = int(cfg.get("num_colors", scope))

    def color_matrix(self, A):
        c = jnp.arange(A.num_rows, dtype=jnp.int32) % self.num_colors
        return Coloring(c, min(self.num_colors, max(A.num_rows, 1)))


@registry.matrix_coloring.register("UNIFORM")
class UniformColoring(MatrixColoring):
    """Geometric striping (uniform.cu): valid for banded stencils whose
    bandwidth is below num_colors."""

    def __init__(self, cfg, scope):
        super().__init__(cfg, scope)
        self.num_colors = int(cfg.get("num_colors", scope))

    def color_matrix(self, A):
        return RoundRobinColoring.color_matrix(self, A)


@registry.matrix_coloring.register("GRID_PARITY")
class GridParityColoring(MatrixColoring):
    """The coloring by grid parity (parity_coloring) asked for by name.
    MIN_MAX takes it of itself where the matrix allows and colors by
    JPL where it does not: minutes on a fine level, and four times the
    colors. A configuration sized for the parity colors names them, so
    that a matrix without a grid, or a build without this coloring,
    stops at setup instead."""

    def color_matrix(self, A):
        coloring = parity_coloring(A)
        if coloring is None:
            raise BadParametersError(
                "matrix_coloring_scheme=GRID_PARITY needs a matrix with "
                "grid_shape whose couplings stay within one point per "
                f"axis; got grid_shape={getattr(A, 'grid_shape', None)}")
        return coloring


@registry.matrix_coloring.register("SERIAL_GREEDY_BFS")
class SerialGreedyBfsColoring(MatrixColoring):
    """Host-side first-fit greedy in BFS order (serial_greedy_bfs.cu):
    the quality/determinism reference the parallel schemes are judged
    against."""

    def color_matrix(self, A):
        n = A.num_rows
        ro = np.asarray(A.row_offsets)
        ci = np.asarray(A.col_indices)
        colors = np.full(n, -1, np.int32)
        for i in range(n):
            nbr = ci[ro[i]:ro[i + 1]]
            used = set(colors[j] for j in nbr if j != i and colors[j] >= 0)
            c = 0
            while c in used:
                c += 1
            colors[i] = c
        return Coloring(jnp.asarray(colors), int(colors.max()) + 1 if n else 0)


def color_matrix(A: CsrMatrix, cfg, scope: str = "default") -> Coloring:
    """MatrixColoringFactory entry (src/core.cu:669). A user-attached
    coloring (AMGX_matrix_attach_coloring) overrides the configured
    scheme, matching the reference's attach semantics."""
    if A.user_colors is not None:
        return Coloring(A.user_colors, int(A.user_num_colors))
    name = str(cfg.get("matrix_coloring_scheme", scope))
    return registry.matrix_coloring.create(name, cfg, scope).color_matrix(A)

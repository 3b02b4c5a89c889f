"""Multicolor smoothers: GS, DILU, ILU(k), fixed-color GS, serial GS,
CF-Jacobi.

TPU-native analogs of the reference's color-parallel smoother family
(src/solvers/multicolor_gauss_seidel_solver.cu:1,
multicolor_dilu_solver.cu:1 — its largest kernel investment and the
default smoother in shipped configs, multicolor_ilu_solver.cu:1,
fixcolor_gauss_seidel_solver.cu:1, gauss_seidel_solver.cu:1,
cf_jacobi_solver.cu:1).

Execution model redesign for XLA: the reference launches one kernel per
color over the rows of that color. Here each color step is a *masked
dense update* over the full vector driven by one SpMV, in a rolled loop
over the (static) color count. MULTICOLOR_GS on a grid colored by
parity takes a color's points by the grid rows they lie in instead
(ops/parity_sweep.py), so that a step reads its own rows' coefficients
only; it is the same sweep in the same order.

- colored GS sweep:  for c: x  <- where(color==c, x + w*D^-1(b-Ax), x)
  (exact Gauss-Seidel in the color ordering: the SpMV sees the already-
  updated colors);
- DILU forward:      for c asc:  delta <- where(color==c,
                        Einv*(r - A delta), delta)
  where A delta only picks up colors < c because delta is still zero
  elsewhere — the masked-SpMV trick that replaces the reference's
  row_colors[j] < current_color predicate
  (DILU_forward_1x1_kernel, multicolor_dilu_solver.cu:1766);
- DILU backward:     for c desc: Delta <- where(color==c,
                        delta - Einv*(A Delta), Delta); x += w*Delta
  (DILU_backward kernels, :1908+);
- DILU setup:        Einv_i = 1/(a_ii - sum_{color_j < color_i}
                        a_ij * Einv_j * a_ji)
  color-by-color, with the a_ji lookup done as a key search into the
  CSR pattern (DILU_setup_1x1_kernel, :650-810).

ILU(k) factors the *color-permuted* matrix with fixed-point (Chow-Patel
style) sweeps, each one pattern-restricted L@U product; because the
elimination DAG of a C-colored matrix has depth <= C, C sweeps reproduce
the exact ILU(0) factors (E. Chow, A. Patel, "Fine-grained parallel
incomplete LU factorization", SISC 2015 — public algorithm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import registry
from ..errors import BadParametersError
from ..matrix import CsrMatrix
from ..ops import parity_sweep
from ..ops.coloring import Coloring, color_matrix
from ..ops.dense import abs_det, inverse, safe_inverse
from ..ops.spmv import spmv
from .base import Solver
from .relaxation import _apply_dinv, l1_strengthened_diag, safe_recip


def _match_transpose_np(num_rows, num_cols, ro, ci, vals):
    """Host twin of _match_transpose (scalar matrices): numpy int64-key
    searchsorted over host (numpy/mirror) arrays. CSR keys are already
    sorted when columns are sorted in-row (the host hierarchy build's
    invariant), so the argsort is usually skipped entirely — the device
    form's eager int64 argsort was the single hottest op of the host
    smoother setup."""
    import numpy as np
    cols = ci.astype(np.int64)
    rows = np.repeat(np.arange(num_rows, dtype=np.int64), np.diff(ro))
    keys = rows * num_cols + cols
    if np.all(keys[1:] >= keys[:-1]):
        order = None
        skeys = keys
    else:
        order = np.argsort(keys, kind="stable")
        skeys = keys[order]
    want = cols * num_cols + rows
    pos = np.clip(np.searchsorted(skeys, want), 0, max(keys.shape[0] - 1, 0))
    found = skeys[pos] == want
    src = pos if order is None else order[pos]
    return np.where(found, vals[src], 0.0)


def _match_transpose(A: CsrMatrix):
    """For every CSR entry (i,j) return the value of (j,i), or 0 when the
    pattern has no such entry (the reference's warp search over row j,
    multicolor_dilu_solver.cu:740-781)."""
    rows, cols, vals = A.coo()
    keys = rows.astype(jnp.int64) * A.num_cols + cols.astype(jnp.int64)
    order = jnp.argsort(keys)          # CSR is usually already sorted
    skeys = keys[order]
    want = cols.astype(jnp.int64) * A.num_cols + rows.astype(jnp.int64)
    pos = jnp.clip(jnp.searchsorted(skeys, want), 0, keys.shape[0] - 1)
    src = order[pos]
    found = skeys[pos] == want
    if A.is_block:
        # the (j,i) block participates as A_ji, i.e. transposed in the
        # i-row formula; keep it as stored — the caller contracts it on
        # the correct side
        return jnp.where(found[:, None, None], vals[src], 0.0)
    return jnp.where(found, vals[src], 0.0)


class _ColoredSolver(Solver):
    """Shared coloring plumbing (Solver::setup colors the matrix when
    isColoringNeeded(), include/solvers/solver.h:140)."""

    is_smoother = True

    def __init__(self, cfg, scope="default", name="?"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))

    # the colors of this matrix, where the owner of the solver colored
    # it ahead of setup (the hierarchy does, under its own span)
    _colored = None

    def color(self, A):
        """Color A now; the setup on the same matrix then finds the
        coloring made."""
        self._colored = (A, color_matrix(A, self.cfg, self.scope))

    def _color(self):
        if self._colored is None or self._colored[0] is not self.A:
            self.color(self.A)
        self.coloring = self._colored[1]
        self._colored = None
        self.row_colors = self.coloring.row_colors
        self.num_colors = int(self.coloring.num_colors)

    # ordered color steps of one sweep: what a solve is made of
    # (counter smoother.color_steps)
    sweep_passes = 1

    def color_steps_per_iteration(self):
        return self.sweep_passes * getattr(self, "num_colors", 0)

    def computes_residual(self):
        return False


@registry.solvers.register("MULTICOLOR_GS")
class MulticolorGSSolver(_ColoredSolver):
    """Color-parallel Gauss-Seidel
    (multicolor_gauss_seidel_solver.cu:1). `symmetric_GS=1` appends the
    reverse color sweep."""

    def __init__(self, cfg, scope="default", name="MULTICOLOR_GS"):
        super().__init__(cfg, scope, name)
        self.symmetric = bool(int(cfg.get("symmetric_GS", scope)))
        self.sweep_passes = 2 if self.symmetric else 1

    def solver_setup(self):
        self._color()
        d = self.A.diagonal()
        self._dinv = safe_inverse(d) if self.A.is_block else safe_recip(d)
        # parity colors on a DIA box stencil: the sweep by row sets
        self._parity = parity_sweep.make_plan(self.A, self.coloring)
        self._parity_slabs = None if self._parity is None else \
            parity_sweep.build_slabs(self.A.dia_vals, self._dinv,
                                     self._parity)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["dinv"] = self._dinv
        d["colors"] = self.row_colors
        if self._parity is not None:
            d["parity"] = self._parity_slabs
        return d

    def _color_update(self, data, b, x, c):
        A = data["A"]
        r = b - spmv(A, x)
        upd = x + self.relaxation_factor * _apply_dinv(
            data["dinv"], r, A.is_block)
        mask = data["colors"] == c
        if A.is_block:
            mask = jnp.repeat(mask, A.block_dimx,
                              total_repeat_length=x.shape[0])
        return jnp.where(mask, upd, x)

    def _sweep(self, data, b, x):
        if "parity" in data:
            return parity_sweep.sweep(self._parity, data["parity"], b, x,
                                      self.relaxation_factor,
                                      self.symmetric)
        nc = self.num_colors
        # rolled color loop (traced color index — see the DILU sweep)
        x = jax.lax.fori_loop(
            0, nc, lambda c, x: self._color_update(data, b, x, c), x)
        if self.symmetric:
            x = jax.lax.fori_loop(
                0, nc,
                lambda i, x: self._color_update(data, b, x, nc - 1 - i),
                x)
        return x

    def solve_iteration(self, data, b, st):
        out = dict(st)
        out["x"] = self._sweep(data, b, st["x"])
        return out

    def smooth(self, data, b, x, sweeps: int):
        # a sweep needs no residual of its own: not the base's, which
        # computes one first
        return jax.lax.fori_loop(
            0, sweeps, lambda _, x: self._sweep(data, b, x), x)


@registry.solvers.register("FIXCOLOR_GS")
class FixcolorGSSolver(MulticolorGSSolver):
    """Fixed 4-color striped GS (fixcolor_gauss_seidel_solver.cu:1):
    colors are assigned round-robin by row index instead of from the
    graph — valid for banded stencils, cheap to set up."""

    FIXED_COLORS = 4

    def color(self, A):
        n = A.num_rows
        self._colored = (A, Coloring(
            jnp.arange(n, dtype=jnp.int32) % self.FIXED_COLORS,
            min(self.FIXED_COLORS, max(n, 1))))


@registry.solvers.register("GS")
class GSSolver(Solver):
    """Serial natural-order Gauss-Seidel (gauss_seidel_solver.cu:1).
    Exact sequential sweep as a lax.fori_loop over rows with padded-ELL
    row gathers — inherently O(n) sequential steps; the reference's GS is
    serial too. Use MULTICOLOR_GS for large problems."""

    is_smoother = True

    def __init__(self, cfg, scope="default", name="GS"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))
        if bool(int(cfg.get("GS_L1_variant", scope))):
            self._l1 = True
        else:
            self._l1 = False

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError("GS: scalar matrices only")
        from ..ops.spgemm import _fold_diag
        A = _fold_diag(self.A)          # row_dot must include a_ii * x_i
        if A.ell_cols is None:
            A = CsrMatrix(
                row_offsets=A.row_offsets, col_indices=A.col_indices,
                values=A.values, num_rows=A.num_rows,
                num_cols=A.num_cols).init(ell="always")
        self._ell_cols, self._ell_vals = A.ell_cols, A.ell_vals
        d = l1_strengthened_diag(self.A) if self._l1 else self.A.diagonal()
        self._diag = d
        self._dinv = safe_recip(d)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d.update(ell_cols=self._ell_cols, ell_vals=self._ell_vals,
                 gs_diag=self._diag, dinv=self._dinv)
        return d

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        cols, vals = data["ell_cols"], data["ell_vals"]
        diag, dinv = data["gs_diag"], data["dinv"]
        w = self.relaxation_factor

        def row_update(i, x):
            row_dot = jnp.dot(vals[i], x[cols[i]])
            # row_dot includes a_ii * x_i; remove it for the GS update
            xi_new = dinv[i] * (b[i] - row_dot + diag[i] * x[i])
            return x.at[i].set((1 - w) * x[i] + w * xi_new)

        x = jax.lax.fori_loop(0, self.A.num_rows, row_update, st["x"])
        out = dict(st)
        out["x"] = x
        return out


@registry.solvers.register("MULTICOLOR_DILU")
class MulticolorDILUSolver(_ColoredSolver):
    """Diagonal-ILU smoother (multicolor_dilu_solver.cu:1 — 4259 LoC in
    the reference, its single largest kernel file). M = (E+L)E^{-1}(E+U)
    where L/U split A by color order and E is chosen so diag(M)=diag(A):

        E_i = A_ii - sum_{color_j < color_i} A_ij E_j^{-1} A_ji.
    """

    sweep_passes = 2            # forward and backward

    def solver_setup(self):
        from ..matrix import host_arrays
        self._color()
        A = self.A
        ha = None if A.is_block else host_arrays(
            A.row_offsets, A.col_indices, A.values)
        if ha is not None and A.has_external_diag \
                and host_arrays(A.diag) is None:
            ha = None             # device-only external diagonal
        if ha is not None:
            # host fast path (host-resident OR mirror-backed device
            # matrices): the whole color recurrence in synchronous
            # numpy — the eager per-color dispatches and the int64-key
            # argsort dominate the smoother setup otherwise (one eager
            # dispatch and one host sync per color)
            import numpy as onp
            ro, cols, vals = ha
            n = A.num_rows
            at_vals = _match_transpose_np(n, A.num_cols, ro, cols, vals)
            if A.has_external_diag:
                d = host_arrays(A.diag)[0]
            else:
                hdi = host_arrays(A.diag_idx) if A.diag_idx is not None \
                    else None
                if hdi is not None:
                    # init already stored the first-occurrence in-row
                    # diagonal index (padded-duplicate CSR convention)
                    di = hdi[0]
                    d = onp.where(di >= 0,
                                  vals[onp.maximum(di, 0)], 0.0)
                else:
                    # fallback: scan (uninitialized host matrices)
                    rows64 = onp.repeat(onp.arange(n, dtype=onp.int64),
                                        onp.diff(ro))
                    cand = onp.where(cols == rows64,
                                     onp.arange(cols.shape[0]),
                                     cols.shape[0])
                    from ..matrix import _np_row_reduce
                    dmin = _np_row_reduce(onp.minimum, cand, ro, n,
                                          cols.shape[0])
                    d = onp.where(
                        dmin < cols.shape[0],
                        vals[onp.minimum(dmin, cols.shape[0] - 1)], 0.0)
            colors = onp.asarray(self.row_colors)
            Einv = onp.zeros(n, vals.dtype)
            from ..matrix import _np_row_reduce
            prod = vals * at_vals
            for c in range(self.num_colors):
                e = _np_row_reduce(onp.add, prod * Einv[cols], ro, n, 0.0)
                blk = d - e
                new = onp.divide(1.0, blk,
                                 out=onp.zeros_like(blk),
                                 where=blk != 0)
                Einv = onp.where(colors == c, new, Einv)
            self._Einv = Einv
            return
        rows, cols, vals = A.coo()
        at_vals = _match_transpose(A)
        d = A.diagonal()
        colors = self.row_colors
        n = A.num_rows
        if A.is_block:
            bx = A.block_dimx
            Einv = jnp.zeros((n, bx, bx), A.dtype)
            eye = jnp.eye(bx, dtype=A.dtype)
            for c in range(self.num_colors):
                # contributions A_ij Einv_j A_ji; Einv_j is zero for
                # colors >= c (incl. the diagonal j==i), so the masked
                # predicate of the reference kernel falls out for free
                contrib = jnp.einsum("nab,nbc,ncd->nad",
                                     vals, Einv[cols], at_vals)
                e = jax.ops.segment_sum(contrib, rows, num_segments=n,
                                        indices_are_sorted=True)
                blk = d - e
                # singular guard: fall back to identity like the scalar 1/0
                det_ok = abs_det(blk) > 0
                blk = jnp.where(det_ok[:, None, None], blk, eye[None])
                Einv = jnp.where((colors == c)[:, None, None],
                                 inverse(blk), Einv)
        else:
            Einv = jnp.zeros((n,), A.dtype)
            for c in range(self.num_colors):
                contrib = vals * Einv[cols] * at_vals
                e = jax.ops.segment_sum(contrib, rows, num_segments=n,
                                        indices_are_sorted=True)
                Einv = jnp.where(colors == c, safe_recip(d - e), Einv)
        self._Einv = Einv

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["Einv"] = self._Einv
        d["colors"] = self.row_colors
        return d

    def _mask(self, data, c, like):
        m = data["colors"] == c
        if self.A.is_block:
            m = jnp.repeat(m, self.A.block_dimx,
                           total_repeat_length=like.shape[0])
        return m

    def solve_iteration(self, data, b, st):
        A, Einv = data["A"], data["Einv"]
        x = st["x"]
        r = b - spmv(A, x)
        nc = self.num_colors
        # color sweeps as lax.fori_loop (the mask compares against the
        # TRACED color index): a Python unroll put 2*colors SpMVs per
        # level into one XLA program, which at 128^3-classical scale
        # (8 levels x ~8 colors) faulted the TPU at compile/run time

        def fwd(c, delta):
            # forward: (E+L) delta = r, colors ascending (only colors
            # < c are nonzero in delta)
            upd = _apply_dinv(Einv, r - spmv(A, delta), A.is_block)
            return jnp.where(self._mask(data, c, x), upd, delta)

        delta = jax.lax.fori_loop(0, nc, fwd, jnp.zeros_like(x))

        def bwd(i, Delta):
            # backward: (E+U) Delta = E delta, colors descending (only
            # colors > c are nonzero in Delta)
            c = nc - 1 - i
            upd = delta - _apply_dinv(Einv, spmv(A, Delta), A.is_block)
            return jnp.where(self._mask(data, c, x), upd, Delta)

        Delta = jax.lax.fori_loop(0, nc, bwd, jnp.zeros_like(x))
        out = dict(st)
        out["x"] = x + self.relaxation_factor * Delta
        return out


def _permute_csr(A: CsrMatrix, perm, iperm) -> CsrMatrix:
    """P A P^T: row/col relabeling by new = iperm[old] (the reference's
    reorderColumnsByColor + row sort, src/matrix.cu)."""
    rows, cols, vals = A.coo()
    return CsrMatrix.from_coo(iperm[rows], iperm[cols], vals,
                              A.num_rows, A.num_cols)


@registry.solvers.register("MULTICOLOR_ILU")
class MulticolorILUSolver(_ColoredSolver):
    """ILU(k) smoother on the color-permuted matrix
    (multicolor_ilu_solver.cu:1). Factors via fixed-point sweeps, each a
    pattern-restricted Lstrict@U product; C sweeps are exact for a
    C-colored matrix (elimination depth <= C). Triangular solves run
    color-by-color with the same masked-SpMV scheme as DILU.

    ilu_sparsity_level=k extends the pattern by k rounds of level-fill;
    fill edges must stay properly colored, so k>0 requires a distance-2
    coloring (coloring_level=2) — validated at setup."""

    sweep_passes = 2            # L solve and U solve

    def __init__(self, cfg, scope="default", name="MULTICOLOR_ILU"):
        super().__init__(cfg, scope, name)
        self.sparsity_level = int(cfg.get("ilu_sparsity_level", scope))

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError(
                "MULTICOLOR_ILU: scalar matrices only in this build; use "
                "MULTICOLOR_DILU for block matrices")
        self._color()
        from ..ops.spgemm import _fold_diag
        A, n = _fold_diag(self.A), self.A.num_rows
        colors = self.row_colors
        # color-sort permutation: position p holds original row perm[p]
        perm = jnp.argsort(colors, stable=True)
        iperm = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(n, dtype=perm.dtype))
        Ap = _permute_csr(A, perm, iperm)
        colors_p = colors[perm]
        if self.sparsity_level > 0:
            Ap = self._extend_pattern(Ap)
        Ap = Ap.init(ell="never")
        rows, cols, vals = Ap.coo()
        # validate: factor pattern must have no same-color off-diagonals
        same = (rows != cols) & (colors_p[rows] == colors_p[cols])
        if bool(jnp.any(same)):
            raise BadParametersError(
                "MULTICOLOR_ILU: fill pattern joins same-colored rows; "
                "use coloring_level=2 (distance-2 coloring) with "
                f"ilu_sparsity_level={self.sparsity_level}")
        lower = rows > cols
        upper = ~lower
        keys = rows.astype(jnp.int64) * n + cols.astype(jnp.int64)
        # initial guess: l = a_ij/a_jj, u = a_ij (standard CP init)
        diag_full = Ap.diagonal()
        l = jnp.where(lower, vals * safe_recip(diag_full)[cols], 0.0)
        u = jnp.where(upper, vals, 0.0)
        sweeps = min(self.num_colors, 24) + 1
        from ..ops.spgemm import csr_multiply
        for _ in range(sweeps):
            Lm = CsrMatrix.from_coo(rows[lower], cols[lower], l[lower],
                                    n, n)
            Um = CsrMatrix.from_coo(rows[upper], cols[upper], u[upper],
                                    n, n)
            Pm = csr_multiply(Lm, Um)
            pr, pc, pv = Pm.coo()
            pkeys = pr.astype(jnp.int64) * n + pc.astype(jnp.int64)
            pos = jnp.clip(jnp.searchsorted(pkeys, keys), 0,
                           max(int(pkeys.shape[0]) - 1, 0))
            if pkeys.shape[0] == 0:
                prod = jnp.zeros_like(vals)
            else:
                prod = jnp.where(pkeys[pos] == keys, pv[pos], 0.0)
            u_diag = jnp.where(Ap.diag_idx < 0, 0.0,
                               u[jnp.maximum(Ap.diag_idx, 0)])
            # (Lstrict@U)_ij includes the k=j term l_ij*u_jj for i>j
            u_jj = u_diag[cols]
            l_new = safe_recip(u_jj) * (vals - (prod - l * u_jj))
            u_new = vals - prod
            l = jnp.where(lower, l_new, 0.0)
            u = jnp.where(upper, u_new, 0.0)
        # store the factors in the ORIGINAL row ordering: a proper
        # coloring has no same-color off-diagonals (validated above), so
        # the color-masked sweeps are ordering-independent — and
        # original-order factors are row-partitionable, which makes this
        # smoother distribution-aware (no global permutation at solve
        # time)
        ro, co = perm[rows[lower]], perm[cols[lower]]
        self._Lp = CsrMatrix.from_coo(ro, co, l[lower], n,
                                      n).init(ell="never")
        ro, co = perm[rows[upper]], perm[cols[upper]]
        self._Up = CsrMatrix.from_coo(ro, co, u[upper], n,
                                      n).init(ell="never")
        u_diag_p = jnp.where(Ap.diag_idx < 0, 0.0,
                             u[jnp.maximum(Ap.diag_idx, 0)])
        self._u_diag = jnp.zeros_like(u_diag_p).at[perm].set(u_diag_p)

    def _extend_pattern(self, Ap: CsrMatrix) -> CsrMatrix:
        """Level-fill pattern extension: union A with the pattern of
        Lpat@Upat, `sparsity_level` times (zero values on fill)."""
        from ..ops.spgemm import csr_add, csr_multiply
        n = Ap.num_rows
        for _ in range(self.sparsity_level):
            rows, cols, vals = Ap.coo()
            lo, up = rows > cols, rows < cols
            Lpat = CsrMatrix.from_coo(rows[lo], cols[lo],
                                      jnp.ones(int(lo.sum())), n, n)
            Upat = CsrMatrix.from_coo(rows[up], cols[up],
                                      jnp.ones(int(up.sum())), n, n)
            F = csr_multiply(Lpat, Upat)
            fr, fc, _ = F.coo()
            fill = CsrMatrix.from_coo(fr, fc, jnp.zeros(fr.shape[0]), n, n)
            Ap = csr_add(Ap, fill)
        return Ap

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d.update(ilu_L=self._Lp, ilu_U=self._Up, u_diag=self._u_diag,
                 colors=self.row_colors)
        return d

    def solve_iteration(self, data, b, st):
        A = data["A"]
        Lp, Up = data["ilu_L"], data["ilu_U"]
        u_dinv = safe_recip(data["u_diag"])
        colors = data["colors"]
        x = st["x"]
        r = b - spmv(A, x)
        nc = self.num_colors
        # rolled color sweeps (traced color index — see the DILU sweep:
        # a Python unroll emits 2*colors SpMVs per level into one XLA
        # program, which faulted the TPU at 128^3-classical scale)
        # L y = r (unit diag), colors ascending (original ordering:
        # L only connects strictly lower colors)
        y = jax.lax.fori_loop(
            0, nc,
            lambda c, y: jnp.where(colors == c, r - spmv(Lp, y), y),
            jnp.zeros_like(r))
        # U z = y, colors descending (diagonal term zero pre-assignment)
        z = jax.lax.fori_loop(
            0, nc,
            lambda i, z: jnp.where(colors == nc - 1 - i,
                                   u_dinv * (y - spmv(Up, z)), z),
            jnp.zeros_like(r))
        out = dict(st)
        out["x"] = x + self.relaxation_factor * z
        return out


@registry.solvers.register("CF_JACOBI")
class CFJacobiSolver(Solver):
    """CF-ordered Jacobi for classical AMG (cf_jacobi_solver.cu:1): one
    sweep updates F-points then C-points (or the reverse), using the CF
    map produced by the level's selector. `cf_smoothing_mode` picks the
    order (0: C-then-F presmooth / F-then-C postsmooth flavor; here the
    mode picks the fixed order, 0=CF 1=FC, matching the implemented
    reference modes src/core.cu:416)."""

    is_smoother = True
    needs_cf_map = True

    def __init__(self, cfg, scope="default", name="CF_JACOBI"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))
        self.mode = int(cfg.get("cf_smoothing_mode", scope))
        self.cf_map = None

    def set_cf_map(self, cf_map):
        self.cf_map = jnp.asarray(cf_map)

    def solver_setup(self):
        if self.A.is_block:
            raise BadParametersError("CF_JACOBI: scalar matrices only")
        if self.cf_map is None:
            raise BadParametersError(
                "CF_JACOBI needs the CF map of a classical AMG level "
                "(use it as a smoother under algorithm=CLASSICAL)")
        self._dinv = safe_recip(self.A.diagonal())

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["dinv"] = self._dinv
        d["is_coarse"] = self.cf_map == 1
        return d

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A, dinv = data["A"], data["dinv"]
        coarse = data["is_coarse"]
        w = self.relaxation_factor
        x = st["x"]
        phases = (coarse, ~coarse) if self.mode == 0 else (~coarse, coarse)
        for mask in phases:
            r = b - spmv(A, x)
            x = jnp.where(mask, x + w * dinv * r, x)
        out = dict(st)
        out["x"] = x
        return out

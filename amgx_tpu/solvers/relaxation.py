"""Pointwise/block relaxation solvers (usable standalone, as
preconditioners, or as AMG smoothers).

Analogs of src/solvers/block_jacobi_solver.cu (1445 LoC),
jacobi_l1_solver.cu, dummy_solver.cu. On TPU a Jacobi sweep is one fused
SpMV + elementwise update; block diagonals are inverted batched at setup
(XLA maps the (n, b, b) inversion onto the MXU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from .. import registry
from ..ops import smooth as fused
from ..ops.dense import safe_inverse
from ..ops.spmv import spmv
from .base import Solver


class _FusedJacobiMixin:
    """Fused smooth/smooth_residual for scalar damped-Jacobi solvers
    (x' = x + omega * dinv . (b - A x)): all sweeps and the trailing
    cycle residual run through the single-pass kernels of ops/smooth.py
    when the level layout supports them. `fused_smoother=0` (or any
    unsupported layout/backend) falls back to the base implementations
    unchanged — bit-for-bit the pre-fusion computation.

    Matrix-free levels: when the hierarchy's constant-coefficient
    detector installed a StencilOperator on this smoother
    (`_mf_stencil`, amg/hierarchy.py `matrix_free` knob), solve_data
    carries the stencil INSTEAD of the dinv vector and fused slabs —
    the A value slab and the dinv stream vanish from the level's HBM
    footprint — and every smooth entry routes through the coefficient
    forms in ops/stencil.py (which synthesize dinv in-register from
    the diagonal coefficient)."""

    # consulted by AMG._maybe_install_stencil: this smoother family's
    # sweeps are expressible from stencil coefficients alone, with the
    # diagonal inverse synthesized per `matrix_free_dinv`
    supports_matrix_free = True
    matrix_free_dinv = "jacobi"

    def _fused_eligible(self, data):
        A = data["A"]
        return (self.fused_smoother and not getattr(A, "is_block", True)
                and "dinv" in data)

    def _fused_taus(self, sweeps: int, dtype):
        return jnp.asarray(
            np.full(max(sweeps, 0), self.relaxation_factor), dtype)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        st = getattr(self, "_mf_stencil", None)
        if st is not None:
            # matrix-free level: the stencil payload replaces BOTH the
            # dinv vector and the fused value slabs; the operator view
            # drops its value slab entirely (O(levels) memory)
            from ..ops.stencil import mf_slim
            d["A"] = mf_slim(d["A"])
            d["stencil"] = st
            return d
        d["dinv"] = self._dinv
        if self.fused_smoother and self.A is not None \
                and not getattr(self.A, "is_block", True):
            slabs = fused.solver_fused_slabs(self, self.A,
                                             dinv=self._dinv)
            if slabs is not None:
                d["fused"] = slabs
        return d

    def smooth(self, data, b, x, sweeps: int):
        st = data.get("stencil")
        if st is not None:
            if sweeps < 1:
                return x
            from ..ops import stencil as mf
            return mf.stencil_fused_smooth(
                st, self._fused_taus(sweeps, x.dtype), b, x,
                with_residual=False)
        if sweeps > 0 and self._fused_eligible(data):
            out = fused.fused_smooth(
                data, b, x, self._fused_taus(sweeps, x.dtype),
                dinv=data["dinv"], with_residual=False)
            if out is not None:
                return out
        return super().smooth(data, b, x, sweeps)

    def smooth_residual(self, data, b, x, sweeps: int):
        st = data.get("stencil")
        if st is not None:
            from ..ops import stencil as mf
            return mf.stencil_fused_smooth(
                st, self._fused_taus(max(sweeps, 0), x.dtype), b, x,
                with_residual=True)
        if sweeps > 0 and self._fused_eligible(data):
            out = fused.fused_smooth(
                data, b, x, self._fused_taus(sweeps, x.dtype),
                dinv=data["dinv"], with_residual=True)
            if out is not None:
                return out
        return super().smooth_residual(data, b, x, sweeps)


def safe_recip(d):
    """Elementwise 1/d with 0 -> 0 (zero-in-diagonal robustness).
    Numpy in, numpy out: the host-setup path keeps smoother payloads
    numpy-backed so the hierarchy ship stays one packed transfer."""
    import numpy as np
    xp = np if isinstance(d, np.ndarray) else jnp
    safe = xp.where(d == 0, 1.0, d)
    return xp.where(d == 0, 0.0, 1.0 / safe)


def _invert_diag(A):
    """D^{-1}: scalar reciprocal or batched block inverse."""
    d = A.diagonal()
    if A.is_block:
        return safe_inverse(d)
    return safe_recip(d)


def _apply_dinv(dinv, v, block: bool):
    if block:
        vb = v.reshape(dinv.shape[0], -1)
        return jnp.einsum("nxy,ny->nx", dinv, vb).reshape(-1)
    return dinv * v


def l1_strengthened_diag(A):
    """Scalar diagonal strengthened by the off-diagonal row L1 norm in
    the diagonal's sign (jacobi_l1_solver.cu); zero diagonals stay zero
    (sign 0) so safe_recip keeps them inert."""
    from ..matrix import host_resident
    if not A.is_block and host_resident(A.row_offsets, A.col_indices,
                                        A.values, A.diag):
        import numpy as np
        n = A.num_rows
        ro = np.asarray(A.row_offsets)
        cols = np.asarray(A.col_indices)
        vals = np.asarray(A.values)
        if not A.has_external_diag and vals.dtype.kind == "f":
            # one native C++ sweep (per-level smoother-setup hot path)
            from .. import native
            out = native.l1_diag_native(n, ro, cols, vals)
            if out is not None:
                return out.astype(vals.dtype, copy=False)
        rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(ro))
        l1 = np.bincount(rows, weights=np.where(rows != cols,
                                                np.abs(vals), 0.0),
                         minlength=n).astype(vals.dtype)
        d = np.asarray(A.diagonal())
        # numpy out (both branches): the host-setup ship casts numpy
        # leaves host-side before the wire
        return d + np.sign(d) * l1
    rows, cols, vals = A.coo()
    offdiag = jnp.where(rows != cols, jnp.abs(vals), 0.0)
    l1 = jax.ops.segment_sum(offdiag, rows, num_segments=A.num_rows,
                             indices_are_sorted=True)
    d = A.diagonal()
    return d + jnp.sign(d) * l1


@registry.solvers.register("BLOCK_JACOBI")
@registry.solvers.register("JACOBI")
class BlockJacobiSolver(_FusedJacobiMixin, Solver):
    """Damped (block-)Jacobi: x += omega * D^{-1} (b - A x)."""

    is_smoother = True

    def __init__(self, cfg, scope="default", name="BLOCK_JACOBI"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))
        self.fused_smoother = bool(int(cfg.get("fused_smoother", scope)))

    def solver_setup(self):
        self._dinv = _invert_diag(self.A)

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A = data["A"]
        r = b - spmv(A, st["x"])
        x = st["x"] + self.relaxation_factor * _apply_dinv(
            data["dinv"], r, A.is_block)
        out = dict(st)
        out["x"] = x
        return out


@registry.solvers.register("JACOBI_L1")
class JacobiL1Solver(_FusedJacobiMixin, Solver):
    """L1-Jacobi: the diagonal is strengthened by the off-diagonal row L1
    norm, making the sweep unconditionally convergent for SPD matrices
    (jacobi_l1_solver.cu analog)."""

    is_smoother = True
    matrix_free_dinv = "l1"

    def __init__(self, cfg, scope="default", name="JACOBI_L1"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))
        self.fused_smoother = bool(int(cfg.get("fused_smoother", scope)))

    def solver_setup(self):
        A = self.A
        rows, cols, vals = A.coo()
        if A.is_block:
            # block L1: add the off-diagonal blocks' row-wise L1 norms to
            # the diagonal of each diagonal block
            offdiag = jnp.where((rows != cols)[:, None, None],
                                jnp.abs(vals), 0.0)
            l1 = jax.ops.segment_sum(offdiag.sum(axis=-1), rows,
                                     num_segments=A.num_rows,
                                     indices_are_sorted=True)
            d = A.diagonal() + jnp.eye(A.block_dimx)[None] * l1[:, :, None]
            self._dinv = safe_inverse(d)
        else:
            self._dinv = safe_recip(l1_strengthened_diag(A))

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        A = data["A"]
        r = b - spmv(A, st["x"])
        x = st["x"] + self.relaxation_factor * _apply_dinv(
            data["dinv"], r, A.is_block)
        out = dict(st)
        out["x"] = x
        return out


@registry.solvers.register("NOSOLVER")
@registry.solvers.register("DUMMY")
class NoSolver(Solver):
    """Identity 'solver' (dummy_solver.cu analog): x = b. As a
    preconditioner this is M = I."""

    is_smoother = True

    def computes_residual(self):
        return False

    def solve_iteration(self, data, b, st):
        out = dict(st)
        out["x"] = b
        return out

    def apply(self, data, rhs):
        return rhs

    def smooth(self, data, b, x, sweeps):
        return x

"""Kaczmarz smoother.

TPU-native analog of src/solvers/kaczmarz_solver.cu (843 LoC). A
Kaczmarz sweep projects the iterate onto each row's hyperplane:

    x += omega * (b_i - a_i . x) / ||a_i||^2 * a_i^T

The reference ships two flavors selected by `kaczmarz_coloring_needed`
(src/core.cu registry; kaczmarz_solver.cu:494-496): a multicolor sweep
(rows of one color processed in parallel) and a "warp-naive" variant
that simply races the scatters. The TPU redesign keeps the same two
modes but makes both deterministic:

- MC mode: per color, all that color's row projections are applied
  simultaneously with a segment-sum scatter over columns — additive
  collisions between same-color rows that share a column turn the sweep
  into a block-Cimmino update within each color, which is deterministic
  (the reference's racing scatters are not) and convergent for the same
  damping range.
- naive mode (kaczmarz_coloring_needed=0): one simultaneous projection
  over ALL rows (the classical Cimmino iteration) — the deterministic
  analog of the racing warp-naive kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import registry
from ..errors import BadParametersError
from ..ops.coloring import color_matrix
from ..ops.spmv import spmv
from .base import Solver
from .relaxation import safe_recip


@registry.solvers.register("KACZMARZ")
class KaczmarzSolver(Solver):

    is_smoother = True
    slim_A_ok = False      # _project reads COO structure per sweep

    def __init__(self, cfg, scope="default", name="KACZMARZ"):
        super().__init__(cfg, scope, name)
        self.relaxation_factor = float(cfg.get("relaxation_factor", scope))
        self.use_coloring = bool(int(cfg.get("kaczmarz_coloring_needed",
                                             scope)))

    def solver_setup(self):
        A = self.A
        if A.is_block:
            raise BadParametersError("KACZMARZ supports scalar matrices")
        rows, cols, vals = A.coo()
        sq = jax.ops.segment_sum(vals * vals, rows,
                                 num_segments=A.num_rows,
                                 indices_are_sorted=True)
        if A.has_external_diag:
            sq = sq + A.diag * A.diag
        self._inv_rownorm2 = safe_recip(sq)
        if self.use_coloring:
            coloring = color_matrix(A, self.cfg, self.scope)
            self.row_colors = coloring.row_colors
            self.num_colors = int(coloring.num_colors)
        else:
            self.row_colors = jnp.zeros((A.num_rows,), jnp.int32)
            self.num_colors = 1

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["inv_rn2"] = self._inv_rownorm2
        d["colors"] = self.row_colors
        return d

    def computes_residual(self):
        return False

    def _project(self, data, b, x, mask):
        """Simultaneous damped projection of the masked rows."""
        A = data["A"]
        rows, cols, vals = A.coo()
        r = b - spmv(A, x)
        coef = jnp.where(mask, r * data["inv_rn2"], 0.0)
        # x += omega * avg_i coef_i * a_i^T: scatter over columns; rows
        # of one color that share a column are AVERAGED (convex
        # combination of single-row projections -> non-expansive),
        # instead of the reference's racing scatters
        upd = jax.ops.segment_sum(vals * coef[rows], cols,
                                  num_segments=A.num_cols)
        cnt = jax.ops.segment_sum(
            jnp.where(mask[rows], 1.0, 0.0), cols,
            num_segments=A.num_cols)
        if A.has_external_diag:
            upd = upd.at[jnp.arange(A.num_rows)].add(A.diag * coef)
            cnt = cnt.at[jnp.arange(A.num_rows)].add(
                jnp.where(mask, 1.0, 0.0))
        upd = upd / jnp.maximum(cnt, 1.0)
        return x + self.relaxation_factor * upd[: x.shape[0]]

    def solve_iteration(self, data, b, st):
        x = st["x"]
        if self.num_colors == 1:
            x = self._project(data, b, x, jnp.ones_like(x, bool))
        else:
            for c in range(self.num_colors):
                x = self._project(data, b, x, data["colors"] == c)
        out = dict(st)
        out["x"] = x
        return out

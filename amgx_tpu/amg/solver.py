"""AMG as a Solver (registry name "AMG").

Analog of AlgebraicMultigrid_Solver (src/solvers/
algebraic_multigrid_solver.cu:34-59): setup delegates to AMG::setup, one
solve iteration is one multigrid cycle.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import registry
from ..solvers.base import Solver
from .hierarchy import AMG


@registry.solvers.register("AMG")
class AlgebraicMultigridSolver(Solver):
    is_smoother = False

    def __init__(self, cfg, scope="default", name="AMG"):
        super().__init__(cfg, scope, name)
        self.amg = AMG(cfg, scope)

    def solver_setup(self):
        self.amg.setup(self.A)

    def solver_resetup(self):
        self.amg.resetup(self.A)

    def _resetup_kept_static(self):
        # the hierarchy's depth and level shapes depend on the values.
        # The fused value-only resetup keeps the levels themselves;
        # any other route rebuilds them, and the hierarchy then says
        # whether what a solve program read from the old ones besides
        # its arguments is what the new ones hold (amg/signature.py)
        if getattr(self.amg, "_last_resetup_value_only", False):
            return True
        return self._resetup_rebuilt_same()

    def _resetup_rebuilt_same(self):
        amg = self.amg
        if not amg._resetup_same_static:
            return False
        # a smoother or coarse solver that bakes value-derived scalars
        # into the trace answers for itself, as anywhere in a tree
        solvers = [lv.smoother for lv in amg.levels] + [amg.coarse_solver]
        return all(s is None or s._resetup_kept_static() for s in solvers)

    def _build_solve_data(self):
        d = super()._build_solve_data()
        d["amg"] = self.amg.solve_data_part()
        return d

    def _solve_data_children(self):
        return super()._solve_data_children() + (self.amg,)

    def computes_residual(self):
        return False

    def color_steps_per_iteration(self):
        return self.amg.color_steps_per_cycle()

    def geo_transfers_per_iteration(self):
        return self.amg.geo_transfers_per_cycle()

    def swell_vreg_steps_per_iteration(self):
        return self.amg.swell_vreg_steps_per_cycle()

    def dia_smooth_per_iteration(self):
        return self.amg.dia_smooth_per_cycle()

    def swell_model_s_per_iteration(self):
        return self.amg.swell_model_s_per_cycle()

    def csr_road_nnz_per_iteration(self):
        return self.amg.csr_road_nnz_per_cycle()

    def solve_init(self, data, b, x, r):
        return self._guard_init()

    def solve_iteration(self, data, b, st):
        out = dict(st)
        x_new = self.amg.cycle(data["amg"], b, st["x"])
        out["x"] = x_new
        if self.health_guards:
            # a non-finite cycle output means the hierarchy itself is
            # broken (singular coarse factor, corrupted Galerkin
            # values): BREAKDOWN, not a NaN storm at max_iters. Unused
            # (and DCE'd by XLA) when AMG runs as a preconditioner.
            out["breakdown"] = ~jnp.all(jnp.isfinite(x_new))
        return out

    def grid_stats(self):
        return self.amg.grid_stats()

    def grid_stats_dict(self):
        return self.amg.grid_stats_dict()

"""Mixed-precision defect correction (iterative refinement).

TPU-native execution strategy for full double-precision (dDDI-mode)
accuracy: the TPU has no native f64 datapath — bulk f64 vector work runs
~10x slower than f32 — so solving the whole system in f64 wastes the
machine. REFINEMENT runs the classic defect-correction loop instead
(the same scheme LAPACK dsgesv uses around an f32 LU, and the standard
mixed-precision practice in modern GPU/TPU HPC):

    r_k = b - A x_k                  (f64: one SpMV + axpy per step)
    solve  A32 d = r_k  to tol_inner (f32: any configured inner solver,
                                      e.g. FGMRES + GEO-aggregation AMG)
    x_{k+1} = x_k + d                (f64)

All heavy work (the inner Krylov loop, the AMG cycle) runs in f32 at
full vector-unit speed; the f64 cost is two fused streaming passes per
outer step. Convergence is monitored on the TRUE f64 residual, so the
reported tolerance is meaningful to 1e-14-level — unlike a pure-f32
(dFFI-mode) solve whose estimated residual drifts from the true one
near f32 epsilon.

The inner solver comes from the `preconditioner` role, matching the
nested-solver architecture of the reference (any solver can own a child
solver, src/core.cu:381-388):

    solver=REFINEMENT, tolerance=1e-10, preconditioner(in)=FGMRES,
    in:tolerance=1e-6, in:preconditioner(amg)=AMG, ...

With `solve_precision=bfloat16` this loop is the f64-RESTORING outer
shell of the mixed-precision fused path: the AMG cycle below streams
bf16 operand slabs (f32 in-kernel accumulation, ops/pallas_spmv.py),
the inner Krylov stays f32 (a bf16 Krylov basis would not converge —
flexible Krylov tolerates the reduced-precision preconditioner), and
the outer f64 defect still drives convergence to the requested
tolerance. When the policy is active the driver also accumulates the
INNER iteration count in the while_loop state and packs it onto the
stats vector (zero extra transfers), so `SolveReport.precision`
records per-precision iteration counts — the accuracy/work trade is
measured, not folklore. Unset solve_precision is bitwise-off: no
extra state leaf, jaxpr-identical to the pre-knob build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import registry
from ..errors import BadParametersError
from ..ops.spmv import residual
from ..profiling import trace_region
from .base import Solver


@registry.solvers.register("REFINEMENT")
@registry.solvers.register("DEFECT_CORRECTION")
class RefinementSolver(Solver):
    """Outer f64 defect-correction loop around an f32 inner solve."""

    is_smoother = False
    uses_preconditioner = True
    inner_dtype = jnp.float32
    # solve_data stores the child tree under "inner" (not the base's
    # "precond") — the diagnostics probe walks this key
    _child_data_key = "inner"

    def precond_operator(self, A):
        # the inner chain (and its own preconditioner tree, e.g. the AMG
        # hierarchy) builds against the reduced-precision operator
        # a leaf of the accounted set-up (telemetry/spans.py): it runs
        # before the inner chain's, and the hierarchy's, own
        with trace_region("amg.operator_cast"):
            self._A32 = A.astype(self.inner_dtype)
        return self._A32

    def solver_setup(self):
        if self.preconditioner is None:
            raise BadParametersError(
                "REFINEMENT needs an inner solver in the `preconditioner` "
                "role (e.g. preconditioner(in)=FGMRES)")
        # diag=False: the inner fn's stats are discarded each outer step
        # (only d matters); the diagnostics probe belongs to the OUTER
        # driver, which walks the tree to the AMG itself
        # its own extra stats (GMRES / FGMRES's step and basis-row
        # counts) are summed over the outer steps and handed on
        self._inner_fn = self.preconditioner._build_solve_fn(
            diag=False, extras=True)

    def _build_solve_data(self):
        # overrides the base: the inner data is the f32 solve tree; the
        # outer operator is only ever SpMV'd (defect computation), so a
        # layout-only view suffices
        return {"A": self.A.slim_for_spmv(),
                "inner": self.preconditioner.solve_data_part()}

    def computes_residual(self):
        return True

    def solve_init(self, data, b, x0, r0):
        st = super().solve_init(data, b, x0, r0)
        if "inner_iters" in self._extra_stats_spec():
            # the accumulated inner-Krylov iteration count rides the
            # state (and, via _extra_stats, the packed stats vector):
            # per-precision accounting, and the count of colored cycles
            # a solve ran. Keyed on who reads it, so the default build
            # carries no extra leaf (bitwise-off)
            st["inner_iters"] = jnp.zeros((), jnp.float32)
        if self._inner_extras:
            st["inner_extras"] = jnp.zeros(
                (len(self._inner_extras),), jnp.float32)
        return st

    def solve_iteration(self, data, b, st):
        # scopes: the outer loop's own f64 work, apart from the inner
        # solve's (which carries its own krylov.* / amg.* names)
        x = st["x"]
        r = st["r"]        # f64 defect (maintained by the previous step)
        with jax.named_scope("refine.update"):
            r32 = r.astype(self.inner_dtype)
        d32, istats = self._inner_fn(data["inner"], r32,
                                     jnp.zeros_like(r32))
        with jax.named_scope("refine.update"):
            x = x + d32.astype(x.dtype)
        out = dict(st)
        out["x"] = x
        with jax.named_scope("refine.defect"):
            out["r"] = residual(data["A"], x, b)         # true f64 residual
        if "inner_iters" in st:
            # istats[0] is the inner fn's iteration count (the packed
            # stats layout _build_solve_fn emits)
            out["inner_iters"] = st["inner_iters"] + \
                istats[0].astype(jnp.float32)
        if self._inner_extras:
            # the tail of the inner stats, by the spec that packed it
            out["inner_extras"] = st["inner_extras"] + \
                istats[-len(self._inner_extras):].astype(jnp.float32)
        return out

    @property
    def _inner_extras(self) -> tuple:
        return tuple(self.preconditioner._extra_stats_spec())

    # -- per-precision accounting (solve_precision policy) --------------
    def _extra_stats_spec(self):
        # who reads the inner count: the precision report, and the
        # counter of color steps (which are per INNER iteration)
        counted = self._precision_policy.active \
            or self.color_steps_per_iteration() > 0
        return (("inner_iters",) if counted else ()) + self._inner_extras

    def _extra_stats(self, final_state):
        own = (final_state["inner_iters"],) \
            if "inner_iters" in final_state else ()
        return own + tuple(final_state.get("inner_extras", ()))

    def _precision_block(self, res):
        block = super()._precision_block(res)
        if block is None:
            return None
        block["inner_dtype"] = str(jnp.dtype(self.inner_dtype).name)
        if res.extra_stats is not None:
            block["inner_iterations"] = int(round(
                res.extra_stats.get("inner_iters", 0.0)))
        return block

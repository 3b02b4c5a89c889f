"""Multigrid cycles: V, W, F, CG (K-cycle), CG-flex.

Analog of src/cycles/ (fixed_cycle.cu:25-248 implements presmooth ->
residual -> restrict -> recurse -> prolongate+correct -> postsmooth;
v/w/f/cg_cycle.cu choose the recursion shape; registry
src/core.cu:631-635). Here the recursion is plain Python unrolled at
trace time over the static hierarchy depth, so a whole cycle is one XLA
program.

Every stage is traced under a `jax.named_scope` so that a device op can
be put down to its level and stage (telemetry/programs.py reads the
scopes back from the compiled program; benchmark/scope_metrics.py joins
them with a trace): `amg.L<k>` round everything a level does,
`amg.L<k>.presmooth` / `.restrict` / `.prolong` / `.postsmooth` round
the four stages, `amg.coarse` round the coarsest solve. A scope is
metadata: it adds no op and renames no kernel. The composition is the
same on every backend: nothing chooses it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import blas
from ..ops.spmv import residual, spmv
from ..ops.stencil import level_operator as _level_A
from ..telemetry import diagnostics as _diag


def _smooth(level, data, b, x, sweeps: int):
    if sweeps <= 0 or level.smoother is None:
        return x
    return level.smoother.smooth(data["smoother"], b, x, sweeps)


def _smooth_residual(level, data, b, x, sweeps: int):
    """Presmooth + residual as ONE smoother call: the damped-relaxation
    smoothers fuse the final sweep with the residual SpMV (and all
    sweeps with each other) into single-pass kernels on DIA/SWELL
    levels (ops/smooth.py), so the cycle's hottest pair costs one HBM
    pass over A instead of sweeps+1. Smoothers without a fused form
    compose exactly what this replaced (Solver.smooth_residual)."""
    if sweeps <= 0 or level.smoother is None:
        # matrix-free levels rebuild the operator in-trace
        # (ops/stencil.level_operator); slab levels pass through
        return x, residual(_level_A(data), x, b)
    return level.smoother.smooth_residual(data["smoother"], b, x, sweeps)


def _prolongate_correct(level, data, x, xc):
    """x + P xc, the coarse-grid correction. A level class that defines
    `prolongate_correct` does the add inside its own transfer (GEO
    aggregation levels: one pass over x instead of a prolongation and
    an add); resolved through the CLASS, so a `__getattr__`-delegating
    wrapper (distributed consolidation) keeps its own `prolongate`."""
    fn = getattr(type(level), "prolongate_correct", None)
    if fn is None:
        return x + level.prolongate(data, xc)
    return fn(level, data, x, xc)


def _smooth_restrict(level, data, b, x, sweeps: int, lvl: int):
    """Presmooth + residual, then the restriction: (x', R r)."""
    with jax.named_scope(f"amg.L{lvl}.presmooth"):
        x, r = _smooth_residual(level, data, b, x, sweeps)
    with jax.named_scope(f"amg.L{lvl}.restrict"):
        return x, level.restrict(data, r)


def _correct_smooth(level, data, b, x, xc, sweeps: int, lvl: int,
                    rec=None):
    """Coarse-grid correction, then the postsmooth. `rec` (a
    diagnostics probe, telemetry/diagnostics.py) records the stage
    residuals on either side of the smoother."""
    with jax.named_scope(f"amg.L{lvl}.prolong"):
        x = _prolongate_correct(level, data, x, xc)
    if rec is not None:
        rec.record(lvl, 2, _level_A(data), x, b)
    with jax.named_scope(f"amg.L{lvl}.postsmooth"):
        x = _smooth(level, data, b, x, sweeps)
    if rec is not None:
        rec.record(lvl, 3, _level_A(data), x, b)
    return x


def apply_coarse_solver(cs, data, bc, xc, coarsest_sweeps: int):
    """Coarsest-level dispatch (launchCoarseSolver analog,
    include/amg_level.h:229-242). Relaxation-type coarse solvers run
    `coarsest_sweeps` sweeps (reference parameter); direct/Krylov coarse
    solvers use their own apply. Shared with the distributed coarse
    solver so both paths stay in lockstep."""
    if cs.name in ("NOSOLVER", "DUMMY"):
        # Dummy_Solver zero-fills x (dummy_solver.cu:22-31): NOSOLVER as
        # coarse solver means *no coarse correction*, not identity —
        # injecting the raw coarse residual destabilizes the cycle
        return xc
    if cs.is_smoother and cs.name != "DENSE_LU_SOLVER":
        return cs.smooth(data, bc, xc, coarsest_sweeps)
    return cs.apply(data, bc)


def _coarse_solve(amg, data, bc, xc):
    with jax.named_scope("amg.coarse"):
        if bc.dtype == jnp.bfloat16:
            # the coarse solve stays f32+ (precision.py policy keeps the
            # coarse-solver payload at f32): a bf16 cycle upcasts the
            # coarse rhs around the solve and rounds the correction back
            out = apply_coarse_solver(
                amg.coarse_solver, data["coarse"],
                bc.astype(jnp.float32), xc.astype(jnp.float32),
                amg.coarsest_sweeps)
            return out.astype(bc.dtype)
        return apply_coarse_solver(amg.coarse_solver, data["coarse"], bc,
                                   xc, amg.coarsest_sweeps)


def _cycle(amg, shape: str, data, lvl: int, b, x):
    """FixedCycle::cycle analog. `shape` in {V, W, F}; recursion count per
    level: V=1, W=2, F=(F then V)."""
    levels = amg.levels
    if lvl == len(levels):
        return _coarse_solve(amg, data, b, x)
    # convergence diagnostics (telemetry/diagnostics.py): while a probe
    # cycle is being traced, record the level's stage residual norms.
    # `rec` is None for every normal cycle trace — the probe is a
    # separate trace at the end of the solve program.
    rec = _diag.current()
    with jax.named_scope(f"amg.L{lvl}"):
        level = levels[lvl]
        ldata = data["levels"][lvl]
        if rec is not None:
            rec.record(lvl, 0, _level_A(ldata), x, b)
        x, bc = _smooth_restrict(level, ldata, b, x,
                                 amg._sweeps(lvl, pre=True), lvl)
        if rec is not None:
            rec.record(lvl, 1, _level_A(ldata), x, b)
        xc = jnp.zeros_like(bc)
        if shape == "V":
            xc = _cycle(amg, "V", data, lvl + 1, bc, xc)
        elif shape == "W":
            xc = _cycle(amg, "W", data, lvl + 1, bc, xc)
            if lvl + 1 < len(levels):   # second visit (W shape)
                xc = _cycle(amg, "W", data, lvl + 1, bc, xc)
        elif shape == "F":
            xc = _cycle(amg, "F", data, lvl + 1, bc, xc)
            if lvl + 1 < len(levels):   # F = one F-visit then one V-visit
                xc = _cycle(amg, "V", data, lvl + 1, bc, xc)
        else:
            raise ValueError(f"unknown fixed cycle {shape!r}")
        return _correct_smooth(level, ldata, b, x, xc,
                               amg._sweeps(lvl, pre=False), lvl, rec)


def _kcycle(amg, data, lvl: int, b, x, flex: bool):
    """CG / CGF cycle (cg_cycle.cu, cg_flex_cycle.cu): the coarse-grid
    correction is accelerated by `cycle_iters` steps of (flexible) CG
    whose preconditioner is the next-coarser cycle."""
    if lvl == len(amg.levels):
        return _coarse_solve(amg, data, b, x)
    with jax.named_scope(f"amg.L{lvl}"):
        levels = amg.levels
        level = levels[lvl]
        ldata = data["levels"][lvl]
        rec = _diag.current()
        if rec is not None:
            rec.record(lvl, 0, _level_A(ldata), x, b)
        x, bc = _smooth_restrict(level, ldata, b, x,
                                 amg._sweeps(lvl, pre=True), lvl)
        if rec is not None:
            rec.record(lvl, 1, _level_A(ldata), x, b)
        Ac_data_lvl = lvl + 1

        def M(v):
            return _kcycle(amg, data, Ac_data_lvl, v, jnp.zeros_like(v), flex)

        def Ac_mv(v):
            if Ac_data_lvl == len(levels):
                if v.dtype == jnp.bfloat16:
                    # the coarsest operator stays f32+ under a bf16 cycle
                    # (precision policy) — upcast the matvec and round
                    # back so the K-cycle recurrence keeps one dtype
                    return spmv_coarsest(
                        amg, data, v.astype(jnp.float32)).astype(v.dtype)
                return spmv_coarsest(amg, data, v)
            # matrix-free coarse levels materialize in-trace for the
            # K-cycle matvec (VPU work instead of a resident slab)
            return spmv(_level_A(data["levels"][Ac_data_lvl]), v)

        # a few steps of preconditioned CG on the coarse equation
        xc = jnp.zeros_like(bc)
        rc = bc
        z = M(rc)
        p = z
        rz = blas.dot(rc, z)
        k_iters = max(amg.cycle_iters, 1)
        for it in range(k_iters):
            Ap = Ac_mv(p)
            denom = blas.dot(p, Ap)
            alpha = rz / jnp.where(denom == 0, 1.0, denom) * (denom != 0)
            xc = xc + alpha * p
            rc_old = rc
            rc = rc - alpha * Ap
            if it + 1 == k_iters:
                break   # last update: skip the unused trailing M()/beta/p
            z = M(rc)
            rz_new = blas.dot(rc, z)
            if flex:
                # flexible (Polak-Ribiere) beta tolerates a varying M
                num = blas.dot(rc - rc_old, z)
            else:
                # Fletcher-Reeves: the beta numerator IS the next rz —
                # reuse it instead of computing the same reduction twice
                num = rz_new
            beta = num / jnp.where(rz == 0, 1.0, rz) * (rz != 0)
            rz = rz_new
            p = z + beta * p
        return _correct_smooth(level, ldata, b, x, xc,
                               amg._sweeps(lvl, pre=False), lvl, rec)


def spmv_coarsest(amg, data, v):
    """SpMV with the coarsest matrix (its CSR lives in the coarse-solver
    data only when that solver keeps it; fall back to the stored matrix).
    Under a DistributedCoarseSolver the coarsest matrix is replicated
    while v is shard-local: gather, apply, keep the local slice (the
    K-cycle's coarse-grid matvec, exact_coarse_solve layout)."""
    cd = data["coarse"]
    cs = amg.coarse_solver
    from ..distributed.amg import DistributedCoarseSolver
    if isinstance(cs, DistributedCoarseSolver):
        return cs.gather_apply_slice(lambda bc: spmv(cd["A"], bc), v)
    return spmv(cd["A"], v)


def run_cycle(amg, name: str, data, b, x):
    name = name.upper()
    if name in ("V", "W", "F"):
        return _cycle(amg, name, data, 0, b, x)
    if name == "CG":
        return _kcycle(amg, data, 0, b, x, flex=False)
    if name == "CGF":
        return _kcycle(amg, data, 0, b, x, flex=True)
    raise ValueError(f"unknown cycle {name!r}")

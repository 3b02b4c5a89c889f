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
the stages (a fused kernel that spans two takes both names:
`.presmooth_restrict`, `.prolong_postsmooth`), `amg.coarse` round the
coarsest solve, `amg.tail.L<k>` round the VMEM-resident coarse tail
entered at level k. A scope is metadata: it adds no op and renames no
kernel.
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


def _fusion_caps(level, data):
    """Fusion capabilities a level ADVERTISES for its solve-data — the
    single gate the cycle consults before invoking any fused hook
    (`restrict_fused` / `prolongate_smooth`). Levels declare support
    via `supports_fusion(data)` returning a capability collection
    ("restrict", "prolongate"). Resolved through the CLASS (MRO), not
    instance getattr: a `__getattr__`-delegating wrapper must define
    `supports_fusion` (and the hooks) EXPLICITLY to advertise anything
    — its inner level answering through delegation would claim the
    WRONG transfer space (the level's shard-local R/P instead of the
    wrapper's gather/compact). A class that defines neither advertises
    nothing and is never called, so new hooks cannot re-introduce the
    AttributeError-on-distributed-levels class of bug PR 5 fixed."""
    fn = getattr(type(level), "supports_fusion", None)
    if fn is None:
        return ()
    return fn(level, data)


def _prolongate_correct(level, data, x, xc):
    """x + P xc, the coarse-grid correction. A level class that defines
    `prolongate_correct` does the add inside its own transfer (GEO
    aggregation levels: one pass over x instead of a prolongation and
    an add); resolved through the CLASS, as _fusion_caps is, so a
    `__getattr__`-delegating wrapper keeps its own `prolongate`."""
    fn = getattr(type(level), "prolongate_correct", None)
    if fn is None:
        return x + level.prolongate(data, xc)
    return fn(level, data, x, xc)


def _smooth_restrict(amg, level, data, b, x, sweeps: int, lvl: int):
    """Presmooth + restriction: with cycle_fusion, aggregation/DIA
    levels emit the segment-summed coarse rhs from the presmoother
    kernel's epilogue (ops/smooth.py) — the residual never round-trips
    HBM and `level.restrict` disappears from the trace — classical
    DIA levels do the same through their WEIGHTED row-segment slabs
    (bc = R r summed inside the kernel, general CSR interpolation),
    and distributed DIA levels run the halo-folded per-shard kernel
    (distributed/fused.py) before their explicit sharded restriction.
    Everything else (cycle_fusion=0, non-DIA levels, unsupported
    layouts) composes exactly the prior smooth_residual -> restrict
    pair."""
    if amg.cycle_fusion and sweeps > 0 and \
            "restrict" in _fusion_caps(level, data):
        with jax.named_scope(f"amg.L{lvl}.presmooth_restrict"):
            out = level.restrict_fused(data, b, x, sweeps)
        if out is not None:
            return out
    with jax.named_scope(f"amg.L{lvl}.presmooth"):
        x, r = _smooth_residual(level, data, b, x, sweeps)
    with jax.named_scope(f"amg.L{lvl}.restrict"):
        return x, level.restrict(data, r)


def _prolongate_smooth(amg, level, data, b, x, xc, sweeps: int, lvl: int,
                       want_dot: bool = False):
    """Prolongation + correction + postsmooth: with cycle_fusion,
    aggregation AND classical DIA levels fold x + P xc into the
    postsmoother kernel's first application (ops/smooth.py —
    aggregate-id gather or the weighted multi-entry CSR-row gather),
    removing the correction add's full-vector pass. Falls back to the
    prior x + prolongate -> smooth compose bit-for-bit.

    With want_dot (the cycle-borne reduction, Krylov shell fusion) the
    return is (x', dot) where dot = x'.b from the postsmoother kernel's
    epilogue — PCG reads it as r.z since the cycle's rhs is r and its
    output is z — or (x', None) when no fused hook carries it; the
    want_dot kwarg is only passed to level hooks when True, so hook
    signatures that predate it keep working un-updated."""
    if amg.cycle_fusion and sweeps > 0 and \
            "prolongate" in _fusion_caps(level, data):
        with jax.named_scope(f"amg.L{lvl}.prolong_postsmooth"):
            if want_dot:
                out = level.prolongate_smooth(data, b, x, xc, sweeps,
                                              want_dot=True)
            else:
                out = level.prolongate_smooth(data, b, x, xc, sweeps)
        if out is not None:
            return out
    with jax.named_scope(f"amg.L{lvl}.prolong"):
        x = _prolongate_correct(level, data, x, xc)
    with jax.named_scope(f"amg.L{lvl}.postsmooth"):
        x = _smooth(level, data, b, x, sweeps)
    return (x, None) if want_dot else x


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
            # the coarse tail stays f32+ (precision.py policy keeps the
            # coarse-solver payload at f32): a bf16 cycle upcasts the
            # coarse rhs around the solve and rounds the correction back
            out = apply_coarse_solver(
                amg.coarse_solver, data["coarse"],
                bc.astype(jnp.float32), xc.astype(jnp.float32),
                amg.coarsest_sweeps)
            return out.astype(bc.dtype)
        return apply_coarse_solver(amg.coarse_solver, data["coarse"], bc,
                                   xc, amg.coarsest_sweeps)


def _cycle(amg, shape: str, data, lvl: int, b, x, want_dot: bool = False):
    """FixedCycle::cycle analog. `shape` in {V, W, F}; recursion count per
    level: V=1, W=2, F=(F then V). want_dot asks the ENTRY level's final
    kernel (postsmoother or whole-cycle VMEM tail) for the x'.b dot
    epilogue; recursion below the entry level never requests it."""
    levels = amg.levels
    if lvl == len(levels):
        out = _coarse_solve(amg, data, b, x)
        return (out, None) if want_dot else out
    # convergence diagnostics (telemetry/diagnostics.py): while a probe
    # cycle is being traced, record the level's stage residual norms
    # and compose the correction/postsmooth boundary explicitly so each
    # stage exists to measure. `rec` is None for every normal cycle
    # trace — the probe is a separate trace at the end of the solve
    # program, so the solve iterations keep their fused kernels.
    rec = _diag.current()
    if amg.cycle_fusion and rec is None:
        # VMEM-resident coarse tail: when every level from here down
        # fits VMEM together, the whole sub-cycle (smooth -> restrict
        # -> ... -> coarsest solve -> ... -> prolongate -> smooth) is
        # ONE pallas_call instead of ~10 tiny dispatches per cycle
        from ..ops.smooth import coarse_tail_cycle
        with jax.named_scope(f"amg.tail.L{lvl}"):
            out = coarse_tail_cycle(amg, shape, data, lvl, b, x,
                                    want_dot=want_dot)
        if out is not None:
            return out
    with jax.named_scope(f"amg.L{lvl}"):
        level = levels[lvl]
        ldata = data["levels"][lvl]
        if rec is not None:
            rec.record(lvl, 0, _level_A(ldata), x, b)
        x, bc = _smooth_restrict(amg, level, ldata, b, x,
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
        if rec is not None:
            x = _prolongate_correct(level, ldata, x, xc)
            rec.record(lvl, 2, _level_A(ldata), x, b)
            x = _smooth(level, ldata, b, x, amg._sweeps(lvl, pre=False))
            rec.record(lvl, 3, _level_A(ldata), x, b)
            return (x, None) if want_dot else x
        return _prolongate_smooth(amg, level, ldata, b, x, xc,
                                  amg._sweeps(lvl, pre=False), lvl,
                                  want_dot=want_dot)


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
        x, bc = _smooth_restrict(amg, level, ldata, b, x,
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
        if rec is not None:
            x = _prolongate_correct(level, ldata, x, xc)
            rec.record(lvl, 2, _level_A(ldata), x, b)
            x = _smooth(level, ldata, b, x, amg._sweeps(lvl, pre=False))
            rec.record(lvl, 3, _level_A(ldata), x, b)
            return x
        return _prolongate_smooth(amg, level, ldata, b, x, xc,
                                  amg._sweeps(lvl, pre=False), lvl)


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


def run_cycle_dot(amg, name: str, data, b, x):
    """Cycle application that ALSO asks for the x'.b dot epilogue from
    the cycle's last kernel (the Krylov shell's cycle-borne r.z).
    Returns (x', dot) with dot=None whenever the cycle cannot carry it
    — K-cycles, diagnostics probes, unfused last levels — so callers
    fall back to an explicit reduction."""
    name = name.upper()
    if name in ("V", "W", "F"):
        return _cycle(amg, name, data, 0, b, x, want_dot=True)
    return run_cycle(amg, name, data, b, x), None

"""Convergence doctor: diagnose a slow AMG configuration down to its
bottleneck level.

ROADMAP item 2's standing question — WHY is the classical path slow? —
used to be answered by staring at residual histories. The diagnostics
layer (telemetry/diagnostics.py, `diagnostics=1`) answers it
structurally: one in-trace probe cycle records the residual norm at
every level's cycle stages, and the report derives per-level reduction
factors, smoother effectiveness, a coarse-correction quality column and
a bottleneck-level attribution.

This example sets up a DELIBERATELY weak classical configuration (an
overdamped Jacobi smoother plus an aggressive strength threshold — a
classic mistuning) next to a healthy reference, solves the same 3D
Poisson system with both, and prints each hierarchy's diagnosis:

    python examples/convergence_doctor.py

Look for: the weak config's higher asymptotic convergence factor, the
per-level `level_reduction` column pointing at the bottleneck level,
and the `smoother_effectiveness` column showing WHERE the overdamped
smoother stops biting — that's the knob to fix first.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp

import amgx_tpu as amgx
from amgx_tpu.config import Config

amgx.initialize()

N = 24            # 24^3 = 13.8k rows: small enough to run anywhere

BASE = (
    "solver(s)=PCG, s:max_iters=120, s:tolerance=1e-8,"
    " s:convergence=RELATIVE_INI, s:monitor_residual=1,"
    " s:store_res_history=1, s:preconditioner(amg)=AMG,"
    " amg:algorithm=CLASSICAL, amg:selector=PMIS,"
    " amg:interpolator=D1, amg:presweeps=1, amg:postsweeps=1,"
    " amg:max_iters=1, amg:coarse_solver=DENSE_LU_SOLVER,"
    " amg:min_coarse_rows=32, amg:max_levels=12, amg:diagnostics=1")

CONFIGS = {
    # healthy reference: L1-Jacobi with the stock strength threshold
    "healthy": BASE + ", amg:smoother(sm)=JACOBI_L1, sm:max_iters=1,"
               " amg:strength_threshold=0.25",
    # mistuned: a badly overdamped plain Jacobi (relaxation_factor far
    # below useful) + a strength threshold that thins interpolation —
    # the cycle limps, and the doctor should say WHERE
    "mistuned": BASE + ", amg:smoother(sm)=BLOCK_JACOBI,"
                " sm:max_iters=1, sm:relaxation_factor=0.15,"
                " amg:strength_threshold=0.7",
}


def doctor(tag, cfg_str):
    A = amgx.gallery.poisson("7pt", N, N, N).init()
    b = jnp.ones(A.num_rows)
    slv = amgx.create_solver(Config.from_string(cfg_str))
    slv.setup(A)
    res = slv.solve(b)
    rep = res.report
    d = rep.diagnostics
    print(f"\n=== {tag} ===")
    print(f"status={res.status} iters={res.iterations} "
          f"solve={res.solve_time:.3f}s")
    h = rep.hierarchy
    print(f"hierarchy: {h['num_levels']} levels, "
          f"operator complexity {h['operator_complexity']:.2f}")
    acf = d["asymptotic_convergence_factor"]
    print(f"asymptotic convergence factor: "
          f"{'n/a' if acf is None else f'{acf:.3f}'} "
          f"(lower is better; >0.9 means the cycle barely bites)")
    print("  lvl     rows  level_red  presmooth  correction  "
          "postsmooth  smoother_eff")
    for row, hrow in zip(d["levels"], h["levels"]):
        def f(v):
            return "     n/a" if v is None else f"{v:8.3f}"
        print(f"  {row['level']:3d} {hrow['rows']:8d} "
              f"{f(row['level_reduction'])}   {f(row['presmooth_reduction'])}"
              f"   {f(row['correction_reduction'])}"
              f"    {f(row['postsmooth_reduction'])}"
              f"     {f(row['smoother_effectiveness'])}")
    bl = d["bottleneck_level"]
    print(f"bottleneck level: {bl} "
          f"(level_reduction {d['bottleneck_reduction']:.3f})")
    if bl is not None:
        # the shared diagnostics->deltas mapping (the serving
        # autotuner's candidate generator reads the same suggestions);
        # the doctor prints each distinct hint sentence once, in rule
        # order — the historical output, now derived from one source
        from amgx_tpu.telemetry.diagnostics import suggest_config_deltas
        hints = []
        for s in suggest_config_deltas(d):
            if s["hint"] and s["hint"] not in hints:
                hints.append(s["hint"])
        if hints:
            print("doctor says: " + "; ".join(hints))
    return res


if __name__ == "__main__":
    healthy = doctor("healthy", CONFIGS["healthy"])
    mistuned = doctor("mistuned", CONFIGS["mistuned"])
    print(f"\nhealthy converged in {healthy.iterations} iters, "
          f"mistuned took {mistuned.iterations} "
          f"({mistuned.status}) — the table above says why.")

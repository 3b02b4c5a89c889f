#!/usr/bin/env python3
"""Does the program still start on the chip?

Drives the main path once on an attached TPU through the entry points a
user calls, checks what comes out against references that do not share
code with ops/, and prints one JSON line per phase. One process, no
child that imports JAX, no way to run on a CPU: `main` refuses anything
but a TPU before anything is built. The first failed check ends the run
with a non-zero exit code.

    python chip_smoke.py               # one chip: flagship + C-API phases
    python chip_smoke.py --multichip   # four chips: the distributed solve
                                       # and the single-chip solve it is
                                       # compared with, nothing else

The phases are plain functions of a size, so tests/test_chip_compile.py
can call them at 16^3 under the Pallas interpreter. Walls printed here
are information for whoever reads the log: a smoke run is not a
measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

FLAGSHIP_TOL = 1e-8           # presets.FLAGSHIP's own tolerance
PLAIN = ", fused_smoother=0, krylov_fusion=0, matrix_free=0"
CLASSICAL_CFG = "configs/PCG_CLASSICAL_V_JACOBI.json"
# the distributed example's solver (examples/amgx_mpi_poisson7.py)
DIST_CFG = (
    "config_version=2, solver(s)=FGMRES, s:max_iters=100,"
    " s:tolerance=1e-8, s:convergence=RELATIVE_INI,"
    " s:gmres_n_restart=20, s:monitor_residual=1,"
    " s:preconditioner(amg)=AMG, amg:algorithm=AGGREGATION,"
    " amg:selector=SIZE_2, amg:smoother=JACOBI_L1, amg:max_iters=1,"
    " amg:coarse_solver=DENSE_LU_SOLVER, amg:min_coarse_rows=16")


# 64x64x256 (1.05 M rows, one 64x64x64 slab per chip), not 128x128x512:
# compiled for a described v5e the single-chip program it is compared
# with needs 6.5 GiB of temporaries at this size in f64 (memory_analysis)
# and would need eight times that at the larger one — more than a chip
# has.
MULTICHIP_GRID = (64, 64, 256)


class SmokeFailure(SystemExit):
    """A failed check: printed, then exit code 1."""

    def __init__(self, msg):
        print(json.dumps({"ok": False, "failed": msg}), flush=True)
        super().__init__(1)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# references that share nothing with amgx_tpu.ops
# ---------------------------------------------------------------------------


def host_csr(A):
    """The operator as a scipy CSR matrix in f64, from the raw CSR
    arrays (external diagonal folded back in when there is one)."""
    import scipy.sparse as sp
    n = int(A.num_rows)
    M = sp.csr_matrix(
        (np.asarray(A.values, np.float64), np.asarray(A.col_indices),
         np.asarray(A.row_offsets)), shape=(n, n))
    if getattr(A, "has_external_diag", False):
        M = M + sp.diags(np.asarray(A.diag, np.float64))
    return M


def true_relres(M, x, b):
    """||b - M x||_2 / ||b||_2 on the host in f64."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - M @ x) / np.linalg.norm(b))


def _status_ok(res):
    return str(res.status).lower() == "success"


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(expect_count):
    """Refuse anything but a TPU; report what the run stands on."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu":
        # no result line: the caller's contract is "prints no result"
        print(f"chip_smoke: no TPU (jax reports {len(devs)} x {plat}); "
              "this script does not run on a CPU", file=sys.stderr)
        raise SystemExit(1)
    check(len(devs) == expect_count or expect_count == 1,
          f"device: {expect_count} chips needed, {len(devs)} visible")
    from amgx_tpu import compile_cache, native
    cache_dir = compile_cache.enable()
    native.lib(required=True)     # builds from source here, or raises
    device = {"platform": plat, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__, compile_cache=cache_dir,
         native={"loaded": True, "source_hash": native.source_hash()})
    return device


# ---------------------------------------------------------------------------
# phase 2: the flagship at full width
# ---------------------------------------------------------------------------


def _solve_census(slv, b):
    """Kernel names x counts of the traced solve program, and how many
    pallas_call eqns are in interpret mode."""
    import jax
    import jax.numpy as jnp
    from amgx_tpu.telemetry import census
    bb = jnp.asarray(b)
    jaxpr = jax.make_jaxpr(slv._build_solve_fn())(
        slv.solve_data(), bb, jnp.zeros_like(bb))
    calls = census.pallas_calls(jaxpr)
    return (census.kernel_counts(jaxpr), len(calls),
            sum(1 for c in calls if c["interpret"]))


def phase_flagship(n, seed=0, on_chip=True):
    """presets.FLAGSHIP on 7-pt n^3: setup, three solves, the plain
    path on the same system, then new coefficients -> resetup -> solve.
    `on_chip=False` is the CPU rehearsal (interpret mode allowed)."""
    import amgx_tpu as amgx
    from amgx_tpu import gallery, presets
    from amgx_tpu.config import Config
    from amgx_tpu.ops import pallas_spmv as ps

    A = gallery.poisson("7pt", n, n, n).init()
    M = host_csr(A)
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(A.num_rows)

    slv = amgx.create_solver(Config.from_string(presets.FLAGSHIP))
    t0 = time.perf_counter()
    slv.setup(A)
    setup_wall = time.perf_counter() - t0
    solves = []
    for i in range(3):
        t0 = time.perf_counter()
        res = slv.solve(b)
        x = np.asarray(res.x)
        wall = time.perf_counter() - t0
        rr = true_relres(M, x, b)
        check(_status_ok(res), f"flagship solve {i}: status {res.status}")
        check(np.all(np.isfinite(x)) and x.shape == b.shape,
              f"flagship solve {i}: x not finite / wrong shape")
        check(rr <= FLAGSHIP_TOL,
              f"flagship solve {i}: true relres {rr:.3e} > {FLAGSHIP_TOL}")
        solves.append({"iterations": int(res.iterations),
                       "true_relres": rr, "wall_s_info": wall})
    counts, n_calls, n_interp = _solve_census(slv, b)
    if on_chip:
        check(counts.get("_dia_smooth_call", 0) > 0,
              f"flagship: no dia_smooth kernel in the solve ({counts})")
        check(ps.pallas_backend() == "mosaic" and n_interp == 0,
              f"flagship: {n_interp} kernels in interpret mode")
    emit("flagship", rows=int(A.num_rows), grid=[n, n, n],
         setup_wall_s_info=setup_wall, solves=solves,
         kernel_census=counts, pallas_calls=n_calls,
         interpret_calls=n_interp,
         declined_on_chip=ps.declined_families())

    plain = amgx.create_solver(Config.from_string(presets.FLAGSHIP + PLAIN))
    plain.setup(A)
    rp = plain.solve(b)
    xp = np.asarray(rp.x)
    rrp = true_relres(M, xp, b)
    check(_status_ok(rp), f"plain path: status {rp.status}")
    check(rrp <= FLAGSHIP_TOL,
          f"plain path: true relres {rrp:.3e} > {FLAGSHIP_TOL}")
    dit = abs(int(rp.iterations) - solves[-1]["iterations"])
    check(dit <= 1, f"plain path: outer iterations differ by {dit}")
    dx = float(np.linalg.norm(x - xp) / np.linalg.norm(xp))
    check(dx <= 1e-4, f"plain path: |x - x_plain|/|x_plain| = {dx:.3e}")
    emit("flagship_vs_plain", iterations=int(rp.iterations),
         true_relres=rrp, x_rel_diff=dx,
         kernel_census=_solve_census(plain, b)[0])

    # new coefficients on the same pattern -> resetup -> solve
    A2 = A.with_values(np.asarray(A.values) * 1.75)
    if not A2.initialized:
        A2 = A2.init()
    M2 = host_csr(A2)
    t0 = time.perf_counter()
    slv.resetup(A2)
    resetup_wall = time.perf_counter() - t0
    r2 = slv.solve(b)
    rr2 = true_relres(M2, np.asarray(r2.x), b)
    check(_status_ok(r2), f"resetup solve: status {r2.status}")
    check(rr2 <= FLAGSHIP_TOL,
          f"resetup solve: true relres {rr2:.3e} > {FLAGSHIP_TOL}")
    emit("flagship_resetup", iterations=int(r2.iterations),
         true_relres=rr2, resetup_wall_s_info=resetup_wall)


# ---------------------------------------------------------------------------
# phase 3: a reference-shipped preset through the C API
# ---------------------------------------------------------------------------


def _safe(rc, *out):
    from amgx_tpu import capi
    check(rc == capi.RC.OK, f"capi: {capi.AMGX_get_error_string(rc)}")
    return out[0] if len(out) == 1 else (out if out else None)


def phase_capi_classical(n, seed=0, mode="dFFI"):
    """configs/PCG_CLASSICAL_V_JACOBI.json (classical PMIS+D2, PCG) on
    7-pt n^3 through capi upload -> setup -> solve -> download, in the
    f32 mode so the SWELL/ELL kernels and the CG shell carry it; setup
    needs the native library."""
    from amgx_tpu import capi, gallery, native
    from amgx_tpu.telemetry import metrics

    native.lib(required=True)
    A = gallery.poisson("7pt", n, n, n).init()
    M = host_csr(A)
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(A.num_rows)
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    va = np.asarray(A.values)

    _safe(capi.AMGX_initialize())
    cfg = _safe(*capi.AMGX_config_create_from_file(
        os.path.join(REPO, CLASSICAL_CFG)))
    # the shipped file prints per-iteration tables; keep stdout for the
    # JSON lines
    _safe(capi.AMGX_config_add_parameters(
        cfg, "config_version=2, main:print_solve_stats=0,"
             " amg:print_grid_stats=0"))
    rsc = _safe(*capi.AMGX_resources_create_simple(cfg))
    mtx = _safe(*capi.AMGX_matrix_create(rsc, mode))
    rhs = _safe(*capi.AMGX_vector_create(rsc, mode))
    sol = _safe(*capi.AMGX_vector_create(rsc, mode))
    slv = _safe(*capi.AMGX_solver_create(rsc, mode, cfg))
    _safe(capi.AMGX_matrix_upload_all(
        mtx, A.num_rows, int(va.shape[0]), 1, 1, ro, ci, va, None))
    _safe(capi.AMGX_vector_upload(rhs, A.num_rows, 1, b))
    t0 = time.perf_counter()
    _safe(capi.AMGX_solver_setup(slv, mtx))
    setup_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    solve_wall = time.perf_counter() - t0
    status = _safe(*capi.AMGX_solver_get_status(slv))
    iters = _safe(*capi.AMGX_solver_get_iterations_number(slv))
    x = np.asarray(_safe(*capi.AMGX_vector_download(sol)))
    tol = 1e-6                # the shipped file's "tolerance"
    # the rhs the solver saw is b rounded to the mode's vector dtype
    b_seen = b.astype(np.float32 if mode[1] == "F" else np.float64)
    rr = true_relres(M, x, b_seen)
    check(status == 0, f"capi classical: solver status {status}")
    check(np.all(np.isfinite(x)) and x.shape == b.shape,
          "capi classical: x not finite / wrong shape")
    check(rr <= tol, f"capi classical: true relres {rr:.3e} > {tol}")
    snap = metrics.snapshot()
    emit("capi_classical", config=CLASSICAL_CFG, mode=mode,
         rows=int(A.num_rows), iterations=int(iters), true_relres=rr,
         setup_wall_s_info=setup_wall, solve_wall_s_info=solve_wall,
         krylov_fused_dispatch=snap.get("krylov.fused_dispatch"))
    for h, destroy in ((slv, capi.AMGX_solver_destroy),
                       (sol, capi.AMGX_vector_destroy),
                       (rhs, capi.AMGX_vector_destroy),
                       (mtx, capi.AMGX_matrix_destroy),
                       (rsc, capi.AMGX_resources_destroy),
                       (cfg, capi.AMGX_config_destroy)):
        _safe(destroy(h))


# ---------------------------------------------------------------------------
# --multichip: the distributed solve and what it is compared with
# ---------------------------------------------------------------------------


def _shard_report(arr):
    """{device id: bytes} of an array's addressable shards."""
    out = {}
    for s in arr.addressable_shards:
        out[s.device.id] = out.get(s.device.id, 0) + int(s.data.nbytes)
    return out


def _check_spread(name, arr, R):
    rep = _shard_report(arr)
    check(len(rep) == R, f"{name}: shards on {len(rep)} devices, not {R}")
    lo, hi = min(rep.values()), max(rep.values())
    check(hi <= 1.25 * lo, f"{name}: shard bytes uneven {rep}")
    return rep


def phase_multichip(nx, ny, nz, R=4, seed=0, mode="dDDI"):
    """7-pt nx*ny*nz in R z-slabs, one per device, through the capi
    distributed upload as examples/amgx_mpi_poisson7.py does, against
    the single-device solve of the same system."""
    import jax
    from amgx_tpu import capi, gallery

    A = gallery.poisson("7pt", nx, ny, nz).init()
    M = host_csr(A)
    n = int(A.num_rows)
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(n)
    ro = np.asarray(A.row_offsets)
    ci = np.asarray(A.col_indices)
    va = np.asarray(A.values)
    n_local = -(-n // R)
    offsets = np.minimum(np.arange(R + 1) * n_local, n)
    tol = 1e-8

    _safe(capi.AMGX_initialize())
    cfg = _safe(*capi.AMGX_config_create(DIST_CFG))
    rsc = _safe(*capi.AMGX_resources_create_simple(cfg))

    # distributed: per-rank pieces with global column ids
    mtx = _safe(*capi.AMGX_matrix_create(rsc, mode))
    rhs = _safe(*capi.AMGX_vector_create(rsc, mode))
    sol = _safe(*capi.AMGX_vector_create(rsc, mode))
    dist = _safe(*capi.AMGX_distribution_create(cfg))
    _safe(capi.AMGX_distribution_set_partition_data(
        dist, capi.AMGX_DIST_PARTITION_OFFSETS, offsets))
    for r in range(R):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        s, e = int(ro[lo]), int(ro[hi])
        _safe(capi.AMGX_matrix_upload_distributed(
            mtx, n, hi - lo, e - s, 1, 1, ro[lo:hi + 1] - ro[lo],
            ci[s:e], va[s:e], None, dist))
    slv = _safe(*capi.AMGX_solver_create(rsc, mode, cfg))
    t0 = time.perf_counter()
    _safe(capi.AMGX_solver_setup(slv, mtx))
    setup_wall = time.perf_counter() - t0
    _safe(capi.AMGX_vector_bind(rhs, mtx))
    for r in range(R):
        lo, hi = int(offsets[r]), int(offsets[r + 1])
        _safe(capi.AMGX_vector_upload_distributed(
            rhs, hi - lo, 1, b[lo:hi]))
    t0 = time.perf_counter()
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv, rhs, sol))
    solve_wall = time.perf_counter() - t0
    its_d = _safe(*capi.AMGX_solver_get_iterations_number(slv))
    st_d = _safe(*capi.AMGX_solver_get_status(slv))
    x_d = np.asarray(_safe(*capi.AMGX_vector_download(sol)))
    rr_d = true_relres(M, x_d, b)
    check(st_d == 0, f"distributed: solver status {st_d}")
    check(rr_d <= tol, f"distributed: true relres {rr_d:.3e} > {tol}")

    # where the data lives and what the program contains: the C API
    # hands back host arrays, so look at the DistributedSolver behind
    # the handle and run its compiled program once more on the mesh
    from amgx_tpu.distributed.partition import partition_vector
    ds = capi._get(slv, capi._CSolver).solver
    fine = jax.tree_util.tree_leaves(ds._data["A"])
    spread_A = _check_spread(
        "fine operator", max(fine, key=lambda a: a.nbytes), R)
    bl = ds._place(partition_vector(b, R, ds.part.n_local))
    compiled = ds._fn.lower(ds._data, bl, bl * 0).compile()
    x_sh, _stats = compiled(ds._data, bl, bl * 0)
    spread_x = _check_spread("solution", x_sh, R)
    hlo = compiled.as_text()
    for coll in ("collective-permute", "all-reduce"):
        check(coll in hlo, f"distributed: no {coll} in the program")
    emit("distributed", grid=[nx, ny, nz], rows=n, ranks=R,
         iterations=int(its_d), true_relres=rr_d,
         setup_wall_s_info=setup_wall, solve_wall_s_info=solve_wall,
         fine_operator_bytes_by_device=spread_A,
         solution_bytes_by_device=spread_x,
         collective_permute=hlo.count("collective-permute"),
         all_reduce=hlo.count("all-reduce"))

    # the single-device solve of the same system
    mtx1 = _safe(*capi.AMGX_matrix_create(rsc, mode))
    rhs1 = _safe(*capi.AMGX_vector_create(rsc, mode))
    sol1 = _safe(*capi.AMGX_vector_create(rsc, mode))
    slv1 = _safe(*capi.AMGX_solver_create(rsc, mode, cfg))
    _safe(capi.AMGX_matrix_upload_all(
        mtx1, n, int(va.shape[0]), 1, 1, ro, ci, va, None))
    _safe(capi.AMGX_vector_upload(rhs1, n, 1, b))
    _safe(capi.AMGX_solver_setup(slv1, mtx1))
    _safe(capi.AMGX_solver_solve_with_0_initial_guess(slv1, rhs1, sol1))
    its_1 = _safe(*capi.AMGX_solver_get_iterations_number(slv1))
    st_1 = _safe(*capi.AMGX_solver_get_status(slv1))
    x_1 = np.asarray(_safe(*capi.AMGX_vector_download(sol1)))
    rr_1 = true_relres(M, x_1, b)
    check(st_1 == 0, f"single device: solver status {st_1}")
    check(rr_1 <= tol, f"single device: true relres {rr_1:.3e} > {tol}")
    check(abs(int(its_d) - int(its_1)) <= 1,
          f"iterations differ: distributed {its_d}, single {its_1}")
    dx = float(np.linalg.norm(x_d - x_1) / np.linalg.norm(x_1))
    check(dx <= 1e-4, f"|x_dist - x_one|/|x_one| = {dx:.3e}")
    emit("distributed_vs_single", iterations_single=int(its_1),
         true_relres_single=rr_1, x_rel_diff=dx)


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the distributed solve and "
                         "the single-chip solve it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = phase_device(4 if args.multichip else 1)
    if args.multichip:
        phase_multichip(*MULTICHIP_GRID, R=4, seed=args.seed)
    else:
        phase_flagship(128, seed=args.seed)
        phase_capi_classical(64, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
